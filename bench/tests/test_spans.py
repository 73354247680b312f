"""The split of a traced window by the program's own spans
(``bench/spans.py``), on the CPU.

    JAX_PLATFORMS=cpu python -m pytest -q bench/tests/test_spans.py

The planner cells run tiny with the program's spans on; the
reduction is checked on the recorded trace and on hand-made spans.
"""
from __future__ import annotations

import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")

import jax  # noqa: E402
import pytest  # noqa: E402

sys.path[:0] = [os.path.join(os.path.dirname(__file__), "..", "..", "src"),
                os.path.join(os.path.dirname(__file__), "..", "..")]

from bench import spans  # noqa: E402
from bench import trace as tr  # noqa: E402
from bench.tests.test_cells import SECONDS, tiny  # noqa: E402


def test_selftest_on_the_recorded_trace():
    assert spans.selftest() == 0


def test_innermost_looks_past_any_number_of_closed_siblings():
    ss = [("outer", 0, 100)] + [(f"kid{i}", 2 * i, 2 * i + 1)
                                for i in range(20)]
    ss.append(("late", 60, 70))
    got = spans.innermost([2.5, 40.5, 65, 80, 120], ss)
    assert [g[0] if g else None for g in got] == [
        "kid1", "outer", "late", "outer", None]


def test_idle_splits_a_gap_at_span_edges():
    ns = 1.0
    ops = [("op", 0.0, 10 * ns, "op"), ("op", 90 * ns, 100 * ns, "op")]
    ss = [("sweep", 5 * ns, 95 * ns), ("repro.plan.rewrite", 10 * ns, 40 * ns),
          ("repro.plan.model", 40 * ns, 95 * ns)]
    idle = spans.idle_by_span(ops, (0.0, 100 * ns), ss)
    assert {k: round(v * 1e9) for k, v in idle.items()} == {
        "repro.plan.rewrite": 30, "repro.plan.model": 50}


def test_readers_read_nothing_where_the_program_has_nothing():
    window = (0.0, 1e9)
    rec = {"units": 4, "counters": {"device.syncs": 36},
           "trace": {"_trace": {"window": window, "program_spans": [
               ("repro.plan.model", 0.0, 0.4e9, 0),
               ("repro.device.sync", 0.1e9, 0.2e9, 0),
               ("repro.device.sync", 0.5e9, 0.6e9, 0)]}}}
    assert spans.span_ms(rec, "repro.plan.model") == pytest.approx(75.0)
    assert spans.span_ms(rec, "repro.device.sync") == pytest.approx(50.0)
    assert spans.span_ms(rec, "repro.plan.arena") is None
    assert spans.counter_per_unit(rec, "device.syncs") == 9.0
    assert spans.counter_per_unit(rec, "device.h2d_bytes", 1e-6) is None
    parent = {"units": 4, "trace": {"_trace": {"window": window}}}
    assert spans.span_ms(parent, "repro.plan.model") is None
    assert spans.counter_per_unit(parent, "device.syncs") is None


def _split(name, tmp_path, seed=2 ** 31 + 11):
    run = spans.measure(tiny(name), seed, SECONDS, jax.devices()[:1],
                        str(tmp_path))
    path = tr.find_trace(str(tmp_path))
    line = next(ln.name for p in jax.profiler.ProfileData.from_file(
        path).planes if p.name == "/host:CPU" for ln in p.lines
        if ln.name.startswith("tf_XLAPjRtCpuClient"))
    return spans.breakdown(path, 1, run, device_prefix="/host:CPU",
                           op_lines=(line,))


def test_amg_sweep_splits_by_layer(tmp_path):
    b = _split("amg-sweep", tmp_path)
    assert b["units"] >= 1 and b["failed"] == 0
    per = b["counters_per_unit"]
    assert per["device.syncs"] == 9
    assert per["device.calls.kernel.segment_reduce"] == 3
    assert per["device.calls.kernel.queue_walk"] == 1
    for parent in ("repro.plan.sweep", "sweep"):
        assert b["coverage"][parent]["worst"] > 0.9, b["coverage"]
    assert set(b["span_ms"]) >= {"repro.plan.bind", "repro.plan.rewrite",
                                 "repro.plan.arrivals", "repro.plan.arena",
                                 "repro.plan.model", "repro.plan.simulate",
                                 "repro.device.sync", "repro.sim.routing"}
    assert b["idle_program_share"] > 0.5


def test_moe_query_splits_by_station(tmp_path):
    b = _split("moe-query", tmp_path)
    assert b["coverage"]["repro.service.query"]["share"] > 0.9
    assert "repro.service.key" in b["span_ms"]
    assert b["counters_per_unit"]["device.syncs"] > 0
