"""Each cell end to end at a tiny size on the CPU, with its check.

    JAX_PLATFORMS=cpu python -m pytest -q bench/tests

These runs skip the harness's look for a chip and never print a result:
they show that the cell's path runs, that ``correct`` holds on the
program as it is, and that it comes out false when the timed path is
broken underneath (one fault per kind the cell can have) and for the
control.
"""
from __future__ import annotations

import copy
import os
import sys
import time

os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=4")

import jax  # noqa: E402
import numpy as np  # noqa: E402
import pytest  # noqa: E402

sys.path[:0] = [os.path.join(os.path.dirname(__file__), "..", "..", "src"),
                os.path.join(os.path.dirname(__file__), "..", "..")]

from bench import reference, run  # noqa: E402
from bench.kinds import exchange as exchange_kind  # noqa: E402

SECONDS = 0.5


def tiny(name: str) -> dict:
    """The cell ``name`` at a size a test run can hold."""
    c = copy.deepcopy(run.cell(name))
    cfg, mix = c["config"], c["mix"]
    if name == "amg-sweep":
        cfg["machine"]["args"]["torus_dims"] = [2, 2, 1]
        cfg["reference_machine"]["torus_dims"] = [2, 2, 1]
        cfg["problem"]["grid"] = 8
        cfg["problem"].pop("levels")
    elif name == "moe-query":
        cfg["expert_parallel"] = 16
        cfg["machine"]["args"]["torus_dims"] = [2, 1, 1]
        cfg["reference_machine"]["torus_dims"] = [2, 1, 1]
        mix.update(pool=2, tokens_per_rank=32, tokens_range=[16, 512])
    elif name == "moe-exchange-4chip":
        mix.update(tokens_per_rank=16, unit_bytes=256)
        mix["kept"] = {"among": 4, "count": 2}
    return c


def run_tiny(name: str, seed: int = 2 ** 31 + 7) -> dict:
    c = tiny(name)
    n = int(c["workload"]["chips"])
    return run.run_cell(c, seed, SECONDS, False, jax.devices()[:n],
                        start=time.perf_counter())


CELLS = ("amg-sweep", "moe-query", "moe-exchange-4chip")


@pytest.mark.parametrize("name", CELLS)
def test_cell_is_correct(name):
    r = run_tiny(name)
    assert r["correct"], r["checks"]
    assert r["attempted"] >= 1 and r["failed"] == 0
    assert set(r["metrics"]) == {m["name"] for m in tiny(name)["end_to_end"]}
    assert "setup_s" in r["metrics"]


def _altered_verdict(monkeypatch):
    """The program's verdicts come back with the winner's cost scaled."""
    from repro.comm import strategies
    real = strategies.best_strategy_many

    def altered(*a, **kw):
        out = real(*a, **kw)
        v = out[0]
        v.model[v.model_winner] *= 1.01
        return out

    monkeypatch.setattr(strategies, "best_strategy_many", altered)


def _half_the_batch(monkeypatch):
    """Only the first half of the patterns is priced."""
    from repro.comm import strategies
    real = strategies.best_strategy_many

    def half(patterns, *a, **kw):
        patterns = list(patterns)
        return real(patterns[:max(1, len(patterns) // 2)], *a, **kw)

    monkeypatch.setattr(strategies, "best_strategy_many", half)


@pytest.mark.parametrize("name", ("amg-sweep", "moe-query"))
@pytest.mark.parametrize("fault", (_altered_verdict, _half_the_batch))
def test_planner_fault_fails(monkeypatch, name, fault):
    fault(monkeypatch)
    assert not run_tiny(name)["correct"]


def _no_exchange(monkeypatch):
    """``ppermute`` leaves every word where it was."""
    monkeypatch.setattr(jax.lax, "ppermute", lambda x, axis, perm: x)


def _altered_word(monkeypatch):
    """One delivered word is changed where the exchange produces it."""
    real = exchange_kind.Cell.release

    def release(self):
        real(self)
        out = next(iter(self.kept.values()))[0]
        out[np.nonzero(out)[0][0], np.nonzero(out)[1][0]] += 1

    monkeypatch.setattr(exchange_kind.Cell, "release", release)


def _half_the_rounds(monkeypatch):
    """The lowered program runs only the first half of its rounds."""
    import dataclasses

    from repro import exec as exec_
    real = exec_.build_schedule

    def half(*a, **kw):
        sched = real(*a, **kw)
        keep = max(1, sched.n_rounds // 2)
        phases = []
        for ph in sched.phases:
            rounds = ph.rounds[:keep]
            keep -= len(rounds)
            if rounds:
                phases.append(dataclasses.replace(ph, rounds=rounds))
        return dataclasses.replace(sched, phases=tuple(phases))

    monkeypatch.setattr(exec_, "build_schedule", half)


@pytest.mark.parametrize("fault", (_no_exchange, _altered_word,
                                   _half_the_rounds))
def test_exchange_fault_fails(monkeypatch, fault):
    fault(monkeypatch)
    assert not run_tiny("moe-exchange-4chip")["correct"]


def test_amg_traffic_is_pinned():
    """Set-up refuses AMG levels that differ from the configuration's."""
    c = tiny("amg-sweep")
    c["config"]["problem"]["levels"] = [{"messages": 1, "ranks": 2,
                                         "digest": "0"}]
    with pytest.raises(SystemExit):
        run.run_cell(c, 3, SECONDS, False, jax.devices()[:1],
                     start=time.perf_counter())


@pytest.mark.parametrize("name", ("amg-sweep", "moe-query"))
def test_control_fails(name):
    """The reference priced in bfloat16 lies beyond the verdict limit."""
    c = tiny(name)
    cfg = c["config"]
    m = reference.Machine(cfg["reference_machine"])
    if name == "amg-sweep":
        from bench import deploy
        pats = [(p.src, p.dst, p.size, p.n_procs) for p in
                deploy.amg_patterns(cfg["problem"], 2 * 2 * 1 * 32)]
    else:
        from bench import moe
        n = cfg["expert_parallel"]
        pats = [(s, d, z, n) for s, d, z in moe.draw(cfg, 32, 5)]
    gap = max(reference.verdict_gap(
        reference.verdict(m, s, d, z, n, 9, lowp=True),
        reference.verdict(m, s, d, z, n, 9)) for s, d, z, n in pats)
    assert gap > c["mix"]["limits"]["verdict_gap"]


def test_exchange_control_fails():
    """Words carried through float32 come out wrong."""
    r = np.random.default_rng(0)
    payload = r.integers(1, 2 ** 31 - 1, size=64, dtype=np.int32)
    src = np.zeros(64, dtype=np.int64)
    got = np.zeros((2, 64), dtype=np.int64)
    got[1] = payload.astype(np.float32).astype(np.int64)
    wrong = exchange_kind.units_wrong(got, payload, src, np.array([0]),
                                      np.array([1]), np.array([256.0]), 4)
    assert wrong > 0
    got[1] = payload
    assert exchange_kind.units_wrong(got, payload, src, np.array([0]),
                                     np.array([1]), np.array([256.0]),
                                     4) == 0


def test_every_name_has_its_file():
    """Each cell's configuration, traffic and traffic kind, and each per-layer
    metric's reader, are found by name."""
    spec = run.load_json(run.ROOT / "BENCHMARK.json")
    for w in spec["workloads"]:
        c = run.cell(w["name"])
        assert (run.BENCH / "kinds" / f"{c['mix']['kind']}.py").exists()
    for m in spec["per_layer"]:
        assert hasattr(run.load_module(
            run.BENCH / "metrics" / f"{m['name']}.py"), "read")


def test_readers_on_a_record():
    from bench import counts
    ns = 1e9
    ops = [("jit_step/%fusion", 0.0, 0.2 * ns,
            "%fusion = fusion(%x) feeds collective-permute-start"),
           ("jit__unknown/%_unknown_", 0.1 * ns, 0.3 * ns,
            "custom-call tpu_custom_call"),
           ("jit_step/%collective-permute-done", 0.5 * ns, 0.6 * ns,
            "collective-permute-done"),
           ("jit_walk/%while", 0.7 * ns, 0.8 * ns, "%while"),
           ("jit_walk/%fusion", 0.72 * ns, 0.78 * ns, "%fusion")]
    rec = {"units": 2, "compiles": 0, "cache": {"hits": 1, "misses": 3},
           "device_kind": "TPU v5 lite",
           "work": {"phases": 2, "messages": 10, "senders": 4,
                    "link_sources": 6, "links": 3},
           "trace": {"window_s": 1.0, "busy_s": [0.4], "busiest": 0,
                     "_trace": {"window": (0.0, ns), "spans": [],
                                "devices": [ops]}}}

    def read(name):
        return run.load_module(run.BENCH / "metrics" / f"{name}.py").read(rec)

    assert read("idle_share.sweep") == pytest.approx(60.0)
    assert read("segreduce_ms.sweep") == pytest.approx(100.0)
    assert read("permute_ms.exchange") == pytest.approx(50.0)
    # the loop and the op nested in it count once
    assert read("walk_ms.sweep") == pytest.approx(50.0)
    assert read("cache_hit_share.query") == pytest.approx(25.0)
    assert read("compiles.query") == 0.0
    work = counts.segreduce_work(rec["work"])
    assert work == {"bytes": 8.0 * (10 + 10 + 6) + 8.0 * (4 + 4 + 3)}
    least = work["bytes"] / 819e9
    assert read("segreduce_roofline.sweep") == pytest.approx(
        100 * least / 0.1)
    assert read("compiles.exchange") == 0.0
    # dispatch 0->1 (8 B, 2 words), 0->2 (10 B, 3), 3->0 (1 B, 1), and
    # combine reversed, at 4 bytes a word
    src, dst, size = np.array([0, 0, 3]), np.array([1, 2, 0]), [8.0, 10, 1]
    rec["work"] = counts.exchange_work([(src, dst, size), (dst, src, size)],
                                       4, 4.0)
    assert rec["work"] == {"hbm_bytes": [48.0, 16.0, 24.0, 8.0],
                           "ici_bytes": [24.0, 8.0, 12.0, 4.0]}
    least = max(48 / 819e9, 24 / 200e9)
    # busiest chip 0.4 s over 2 exchanges
    assert read("exchange_roofline.exchange") == pytest.approx(
        100 * least / 0.2)
    rec["trace"] = None
    assert read("segreduce_ms.sweep") is None
    assert read("exchange_roofline.exchange") is None
    with pytest.raises(KeyError):
        counts.peaks("TPU v9 imaginary")


def test_exchange_work_is_the_messages():
    """A plan that relays words through a middle chip moves more of them
    than the messages carry; the roofline's work is the messages', which
    the plain plan moves once each over ICI."""
    from repro.comm import CommPhase
    from repro.exec import build_schedule

    from bench import counts, deploy, moe
    c = tiny("moe-exchange-4chip")
    cfg, mix = c["config"], c["mix"]
    n, unit = int(cfg["expert_parallel"]), float(mix["unit_bytes"])
    machine = deploy.machine(cfg["machine"])
    messages = moe.draw(cfg, mix["tokens_per_rank"], mix["routing_seed"])
    moved = {strategy: sum(
        int(ph.msg_units.sum()) for s, d, z in messages
        for ph in build_schedule(CommPhase.build(machine, s, d, z,
                                                 n_procs=n), strategy,
                                 unit_bytes=unit).phases)
        for strategy in ("standard", "three_step")}
    assert moved["three_step"] > moved["standard"]
    work = counts.exchange_work(messages, n, unit)
    assert sum(work["ici_bytes"]) == 4 * moved["standard"]


def test_trace_reduction_selftest():
    """``python -m bench.trace --selftest`` passes on the recorded trace."""
    from bench import trace
    assert trace.selftest() == 0
