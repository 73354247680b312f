"""The cell ``v3-decode-exchange-4chip`` end to end at a tiny size on the
CPU, with its checks, its planted faults and its controls.

    JAX_PLATFORMS=cpu python -m pytest -q bench/tests

As in ``test_cells.py``: the run skips the harness's look for a chip, and
``correct`` holds on the program as it is and comes out false when the
exchange is broken underneath or the program's routing or lowering drifts
from the plain reference (``bench/moe_dedup.py``).
"""
from __future__ import annotations

import copy
import dataclasses
import time

# first: sets the CPU's 4 devices and the import path, as jax starts
from test_cells import (SECONDS, _altered_word, _half_the_rounds,
                        _no_exchange)

import jax  # noqa: E402
import numpy as np  # noqa: E402
import pytest  # noqa: E402

from bench import run  # noqa: E402
from bench.kinds import exchange_dedup  # noqa: E402

CELL = "v3-decode-exchange-4chip"


def tiny() -> dict:
    """The cell at 16 tokens a rank and 256 bytes a payload word."""
    c = copy.deepcopy(run.cell(CELL))
    c["mix"].update(tokens_per_rank=16, unit_bytes=256)
    c["mix"]["kept"] = {"among": 4, "count": 2}
    return c


def run_tiny(seed: int = 2 ** 31 + 7) -> dict:
    return run.run_cell(tiny(), seed, SECONDS, False, jax.devices()[:4],
                        start=time.perf_counter())


def test_cell_is_correct():
    r = run_tiny()
    assert r["correct"], r["checks"]
    assert r["attempted"] >= 1 and r["failed"] == 0
    assert set(r["metrics"]) == {"setup_s", "exchange_ms"}
    assert set(r["checks"]) == {"units_wrong", "messages_wrong"}


@pytest.mark.parametrize("fault", (_no_exchange, _altered_word,
                                   _half_the_rounds))
def test_exchange_fault_fails_units_wrong(monkeypatch, fault):
    """The exchange broken underneath fails ``units_wrong`` alone."""
    fault(monkeypatch)
    r = run_tiny()
    assert not r["correct"]
    assert r["checks"]["units_wrong"]["value"] > 0
    assert r["checks"]["messages_wrong"]["value"] == 0


def _ungrouped_routing(monkeypatch):
    """The program routes over every expert group."""
    from repro.workloads import moe
    real = moe.node_limited_topk
    monkeypatch.setattr(moe, "node_limited_topk",
                        lambda s, k, n_group, topk_group:
                        real(s, k, n_group, n_group))


def _a_copy_per_expert(monkeypatch):
    """The program lowers one copy per (token, expert), not per chip."""
    from repro.workloads import moe

    def per_expert(choices, n_ranks, n_experts, dispatch_bytes,
                   combine_bytes):
        counts = moe.choice_counts(choices, n_ranks, n_experts)
        pat = moe.pattern_from_counts(counts, dispatch_bytes, None,
                                      act_bytes=1)
        combine = moe.pattern_from_counts(counts, combine_bytes, None,
                                          act_bytes=1).combine
        return dataclasses.replace(pat, combine=combine)

    monkeypatch.setattr(moe, "pattern_from_choices", per_expert)


def _wider_scales(monkeypatch):
    """The program's FP8 dispatch carries one scale more per token."""
    from repro.workloads import moe
    real = moe.fp8_token_bytes
    monkeypatch.setattr(moe, "fp8_token_bytes",
                        lambda d: real(d) + moe.SCALE_BYTES)


@pytest.mark.parametrize("fault", (_ungrouped_routing, _a_copy_per_expert,
                                   _wider_scales))
def test_message_fault_fails_both_checks(monkeypatch, fault):
    """A fault in the program's routing or lowering shows in
    ``messages_wrong``, and in ``units_wrong``: the exchange runs the
    program's messages and is held to the reference's."""
    fault(monkeypatch)
    r = run_tiny()
    assert not r["correct"]
    assert r["checks"]["messages_wrong"]["value"] > 0
    assert r["checks"]["units_wrong"]["value"] > 0


def test_control_fails_both_checks():
    """Routed without the group limit and carried through float32, the
    control fails ``messages_wrong`` and ``units_wrong``; the program
    passes both on the same run."""
    c = tiny()
    cell = exchange_dedup.Cell(c["config"], c["mix"], 2 ** 31 + 11,
                               jax.devices()[:4],
                               jax.profiler.TraceAnnotation)
    cell.setup()
    cell.window(0.2)
    cell.release()
    assert {n: v for n, v, _ in cell.check()} == {"units_wrong": 0.0,
                                                  "messages_wrong": 0.0}
    control = {n: v for n, v, _ in cell.check(control=True)}
    assert control["units_wrong"] > 0 and control["messages_wrong"] > 0


def test_messages_wrong_counts_pairs():
    a = (np.array([0, 0, 1]), np.array([1, 2, 0]), np.array([8.0, 4, 2]))
    assert exchange_dedup.messages_wrong(a, a) == 0
    b = (np.array([0, 1]), np.array([1, 0]), np.array([8.0, 3]))
    assert exchange_dedup.messages_wrong(a, b) == 2      # (0,2) gone, (1,0)
    c = (np.array([0, 0, 0, 1]), np.array([1, 1, 2, 0]),
         np.array([4.0, 4, 4, 2]))
    assert exchange_dedup.messages_wrong(c, a) == 1      # (0,1) repeated


def test_phase_readers_on_a_record():
    """Device busy time inside each phase's host spans, per exchange."""
    ns = 1e9
    ops = [("jit_step/%fusion", 0.0, 0.2 * ns, ""),
           ("jit_step/%fusion", 0.1 * ns, 0.3 * ns, ""),
           ("jit_step/%collective-permute-done", 0.5 * ns, 0.6 * ns, ""),
           ("jit_step/%fusion", 0.7 * ns, 0.8 * ns, "")]
    # dispatch over 0-0.25 s and 0.45-0.55 s, combine over 0.6-0.9 s;
    # the device is busy 0-0.3 s (two ops overlap), 0.5-0.6 and 0.7-0.8
    spans = [("window", 0.0, ns), ("exchange.dispatch", 0.0, 0.25 * ns),
             ("exchange.dispatch", 0.45 * ns, 0.55 * ns),
             ("exchange.combine", 0.6 * ns, 0.9 * ns)]
    rec = {"units": 2, "trace": {"window_s": 1.0, "busy_s": [0.5],
                                 "busiest": 0,
                                 "_trace": {"window": (0.0, ns),
                                            "spans": spans,
                                            "devices": [[], ops]}}}

    def read(name):
        return run.load_module(run.BENCH / "metrics" / f"{name}.py").read(rec)

    assert read("dispatch_ms.exchange-dedup") == pytest.approx(
        (0.25 + 0.05) / 2 * 1e3)
    assert read("combine_ms.exchange-dedup") == pytest.approx(0.1 / 2 * 1e3)
    rec["trace"]["_trace"]["spans"] = spans[:1]
    assert read("dispatch_ms.exchange-dedup") is None
    rec["trace"] = None
    assert read("combine_ms.exchange-dedup") is None
