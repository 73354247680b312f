"""Overhead of the robustness layer (fault sites, validation, degradation).

The hardened stack threads every device call through a named injection
site and (optionally) the typed validation layer; these rows pin what
that safety costs when nothing is wrong — and what a fully degraded sweep
costs relative to a clean one.

Rows (``name,us_per_call,derived``):

``faults_site_disarmed``
    One ``fail_point`` + ``poison`` probe with no specs armed — the cost
    every guarded device call pays always.  ``derived`` is 1.0.

``faults_site_armed_miss``
    The same probe with a non-matching spec armed (the worst common case:
    a chaos plan targeting *other* sites).  ``derived`` is the
    disarmed/armed time ratio.

``guard_validate_100k``
    :func:`repro.comm.guard.validate_messages` over a 100k-message
    pattern.  ``derived`` is validated messages per microsecond — the
    layer is a handful of vectorized reductions, so this should stay in
    the tens of messages/us.

``sweep_clean_numpy`` / ``sweep_degraded``
    One :func:`repro.comm.best_strategy` sweep of a 4k-message pattern on
    the numpy reference, then the same sweep on the jax backend with every
    fault site raising — the full degradation path (fault -> health event
    -> numpy fallback, quarantine warm after the first phases).
    ``derived`` for the degraded row is clean/degraded (how much a fully
    degraded sweep costs relative to the reference); skipped without jax.

Run directly for the CSV::

    PYTHONPATH=src python -m benchmarks.bench_faults
"""
from __future__ import annotations

import time

import numpy as np

VALIDATE_MSGS = 100_000
SWEEP_MSGS = 4_000
SITE_PROBES = 20_000


def _best_of(fn, reps: int = 3, trials: int = 4):
    out = fn()
    best = float("inf")
    for _ in range(trials):
        t0 = time.perf_counter()
        for _ in range(reps):
            out = fn()
        best = min(best, (time.perf_counter() - t0) / reps)
    return best * 1e6, out


def _probe_once():
    from repro.comm import faults
    for _ in range(SITE_PROBES):
        faults.fail_point("kernel.segment_reduce")
    return SITE_PROBES


def bench_fault_sites():
    from repro.comm import faults

    us_off, n = _best_of(_probe_once, reps=2)
    rows = [("faults_site_disarmed", us_off / n, 1.0)]
    with faults.inject("serve.cache_write", "raise"):      # never matches
        us_miss, n = _best_of(_probe_once, reps=2)
    rows.append(("faults_site_armed_miss", us_miss / n, us_off / us_miss))
    return rows


def bench_validation():
    from repro.comm.guard import validate_messages

    rng = np.random.default_rng(0)
    P = 4096
    src = rng.integers(0, P, VALIDATE_MSGS)
    dst = rng.integers(0, P, VALIDATE_MSGS)
    size = rng.integers(1, 1 << 16, VALIDATE_MSGS).astype(np.float64)
    us, _ = _best_of(
        lambda: validate_messages(src, dst, size, n_procs=P) or 1, reps=3)
    return [("guard_validate_100k", us, VALIDATE_MSGS / us)]


def _sweep_pattern():
    from repro.net import blue_waters_machine
    from repro.sparse.partition import CommPattern

    machine = blue_waters_machine((2, 2, 2))
    rng = np.random.default_rng(1)
    P = machine.n_procs
    src = rng.integers(0, P, SWEEP_MSGS)
    dst = (src + rng.integers(1, P, SWEEP_MSGS)) % P
    size = rng.integers(1, 1 << 16, SWEEP_MSGS).astype(np.float64)
    return machine, CommPattern(src=src, dst=dst, size=size, n_procs=P)


def bench_degraded_sweep():
    import warnings

    from repro.comm import faults
    from repro.comm.health import reset_health
    from repro.comm.strategies import best_strategy
    from repro.kernels.comm_stack import have_jax

    machine, pat = _sweep_pattern()
    us_clean, clean = _best_of(
        lambda: best_strategy(pat, machine, backend="numpy"), reps=2)
    rows = [("sweep_clean_numpy", us_clean, 1.0)]
    if have_jax():
        def degraded():
            reset_health()              # re-arm quarantine per timed pass
            with warnings.catch_warnings():
                warnings.simplefilter("ignore")
                with faults.inject("*", "raise"):
                    return best_strategy(pat, machine, backend="jax")
        us_deg, verdict = _best_of(degraded, reps=2)
        assert verdict.degraded and verdict.model == clean.model, \
            "degraded sweep drifted from the numpy reference"
        rows.append(("sweep_degraded", us_deg, us_clean / us_deg))
        reset_health()
    return rows


ALL_BENCHES = [bench_fault_sites, bench_validation, bench_degraded_sweep]


def main() -> None:
    print("name,us_per_call,derived")
    for bench in ALL_BENCHES:
        for name, us, derived in bench():
            print(f"{name},{us:.1f},{derived:.6g}", flush=True)


if __name__ == "__main__":
    main()
