"""Execution-layer benchmarks: measured vs predicted strategy orderings.

Rows (``name,us_per_call,derived``):

``exec_model_agreement``
    The calibrated-model loop closed end to end, numpy-only: fit parameter
    tables for the Lassen- and Frontier-like presets from recorded
    noiseless sweeps (:mod:`repro.exec.calibrate` — the model side never
    peeks at ground truth), predict every GPU-strategy winner on the
    crossover pattern set with the *fitted* table, and judge against the
    simulator's ground-truth verdict.  ``derived`` is the fraction of
    (machine, count) cases where the calibrated model picks the
    simulator's winner.

``exec_agreement_crossover``
    The direct-vs-aggregated crossover cases specifically: the small end
    (``device_direct`` wins under the simulator), the large end
    (``host_staged`` wins) and the flip itself on the Lassen-like preset.
    ``derived`` is 1.0 only when the sweep really crosses over AND the
    calibrated model calls every one of those cases — the gated row in
    ``perf_smoke`` (a model that misses the crossover is not predicting,
    it is guessing).

The jax rows run the lowered schedules on a forced 8-device host mesh in a
subprocess pinned to ``JAX_PLATFORMS=cpu`` (absent without jax — optional
in the gate).  Every time in them is CPU-host wall clock: the eight
"devices" are XLA's CPU backend, so none of these rows is a device
(accelerator) metric.

``exec_measured_<strategy>``
    Median CPU-host wall clock of the lowered schedule on the host mesh
    (``us_per_call``) with the calibrated model's predicted cost in
    seconds as ``derived`` — the measured-vs-predicted table, one row per
    strategy on the host-scale Lassen preset.  Bit-identity vs the
    reference executor is asserted inside before timing.

``exec_wallclock_agreement``
    Pairwise ordering agreement between the CPU-host wall-clock ranking
    and the calibrated model's predicted ranking on the host mesh.
    Reported, not gated: the host CPU mesh is a different machine from
    the preset the model describes — the *simulator* rows above are the
    apples-to-apples agreement gate.

``exec_launch_overhead``
    Median CPU-host wall clock of launching the empty ``standard``
    schedule (all launch, no payload); ``derived`` is that overhead as a
    fraction of the measured ``standard`` schedule time.

``exec_standard_vs_naive``
    The greedy edge-colored ``standard`` schedule vs the naive
    one-``ppermute``-per-message lowering of the same exchange
    (``coloring='per_message'``), identical delivered payloads asserted.
    ``derived`` is naive/colored — gated >= 1.0 in ``perf_smoke``: fusing
    messages into permutation rounds must never lose to the per-message
    loop.

Run directly for the CSV::

    PYTHONPATH=src python -m benchmarks.bench_exec
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
import time

import numpy as np

COUNTS = (8, 32, 128, 512, 2048)


def _timed(fn):
    t0 = time.perf_counter()
    out = fn()
    return out, (time.perf_counter() - t0) * 1e6


def _crossover_phases(machine):
    from repro.comm import CommPhase
    out = []
    for n in COUNTS:
        rng = np.random.default_rng(42)
        P = machine.n_procs
        src = rng.integers(0, P, n)
        dst = (src + rng.integers(1, P, n)) % P
        size = rng.integers(256, 8192, n).astype(float)
        out.append(CommPhase.build(machine, src, dst, size, n_procs=P))
    return out


def bench_exec_agreement():
    """Calibrated-model vs simulator strategy ordering (numpy-only)."""
    from repro.comm.strategies import GPU_STRATEGIES, best_strategy_many
    from repro.exec import calibrate, record_sweeps
    from repro.net import frontier_machine, lassen_machine

    def run():
        agrees, lassen_verdicts = [], []
        for mk, dims in ((lassen_machine, (2, 2, 2)),
                         (frontier_machine, (2, 2, 1))):
            machine = mk(dims)
            fitted = calibrate(record_sweeps(machine), machine.params).params
            verdicts = best_strategy_many(_crossover_phases(machine),
                                          strategies=GPU_STRATEGIES,
                                          seed=0, params=fitted)
            agrees += [v.agree for v in verdicts]
            if machine.name == "lassen":
                lassen_verdicts = verdicts
        # the crossover cases: small end (direct), large end (staged) and
        # the first staged count on the Lassen-like sweep
        winners = [v.sim_winner for v in lassen_verdicts]
        staged = [i for i, w in enumerate(winners) if w == "host_staged"]
        crossed = (winners[0] == "device_direct" and staged
                   and winners[-1] == "host_staged")
        cases = ([0, staged[0], len(winners) - 1] if crossed else [])
        crossover_ok = bool(crossed) and all(lassen_verdicts[i].agree
                                             for i in cases)
        return float(np.mean(agrees)), float(crossover_ok)

    (agreement, crossover_ok), us = _timed(run)
    return [("exec_model_agreement", us, agreement),
            ("exec_agreement_crossover", us, crossover_ok)]


_MESH_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import json
import numpy as np
from repro.comm.phase import CommPhase
from repro.comm.strategies import strategies_for
from repro.exec import (build_schedule, build_executor, calibrate,
                        launch_overhead, lassen_8, predicted_costs,
                        record_sweeps, run_reference, time_schedule)

m = lassen_8()
rng = np.random.default_rng(42)
n = 96
src = rng.integers(0, 8, n)
dst = (src + rng.integers(1, 8, n)) % 8
size = rng.integers(256, 8192, n).astype(float)
phase = CommPhase.build(m, src, dst, size, n_procs=8)

fitted = calibrate(record_sweeps(m), m.params).params
predicted = predicted_costs(phase, params=fitted)

measured = {}
for strat in strategies_for(m):
    sched = build_schedule(phase, strat)
    got = build_executor(sched)()
    assert np.array_equal(got, run_reference(sched)), strat
    measured[strat] = time_schedule(sched, reps=5, warmup=2).median_s

overhead = launch_overhead(phase, reps=5, warmup=2)

# naive per-message lowering of the all-to-all standard exchange
colored = build_schedule(phase, "standard")
naive = build_schedule(phase, "standard", coloring="per_message")
assert np.array_equal(run_reference(colored), run_reference(naive))
t_colored = time_schedule(colored, reps=5, warmup=2).median_s
t_naive = time_schedule(naive, reps=5, warmup=2).median_s

print(json.dumps({"measured": measured, "predicted": predicted,
                  "overhead": overhead, "t_colored": t_colored,
                  "t_naive": t_naive,
                  "rounds": [colored.n_rounds, naive.n_rounds]}))
"""


def bench_exec_schedules():
    """Lowered schedules timed on the forced 8-device host mesh (jax)."""
    try:
        import jax  # noqa: F401
    except ImportError:
        return []
    from repro.exec import pairwise_agreement

    # the child forces a host mesh: pin it to the CPU so that it never
    # contends for a chip this process may hold
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run([sys.executable, "-c", _MESH_SCRIPT], env=env,
                         capture_output=True, text=True, timeout=600)
    if out.returncode != 0:
        raise RuntimeError(f"mesh benchmark failed:\n{out.stderr[-2000:]}")
    r = json.loads(out.stdout.strip().splitlines()[-1])

    rows = []
    for strat, med in r["measured"].items():
        rows.append((f"exec_measured_{strat}", med * 1e6,
                     r["predicted"][strat]))
    std = r["measured"]["standard"]
    rows.append(("exec_wallclock_agreement", 0.0,
                 pairwise_agreement(r["measured"], r["predicted"])))
    rows.append(("exec_launch_overhead", r["overhead"] * 1e6,
                 r["overhead"] / std if std > 0 else 0.0))
    rows.append(("exec_standard_vs_naive", r["t_colored"] * 1e6,
                 r["t_naive"] / r["t_colored"]))
    return rows


ALL_BENCHES = [bench_exec_agreement, bench_exec_schedules]


if __name__ == "__main__":
    print("name,us_per_call,derived")
    for bench in ALL_BENCHES:
        for name, us, derived in bench():
            print(f"{name},{us:.1f},{derived:.6g}")
