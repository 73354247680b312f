"""Executing the winning strategy, end to end (DESIGN.md §14).

The rest of the examples *price* strategies; this one runs them.  Lower
each strategy rewrite of an irregular exchange to integral payload units
and edge-colored ``ppermute`` rounds, replay the schedule with the serial
numpy oracle, then calibrate a parameter table from recorded sweeps and
check the fitted model ranks the strategies exactly like the ground-truth
table.  With jax installed the same schedules also execute for real on a
mesh of one device per rank, bit-identical to the oracle, with a
measured-vs-predicted table.  On the CPU, ``XLA_FLAGS`` (set below, before
jax loads) gives the host backend the 8 devices the host-scale preset
needs; on an accelerator host the example runs where jax finds at least 8
devices and otherwise says why it skipped.

    PYTHONPATH=src python examples/comm_exec.py
"""
import os

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

import numpy as np

from repro.comm import CommPhase
from repro.comm.strategies import strategies_for
from repro.exec import (build_schedule, calibrate, lassen_8, ordering,
                        predicted_costs, record_sweeps, reference_delivered,
                        run_reference)


def main():
    m = lassen_8()
    rng = np.random.default_rng(3)
    n = 96
    src = rng.integers(0, 8, n)
    dst = (src + rng.integers(1, 8, n)) % 8
    size = rng.integers(256, 8192, n).astype(float)
    phase = CommPhase.build(m, src, dst, size, n_procs=8)
    print(f"{m.name}-like host preset: {n} messages, "
          f"{phase.size.sum() / 1024:.0f} KiB total\n")

    # -- lowering: every strategy -> units, rounds, bit-identity ----------
    print(f"{'strategy':>14} {'units':>6} {'phases':>7} {'rounds':>7} "
          f"{'naive rounds':>13}   oracle")
    for strat in strategies_for(m):
        sched = build_schedule(phase, strat)
        naive = build_schedule(phase, strat, coloring="per_message")
        ok = np.array_equal(run_reference(sched), reference_delivered(sched))
        print(f"{strat:>14} {sched.n_units:>6} {len(sched.phases):>7} "
              f"{sched.n_rounds:>7} {naive.n_rounds:>13}   "
              f"{'bit-identical' if ok else 'MISMATCH'}")

    # -- calibration: fitted table reproduces the strategy ordering -------
    fit = calibrate(record_sweeps(m), m.params)
    truth = predicted_costs(phase)
    fitted = predicted_costs(phase, params=fit.params)
    print(f"\ncalibrated from recorded sweeps: n_rails={fit.n_rails} "
          f"(truth {m.params.n_rails}), classes {sorted(fit.fitted_classes)}")
    print(f"{'strategy':>14} {'truth s':>12} {'fitted s':>12}")
    for strat in ordering(truth):
        print(f"{strat:>14} {truth[strat]:>12.3e} {fitted[strat]:>12.3e}")
    agree = ordering(fitted) == ordering(truth)
    print(f"fitted-model ordering {'==' if agree else '!='} ground truth")

    # -- execution: the same schedules on a mesh of one device per rank ---
    try:
        import jax
    except ImportError:
        print("\nskipped the mesh execution: jax is not installed")
        return
    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache()
    devices = jax.devices()
    dev = f"{devices[0].platform} ({devices[0].device_kind})"
    if len(devices) < phase.n_procs:
        print(f"\nskipped the mesh execution: the {m.name} preset needs "
              f"{phase.n_procs} devices, and jax found {len(devices)} {dev}")
        return
    from repro.exec import execute, time_schedule
    print(f"\nexecuting on {phase.n_procs} of {len(devices)} {dev} devices "
          f"(shard_map + ppermute); times are wall clock on that platform:")
    print(f"{'strategy':>14} {'measured us':>12} {'model s':>12}   payloads")
    for strat in strategies_for(m):
        sched = build_schedule(phase, strat)
        delivered, _ = execute(sched)
        ok = np.array_equal(delivered, run_reference(sched))
        meas = time_schedule(sched, reps=3, warmup=1)
        print(f"{strat:>14} {meas.median_s * 1e6:>12.0f} "
              f"{truth[strat]:>12.3e}   "
              f"{'bit-identical' if ok else 'MISMATCH'}")


if __name__ == "__main__":
    main()
