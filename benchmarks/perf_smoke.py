"""CI perf smoke: the fast paths must never lose to their reference paths.

Gates, each with a per-row threshold deliberately below the typical
speedup, so CI-runner throttling noise cannot flake the gate while a real
regression still trips it:

* the ``stack_*`` rows of :mod:`benchmarks.bench_kernels` (stacked sweep vs
  per-phase loop on the AMG hierarchy x partition scan, bit-identity
  asserted inside the bench) — the PhaseStack sweep path must never be
  slower than the loop (>= 1.0x);
* the ``delta_local_search_64`` row of :mod:`benchmarks.bench_delta`
  (incremental re-pricing vs rebuild-per-candidate on the same 64-move
  local search, candidate costs asserted allclose inside the bench) — the
  DeltaStack path must never be slower than a full rebuild (>= 1.0x);
* the ``delta_service_qps`` row of the same bench (the full scenario
  registry through a warm :class:`repro.serve.StrategyService` vs a cold
  rebuild per query, cached verdicts asserted bit-identical inside the
  bench) — the fingerprint cache must never lose to re-running the sweep
  (>= 1.0x; in practice the hit path is orders of magnitude ahead);
* the ``llm_sweep_stacked`` row of :mod:`benchmarks.bench_llm_workloads`
  (the registry's ONE cross-machine ``best_strategy_many`` arena vs the
  per-pattern ``best_strategy`` loop on the same bound phases, verdicts
  asserted identical inside the bench) — the stacked all-scenario sweep
  must never lose to the per-scenario loop it replaced (>= 1.0x);
* the ``exec_agreement_crossover`` row of :mod:`benchmarks.bench_exec`
  (numpy-only) — the *calibrated* model (fitted from recorded sweeps,
  never shown ground truth) must call every direct-vs-aggregated
  crossover case on the Lassen-like sweep: the small end where
  ``device_direct`` wins, the large end where ``host_staged`` wins, and
  the flip itself (>= 1.0, i.e. exact);
* the ``exec_standard_vs_naive`` row of the same bench — the greedy
  edge-colored lowering of the ``standard`` schedule vs the naive
  one-``ppermute``-per-message lowering of the same exchange on the
  forced 8-device host mesh, delivered payloads asserted identical inside
  the bench — fusing messages into permutation rounds must never lose to
  the per-message loop (>= 1.0x).  The row only exists where jax is
  importable, so it is optional in a CSV from a jax-less host.

Usage::

    python -m benchmarks.perf_smoke [bench.csv]

With a CSV argument (the ``benchmarks.run`` output, as in CI) the gates are
applied to its rows without re-running the workloads; without one the
benchmarks are executed directly (local development).
"""
from __future__ import annotations

import sys

STACK_ROWS = ("stack_model_ladder", "stack_simulate", "stack_best_strategy")
DELTA_ROWS = ("delta_local_search_64", "delta_service_qps")
#: registry cross-machine arena vs per-scenario loop (numpy-only)
LLM_ROWS = ("llm_sweep_stacked",)
#: calibrated-model crossover agreement (numpy-only, always present)
EXEC_ROWS = ("exec_agreement_crossover",)
#: colored-vs-naive lowered schedule: present only where jax imports
EXEC_JAX_ROWS = ("exec_standard_vs_naive",)

GATED_ROWS = STACK_ROWS + DELTA_ROWS + LLM_ROWS + EXEC_ROWS + EXEC_JAX_ROWS
OPTIONAL_ROWS = frozenset(EXEC_JAX_ROWS)

#: per-row minimum ``derived`` speedup (see the module docstring)
THRESHOLD = {name: 1.0 for name in GATED_ROWS}

#: reference path and unit per row family, for the report line
_REF = {**{n: ("loop", "us/sweep") for n in STACK_ROWS},
        **{n: ("rebuild", "us/search") for n in DELTA_ROWS},
        **{n: ("loop", "us/sweep") for n in LLM_ROWS},
        **{n: ("simulator", "us/sweep") for n in EXEC_ROWS},
        **{n: ("naive", "us/run") for n in EXEC_JAX_ROWS}}
_REF["delta_service_qps"] = ("rebuild", "us/query")


def _rows_from_csv(path: str):
    rows = []
    with open(path) as f:
        for line in f:
            parts = line.strip().split(",")
            if parts and parts[0] in GATED_ROWS:
                rows.append((parts[0], float(parts[1]), float(parts[2])))
    missing = set(GATED_ROWS) - {name for name, _, _ in rows} - OPTIONAL_ROWS
    if missing:
        raise SystemExit(f"{path} is missing gated rows {sorted(missing)} — "
                         "did benchmarks.run fail before producing them?")
    return rows


def main() -> None:
    if len(sys.argv) > 1:
        rows = _rows_from_csv(sys.argv[1])
    else:
        from .bench_delta import bench_delta_local_search, bench_service_qps
        from .bench_exec import bench_exec_agreement, bench_exec_schedules
        from .bench_kernels import bench_phase_stack
        from .bench_llm_workloads import bench_llm_workloads
        rows = (bench_phase_stack() + bench_delta_local_search()
                + bench_service_qps()
                + [r for r in bench_llm_workloads() if r[0] in GATED_ROWS]
                + [r for r in bench_exec_agreement() if r[0] in GATED_ROWS]
                + [r for r in bench_exec_schedules() if r[0] in GATED_ROWS])
    failed = False
    for name, us, speedup in rows:
        ref, unit = _REF[name]
        floor = THRESHOLD[name]
        ok = speedup >= floor
        status = "ok" if ok else f"SLOWER THAN {ref.upper()} (< {floor}x)"
        print(f"{name}: {us:.0f} {unit}, {speedup:.2f}x vs {ref}  "
              f"[{status}]")
        failed |= not ok
    if failed:
        sys.exit(1)


if __name__ == "__main__":
    main()
