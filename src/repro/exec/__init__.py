"""Strategy execution: lower rewrites to runnable JAX schedules and close
the measured-vs-predicted loop (DESIGN.md §14).

Pipeline: :func:`~repro.exec.plan.build_schedule` lowers one strategy of a
bound phase to permutation rounds (:mod:`repro.exec.plan`); the serial
numpy executor replays them as the bit-identity oracle
(:mod:`repro.exec.reference`); the jitted ``shard_map`` + ``ppermute``
program runs them on a device mesh (:mod:`repro.exec.lower`); timed runs
and ordering comparisons live in :mod:`repro.exec.measure`; fitted
parameter tables from recorded sweeps in :mod:`repro.exec.calibrate`; and
:mod:`repro.exec.presets` ships 8-rank host-scale machines for the forced
host mesh.  Everything imports without jax — only actually *running* a
lowered schedule needs it.
"""
from .calibrate import (CalibrationResult, SweepRecord, calibrate,
                        record_sweeps)
from .lower import build_executor, execute, executor_program
from .measure import (Measurement, launch_overhead, measure_strategies,
                      ordering, pairwise_agreement, predicted_costs,
                      time_schedule)
from .plan import (COLORINGS, UNIT_BYTES, ExecPhase, ExecRound, ExecSchedule,
                   build_schedule, pairs_subset_of_plan, synth_payload,
                   units_for)
from .presets import (HOST_PROCS, blue_waters_8, frontier_8, host_machines,
                      lassen_8, tpu_v5e_8)
from .reference import delivered_digest, reference_delivered, run_reference

__all__ = [
    "COLORINGS", "UNIT_BYTES", "ExecPhase", "ExecRound", "ExecSchedule",
    "build_schedule", "pairs_subset_of_plan", "synth_payload", "units_for",
    "reference_delivered", "run_reference", "delivered_digest",
    "build_executor", "execute", "executor_program",
    "Measurement", "time_schedule", "launch_overhead", "measure_strategies",
    "predicted_costs", "ordering", "pairwise_agreement",
    "SweepRecord", "CalibrationResult", "record_sweeps", "calibrate",
    "HOST_PROCS", "blue_waters_8", "tpu_v5e_8", "lassen_8", "frontier_8",
    "host_machines",
]
