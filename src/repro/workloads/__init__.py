"""Real LLM traffic shapes, derived from the in-repo model stack, priced
by the comm model.

The :mod:`repro.nn` / :mod:`repro.parallel` half of the repo *generates*
irregular point-to-point communication (MoE expert all-to-all, TP ring
collectives, pipeline stage boundaries); the :mod:`repro.comm` /
:mod:`repro.core` half *prices* it.  This package connects them: numpy-only
derivations of :class:`repro.sparse.CommPattern` from the real schedules
(capacity formulas, sharding rules and microbatch counts are taken from —
and cross-checked against — the jax implementations, without importing
jax), plus a scenario registry that sweeps every derived shape through one
:func:`repro.comm.strategies.best_strategy_many` arena.
"""
from .moe import (ACT_BYTES, MoeA2APattern, a2a_capacity, choice_counts,
                  dedup_a2a_pattern, fp8_token_bytes, moe_a2a_pattern,
                  node_limited_topk, pattern_from_choices,
                  pattern_from_counts, router_choices, router_routing_counts,
                  synthetic_routing_counts)
from .pipe import pipeline_p2p_pattern
from .registry import (DEFAULT_SCENARIOS, SCENARIOS, Scenario, SweepRow,
                       default_machines, scenario_patterns, sweep,
                       winner_table)
from .tp import (TpCollectives, row_parallel_ops_from_pspecs,
                 row_parallel_ops_per_layer, tp_collective_patterns)

__all__ = [
    "ACT_BYTES", "MoeA2APattern", "a2a_capacity", "moe_a2a_pattern",
    "pattern_from_counts", "router_routing_counts", "synthetic_routing_counts",
    "node_limited_topk", "router_choices", "choice_counts",
    "pattern_from_choices", "fp8_token_bytes", "dedup_a2a_pattern",
    "pipeline_p2p_pattern",
    "TpCollectives", "row_parallel_ops_from_pspecs",
    "row_parallel_ops_per_layer", "tp_collective_patterns",
    "DEFAULT_SCENARIOS", "SCENARIOS", "Scenario", "SweepRow",
    "default_machines", "scenario_patterns", "sweep", "winner_table",
]
