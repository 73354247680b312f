"""DeepSeek-V3's expert-parallel exchange as messages, from a seed.

The plain reference of the traffic kind ``exchange_dedup``, written with
per-token loops and importing nothing of the program, so that a change to
the program cannot move it: iid uniform scores for every (token, expert)
from the routing seed; node-limited top-K (each token keeps the
``topk_group`` of ``n_group`` contiguous expert groups with the highest
sum of their two best scores, then its ``top_k`` best experts inside them,
ties to the lower index); one copy per token and destination rank that
holds any of its experts, self-pairs dropped, nothing dropped.  Dispatch
carries ``dispatch_bytes`` a copy in ``(src, dst)`` order; combine the same
pairs reversed at ``combine_bytes`` a copy, in ``(src, dst)`` order.
"""
from __future__ import annotations

import numpy as np


def scores(seed: int, n_tokens: int, n_experts: int) -> np.ndarray:
    """``[n_tokens, n_experts]`` iid uniform scores from ``seed``."""
    return np.random.default_rng(seed).random((n_tokens, n_experts))


def route(row, top_k: int, n_group: int, topk_group: int) -> list[int]:
    """One token's experts, best first, from its scores ``row``."""
    per = len(row) // n_group

    def group_score(g):
        return sum(sorted(row[g * per:(g + 1) * per], reverse=True)[:2])

    groups = sorted(range(n_group), key=lambda g: (-group_score(g), g))
    allowed = [e for g in groups[:topk_group]
               for e in range(g * per, (g + 1) * per)]
    return sorted(allowed, key=lambda e: (-row[e], e))[:top_k]


def exchange(choices, n_ranks: int, n_experts: int, dispatch_bytes: int,
             combine_bytes: int):
    """``(dispatch, combine)``, each ``(src, dst, size)`` in bytes, of the
    per-token ``choices`` (rows rank by rank, equally many a rank)."""
    per_rank = len(choices) // n_ranks
    experts_per_rank = n_experts // n_ranks
    copies: dict[tuple[int, int], int] = {}
    for t, experts in enumerate(choices):
        src = t // per_rank
        for dst in {e // experts_per_rank for e in experts}:
            if dst != src:
                copies[src, dst] = copies.get((src, dst), 0) + 1

    def messages(pairs: dict, width: int):
        keys = sorted(pairs)
        return (np.array([s for s, _ in keys], dtype=np.int64),
                np.array([d for _, d in keys], dtype=np.int64),
                np.array([float(pairs[k] * width) for k in keys]))

    back = {(d, s): c for (s, d), c in copies.items()}
    return messages(copies, dispatch_bytes), messages(back, combine_bytes)


def draw(cfg: dict, mix: dict):
    """The scores of one seeded draw and the messages they give for the
    configuration's layer at the traffic's tokens a rank: ``(scores,
    (dispatch, combine))``."""
    if mix["scores"] != "uniform":
        raise ValueError(f"unknown scores {mix['scores']!r}")
    n = int(cfg["expert_parallel"])
    e = int(cfg["n_routed_experts"])
    s = scores(int(mix["routing_seed"]), n * int(mix["tokens_per_rank"]), e)
    choices = [route(row, int(cfg["num_experts_per_tok"]),
                     int(cfg["n_group"]), int(cfg["topk_group"]))
               for row in s.tolist()]
    return s, exchange(choices, n, e, int(mix["dispatch_bytes"]),
                       int(mix["combine_bytes"]))
