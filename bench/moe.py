"""One MoE layer's expert-parallel exchange as messages, from a seed.

The routing and lowering arithmetic of the program's MoE workload, kept
here so that a change to the program cannot move the traffic: a seeded
top-K routing histogram, balanced (every token's experts uniform),
clipped at the dispatch buffer's capacity per (rank, expert), summed over
each destination rank's contiguous expert shard, with self-pairs dropped.
Combine is dispatch reversed, in ``(src, dst)`` order.
"""
from __future__ import annotations

import numpy as np


def capacity(tokens_per_rank: int, top_k: int, capacity_factor: float,
             n_experts: int) -> int:
    """Slots per (rank, expert) of the dispatch buffer."""
    return max(8, int(tokens_per_rank * top_k * capacity_factor
                      // n_experts) + 1)


def routing_counts(rng, n_ranks: int, tokens_per_rank: int, n_experts: int,
                   top_k: int) -> np.ndarray:
    """``[n_ranks, n_experts]`` tokens routed: every token picks ``top_k``
    distinct experts, uniformly (the balance that the model's training
    losses aim at)."""
    n_tokens = n_ranks * tokens_per_rank
    keys = rng.random((n_tokens, n_experts))
    experts = np.argpartition(-keys, top_k - 1, axis=1)[:, :top_k]
    rank = np.repeat(np.arange(n_ranks, dtype=np.int64), tokens_per_rank)
    flat = rank[:, None] * n_experts + experts
    return np.bincount(flat.ravel(), minlength=n_ranks * n_experts
                       ).reshape(n_ranks, n_experts)


def exchange(counts, cap: int, token_bytes: int):
    """``(dispatch, combine)``, each ``(src, dst, size)`` in bytes."""
    counts = np.asarray(counts, dtype=np.int64)
    m, e = counts.shape
    pair = np.minimum(counts, cap).reshape(m, m, e // m).sum(axis=2)
    np.fill_diagonal(pair, 0)
    src, dst = np.nonzero(pair)
    size = pair[src, dst].astype(np.float64) * token_bytes
    order = np.lexsort((src, dst))
    return ((src.astype(np.int64), dst.astype(np.int64), size),
            (dst[order].astype(np.int64), src[order].astype(np.int64),
             size[order].copy()))


def draw(cfg: dict, tokens_per_rank: int, seed: int):
    """One seeded routing draw of the configuration's layer: ``(dispatch,
    combine)`` message sets at ``tokens_per_rank`` tokens per rank."""
    model = cfg["model"]
    ranks = int(cfg["expert_parallel"])
    counts = routing_counts(np.random.default_rng(seed), ranks,
                            tokens_per_rank, model["n_routed_experts"],
                            model["num_experts_per_tok"])
    cap = capacity(tokens_per_rank, model["num_experts_per_tok"],
                   model["capacity_factor"], model["n_routed_experts"])
    return exchange(counts, cap, model["hidden_size"] * model["act_bytes"])
