"""MoE expert-parallel all-to-all traffic as irregular point-to-point phases.

The optimized MoE path in this repo (:mod:`repro.parallel.ep_a2a`) moves
tokens between ranks with two ``jax.lax.all_to_all`` exchanges: **dispatch**
ships every routed token from its origin rank to the rank owning its expert,
and **combine** returns the expert outputs along the exact reverse routes.
Which rank owes how many tokens to which rank is decided by the *router* —
a data-dependent top-K choice — so the exchange is exactly the kind of
irregular point-to-point phase the paper's node-aware + queue-search model
prices: per-pair sizes follow the token-routing histogram, not a regular
collective schedule.

This module derives those phases without running any jax: a routing-count
histogram ``counts[rank, expert]`` is lowered to ``(src, dst, size)``
triples (:func:`pattern_from_counts`) that mirror the ``ep_a2a`` schedule —
per-(rank, expert) capacity clipping included — with the histogram itself
coming either from a seeded numpy **router forward pass** (the same
logits → softmax → top-K math as :func:`repro.nn.moe.moe_ffn`, reproduced
in numpy so the derivation runs where jax is absent) or from a seeded
synthetic **top-K multinomial** with a skewed expert-popularity prior.

DeepSeek-V3's exchange (arXiv:2412.19437) needs each token's choices, not
a histogram: its router scores by sigmoid and routes node-limited
(:func:`node_limited_topk`), and its DeepEP-style exchange sends a token
once to each chip that holds any of its experts, in FP8 with per-128
float32 scales, and returns one summed BF16 vector per token and chip,
with nothing dropped (:func:`pattern_from_choices`,
:func:`dedup_a2a_pattern`).

With tracing on (:mod:`repro.comm.obs`), routing runs in the span
``repro.workload.route`` (stats ``tokens``, ``groups``) and lowering in
``repro.workload.lower``, which counts ``moe.expert_copies`` (off-rank
(token, expert) assignments), ``moe.token_copies`` (token copies that
ride the dispatch) and ``moe.dropped`` (assignments lost to capacity).

RNG contract (pinned by the property tests): every function takes an
integer ``seed`` and creates its own ``np.random.default_rng(seed)`` —
the same seed always yields bit-identical histograms and patterns across
calls, processes and platforms; no global numpy state is read or written.
"""
from __future__ import annotations

import dataclasses

import numpy as np

from repro.comm import obs
from repro.nn.config import ArchConfig
from repro.sparse.partition import CommPattern

#: Bytes per activation element crossing the wire (bf16, matching the
#: production stack's activation dtype).
ACT_BYTES = 2

#: DeepSeek-V3's FP8 dispatch: one byte an element and one float32 scale
#: per tile of ``FP8_TILE`` elements.
FP8_TILE = 128
SCALE_BYTES = 4


@dataclasses.dataclass(frozen=True)
class MoeA2APattern:
    """Both exchanges of one MoE layer's expert-parallel all-to-all.

    ``dispatch`` carries routed tokens origin-rank → expert-rank; ``combine``
    is its exact mirror (same pair copies, direction reversed) — expert
    outputs travel back along the routes the tokens arrived on, which is the
    flow-conservation identity the property tests certify.  ``counts`` is
    the raw routing histogram ``[n_ranks, n_experts]``; ``sent`` is the same
    histogram after per-(rank, expert) capacity clipping (what actually
    rides the wire); ``capacity`` is the per-expert slot count of the
    ``ep_a2a`` buffer (None: dropless); ``token_bytes`` the wire size of
    one token copy in dispatch.
    """

    dispatch: CommPattern
    combine: CommPattern
    counts: np.ndarray          # [n_ranks, n_experts] routed assignments
    sent: np.ndarray            # [n_ranks, n_experts] after capacity clip
    capacity: int | None
    token_bytes: int

    @property
    def n_ranks(self) -> int:
        return self.dispatch.n_procs

    @property
    def dropped_tokens(self) -> int:
        """Assignments lost to capacity clipping (over-capacity drops)."""
        return int((self.counts - self.sent).sum())

    def phases(self) -> list[tuple[str, CommPattern]]:
        """The two exchanges in schedule order, labelled."""
        return [("dispatch", self.dispatch), ("combine", self.combine)]


def a2a_capacity(tokens_per_rank: int, cfg: ArchConfig) -> int:
    """Per-expert capacity of the ``ep_a2a`` dispatch buffer.

    The same formula :func:`repro.parallel.ep_a2a.moe_ffn_ep` computes
    inline from ``tokens_per_rank`` (its per-shard token count ``T``) and
    ``cfg`` (``n_experts_active``, ``capacity_factor``, ``n_experts``);
    kept in sync by the jax cross-check in ``tests/test_workloads.py``.
    """
    return max(8, int(tokens_per_rank * cfg.n_experts_active
                      * cfg.capacity_factor // cfg.n_experts) + 1)


def synthetic_routing_counts(n_ranks: int, tokens_per_rank: int,
                             n_experts: int, top_k: int, seed: int = 0,
                             concentration: float = 0.3) -> np.ndarray:
    """Seeded synthetic routing histogram: top-K multinomial token routing.

    Each of the ``n_ranks * tokens_per_rank`` tokens picks ``top_k``
    *distinct* experts out of ``n_experts`` with probability proportional to
    a shared expert-popularity vector drawn from a symmetric Dirichlet with
    parameter ``concentration`` (< 1 skews popular experts — the hot-expert
    imbalance real routers exhibit).  Sampling-without-replacement is the
    Gumbel-top-K trick, fully vectorized.  Returns integer counts
    ``[n_ranks, n_experts]``.  ``seed`` follows the module RNG contract:
    same seed, bit-identical histogram.
    """
    if top_k > n_experts:
        raise ValueError(f"top_k ({top_k}) cannot exceed n_experts "
                         f"({n_experts})")
    rng = np.random.default_rng(seed)
    popularity = rng.dirichlet(np.full(n_experts, concentration))
    # Gumbel top-K over log-popularity == K draws without replacement
    n_tokens = n_ranks * tokens_per_rank
    keys = np.log(popularity)[None, :] + rng.gumbel(size=(n_tokens, n_experts))
    experts = np.argpartition(-keys, top_k - 1, axis=1)[:, :top_k]
    rank_of_token = np.repeat(np.arange(n_ranks, dtype=np.int64),
                              tokens_per_rank)
    flat = rank_of_token[:, None] * n_experts + experts
    return np.bincount(flat.ravel(), minlength=n_ranks * n_experts) \
             .reshape(n_ranks, n_experts)


def node_limited_topk(scores, top_k: int, n_group: int = 1,
                      topk_group: int = 1) -> np.ndarray:
    """Each token's ``top_k`` experts under node-limited routing.

    ``scores`` is ``[tokens, experts]``, the experts split into ``n_group``
    contiguous groups.  A token keeps the ``topk_group`` groups with the
    highest sum of their top-2 scores (the top-1 where a group holds one
    expert), then picks its ``top_k`` highest-scoring experts inside them
    (DeepSeek-V3's ``noaux_tc`` selection, with its learned bias at zero).
    Ties go to the lower index at both steps (stable descending argsort).
    Returns ``[tokens, top_k]`` int64 expert ids, best first; with
    ``topk_group == n_group`` that is the plain stable top-K.
    """
    scores = np.asarray(scores)
    T, E = scores.shape
    if n_group < 1 or E % n_group or not 1 <= topk_group <= n_group:
        raise ValueError(f"{E} experts cannot route {topk_group} of "
                         f"{n_group} groups")
    if top_k > topk_group * (E // n_group):
        raise ValueError(f"top_k ({top_k}) exceeds the {topk_group} "
                         f"groups' {topk_group * (E // n_group)} experts")
    with obs.span("repro.workload.route", tokens=T, groups=n_group):
        if topk_group < n_group:
            per = E // n_group
            grouped = np.sort(scores.reshape(T, n_group, per), axis=2)
            group_score = grouped[:, :, -2:].sum(axis=2)
            keep = np.argsort(-group_score, axis=1,
                              kind="stable")[:, :topk_group]
            allowed = np.zeros((T, n_group), dtype=bool)
            np.put_along_axis(allowed, keep, True, axis=1)
            scores = np.where(np.repeat(allowed, per, axis=1), scores,
                              -np.inf)
        return np.argsort(-scores, axis=1, kind="stable")[:, :top_k]


def router_choices(cfg: ArchConfig, n_ranks: int, tokens_per_rank: int,
                   seed: int = 0) -> np.ndarray:
    """Each token's experts from a seeded router forward pass (numpy).

    Runs the router math of :func:`repro.nn.moe.moe_ffn` — token activations
    × router weight matrix → float32 logits → ``cfg.scoring_func``
    (softmax, or DeepSeek-V3's sigmoid) → top-K, node-limited to
    ``cfg.topk_group`` of ``cfg.n_group`` groups (:func:`node_limited_topk`)
    — on seeded Gaussian activations and a seeded Gaussian router
    ``[cfg.d_model, cfg.n_experts]`` (scaled ``1/sqrt(d)``), entirely in
    numpy so the derivation runs where jax is absent.  Top-K uses a stable
    descending argsort, which matches ``jax.lax.top_k``'s lowest-index
    tie-breaking on identical logits (asserted against the real jax routing
    in ``tests/test_workloads.py`` when jax is importable).  Returns
    ``[n_ranks * tokens_per_rank, n_experts_active]`` expert ids, rank by
    rank (``tokens_per_rank`` tokens each); ``seed`` per the module RNG
    contract.
    """
    rng = np.random.default_rng(seed)
    d, E, K = cfg.d_model, cfg.n_experts, cfg.n_experts_active
    if not (E and K):
        raise ValueError(f"{cfg.name!r} is not a MoE config "
                         f"(n_experts={E}, n_experts_active={K})")
    n_tokens = n_ranks * tokens_per_rank
    x = rng.standard_normal((n_tokens, d)).astype(np.float32)
    router = (rng.standard_normal((d, E)) / np.sqrt(d)).astype(np.float32)
    logits = x @ router
    if cfg.scoring_func == "softmax":
        # monotone per row, kept for fidelity with the moe_ffn path
        z = np.exp(logits - logits.max(axis=1, keepdims=True))
        scores = z / z.sum(axis=1, keepdims=True)
    elif cfg.scoring_func == "sigmoid":
        scores = 1.0 / (1.0 + np.exp(-logits))
    else:
        raise ValueError(f"unknown scoring_func {cfg.scoring_func!r}")
    return node_limited_topk(scores, K, cfg.n_group, cfg.topk_group)


def choice_counts(choices, n_ranks: int, n_experts: int) -> np.ndarray:
    """The ``[n_ranks, n_experts]`` histogram of per-token ``choices``
    (rows rank by rank, equally many a rank)."""
    choices = np.asarray(choices, dtype=np.int64)
    rank = np.repeat(np.arange(n_ranks, dtype=np.int64),
                     len(choices) // n_ranks)
    flat = rank[:, None] * n_experts + choices
    return np.bincount(flat.ravel(), minlength=n_ranks * n_experts) \
             .reshape(n_ranks, n_experts)


def router_routing_counts(cfg: ArchConfig, n_ranks: int, tokens_per_rank: int,
                          seed: int = 0) -> np.ndarray:
    """Routing histogram ``[n_ranks, n_experts]`` of the seeded router
    forward pass :func:`router_choices` for ``cfg``, ``tokens_per_rank``
    tokens a rank; ``seed`` per the module RNG contract."""
    return choice_counts(router_choices(cfg, n_ranks, tokens_per_rank,
                                        seed=seed), n_ranks, cfg.n_experts)


def _exchange(pair_tokens, dispatch_bytes: int, combine_bytes: int,
              where: str) -> tuple[CommPattern, CommPattern]:
    """Token copies per ``(src, dst)`` rank pair as the dispatch pattern
    (pairs in row-major order) and its mirror, the combine pattern (pairs
    reversed, in canonical ``(src, dst)`` order); self-pairs are local
    buffer traffic and send nothing."""
    pair_tokens = np.array(pair_tokens, dtype=np.int64)
    M = len(pair_tokens)
    np.fill_diagonal(pair_tokens, 0)
    src, dst = np.nonzero(pair_tokens)
    copies = pair_tokens[src, dst].astype(np.float64)
    dispatch = CommPattern(src=src.astype(np.int64), dst=dst.astype(np.int64),
                           size=copies * dispatch_bytes, n_procs=M).validate(
                               where=f"{where}(dispatch)")
    order = np.lexsort((src, dst))              # canonical (src, dst) order
    combine = CommPattern(src=dst[order].astype(np.int64),
                          dst=src[order].astype(np.int64),
                          size=copies[order] * combine_bytes,
                          n_procs=M).validate(where=f"{where}(combine)")
    return dispatch, combine


def _count_copies(expert_copies, token_copies, dropped) -> None:
    obs.count("moe.expert_copies", expert_copies)
    obs.count("moe.token_copies", token_copies)
    obs.count("moe.dropped", dropped)


def _off_diagonal(pairs) -> int:
    return int(pairs.sum() - np.trace(pairs))


def pattern_from_counts(counts, d_model: int, capacity: int | None,
                        act_bytes: int = ACT_BYTES) -> MoeA2APattern:
    """Lower a routing histogram to the two-exchange ``ep_a2a`` message set.

    ``counts[r, e]`` tokens routed by rank ``r`` to expert ``e`` are clipped
    at ``capacity`` slots per (rank, expert) — the ``[E, C]`` dispatch
    buffer of :func:`repro.parallel.ep_a2a.moe_ffn_ep` drops over-capacity
    tokens per *source* rank; None drops nothing — then summed over each
    destination rank's contiguous expert shard (expert ``e`` lives on rank
    ``e // (E // M)``, the ``shard_map``-over-experts layout): one copy per
    (token, expert).  Dispatch message sizes are ``tokens * d_model *
    act_bytes``; self-pairs (tokens staying on their origin rank) are local
    buffer traffic, not communication, and are dropped.  The combine
    exchange reuses the same pair volumes with src/dst swapped.
    Deterministic: no randomness, so equal ``counts`` (plus equal
    ``d_model`` / ``capacity`` / ``act_bytes``) give bit-identical patterns.
    """
    counts = np.asarray(counts, dtype=np.int64)
    if counts.ndim != 2:
        raise ValueError(f"counts must be [n_ranks, n_experts], "
                         f"got shape {counts.shape}")
    M, E = counts.shape
    if E % M:
        raise ValueError(f"n_experts ({E}) must divide evenly over "
                         f"n_ranks ({M}), as in ep_a2a")
    with obs.span("repro.workload.lower"):
        sent = counts if capacity is None else np.minimum(counts,
                                                          int(capacity))
        # tokens per (src rank, dst rank): sum each destination's expert shard
        pair_tokens = sent.reshape(M, M, E // M).sum(axis=2)
        token_bytes = int(d_model) * int(act_bytes)
        dispatch, combine = _exchange(pair_tokens, token_bytes, token_bytes,
                                      "pattern_from_counts")
        _count_copies(_off_diagonal(counts.reshape(M, M, E // M).sum(axis=2)),
                      _off_diagonal(pair_tokens), int((counts - sent).sum()))
    return MoeA2APattern(dispatch=dispatch, combine=combine, counts=counts,
                         sent=sent,
                         capacity=None if capacity is None else int(capacity),
                         token_bytes=token_bytes)


def pattern_from_choices(choices, n_ranks: int, n_experts: int,
                         dispatch_bytes: int,
                         combine_bytes: int) -> MoeA2APattern:
    """Lower each token's expert ``choices`` to a deduplicated, dropless
    exchange.

    ``choices`` is ``[n_ranks * tokens_per_rank, top_k]`` expert ids, rank
    by rank; expert ``e`` lives on rank ``e // (n_experts // n_ranks)``.  A
    token goes once to each other rank that holds any of its experts,
    however many of them that rank holds (one copy per (token, rank) pair),
    at ``dispatch_bytes`` a copy; combine returns one summed vector per
    copy along the reversed pairs, at ``combine_bytes``.  No assignment is
    dropped (``capacity`` None, ``sent == counts``).
    """
    choices = np.asarray(choices, dtype=np.int64)
    T = len(choices)
    if T % n_ranks or n_experts % n_ranks:
        raise ValueError(f"{T} tokens and {n_experts} experts must divide "
                         f"evenly over {n_ranks} ranks")
    with obs.span("repro.workload.lower"):
        rank = np.repeat(np.arange(n_ranks, dtype=np.int64), T // n_ranks)
        owner = choices // (n_experts // n_ranks)
        hit = np.zeros((T, n_ranks), dtype=bool)
        np.put_along_axis(hit, owner, True, axis=1)
        pair_tokens = np.zeros((n_ranks, n_ranks), dtype=np.int64)
        np.add.at(pair_tokens, rank, hit)
        dispatch, combine = _exchange(pair_tokens, int(dispatch_bytes),
                                      int(combine_bytes),
                                      "pattern_from_choices")
        counts = choice_counts(choices, n_ranks, n_experts)
        _count_copies(int((owner != rank[:, None]).sum()),
                      _off_diagonal(pair_tokens), 0)
    return MoeA2APattern(dispatch=dispatch, combine=combine, counts=counts,
                         sent=counts, capacity=None,
                         token_bytes=int(dispatch_bytes))


def fp8_token_bytes(d_model: int) -> int:
    """Wire bytes of one ``d_model``-wide token in FP8 with a float32 scale
    per ``FP8_TILE`` elements (DeepSeek-V3's dispatch: 7,168 + 56 x 4 =
    7,392 B)."""
    return int(d_model) + -(-int(d_model) // FP8_TILE) * SCALE_BYTES


def moe_a2a_pattern(cfg: ArchConfig, n_ranks: int, tokens_per_rank: int,
                    seed: int = 0, source: str = "synthetic",
                    act_bytes: int = ACT_BYTES) -> MoeA2APattern:
    """One MoE layer's expert-parallel all-to-all for ``cfg`` on ``n_ranks``.

    ``source`` picks the routing histogram: ``"router"`` runs the seeded
    numpy router forward pass (:func:`router_routing_counts`),
    ``"synthetic"`` the top-K multinomial fallback
    (:func:`synthetic_routing_counts`).  ``tokens_per_rank`` tokens are
    routed per rank and lowered through :func:`pattern_from_counts` with the
    ``ep_a2a`` capacity for that token count (:func:`a2a_capacity`);
    ``act_bytes`` scales the per-token wire size.  ``seed`` per the module
    RNG contract: same seed (and same arguments) → bit-identical pattern.
    """
    if source == "router":
        counts = router_routing_counts(cfg, n_ranks, tokens_per_rank,
                                       seed=seed)
    elif source == "synthetic":
        counts = synthetic_routing_counts(n_ranks, tokens_per_rank,
                                          cfg.n_experts,
                                          cfg.n_experts_active, seed=seed)
    else:
        raise ValueError(f"unknown source {source!r}; expected 'router' "
                         "or 'synthetic'")
    return pattern_from_counts(counts, cfg.d_model,
                               a2a_capacity(tokens_per_rank, cfg),
                               act_bytes=act_bytes)


def dedup_a2a_pattern(cfg: ArchConfig, n_ranks: int, tokens_per_rank: int,
                      seed: int = 0) -> MoeA2APattern:
    """DeepSeek-V3's expert-parallel exchange for ``cfg`` on ``n_ranks``
    at ``tokens_per_rank`` tokens a rank: the seeded router pass
    (:func:`router_choices`, node-limited where ``cfg`` says so) lowered
    one copy per (token, rank), dropless (:func:`pattern_from_choices`),
    dispatch in FP8 with per-128 scales (:func:`fp8_token_bytes`) and
    combine in BF16.  ``seed`` per the module RNG contract."""
    choices = router_choices(cfg, n_ranks, tokens_per_rank, seed=seed)
    return pattern_from_choices(choices, n_ranks, cfg.n_experts,
                                fp8_token_bytes(cfg.d_model),
                                cfg.d_model * ACT_BYTES)
