"""Communication performance models (postal -> max-rate -> node-aware -> +queue/+contention).

All functions are vectorized over *message arrays*: ``size[i]`` bytes from
process ``src[i]`` to ``dst[i]`` with locality class ``loc[i]``.  Aggregation
follows the paper: per-process transport sums (max over processes), a single
worst-process queue term ``gamma * n^2`` and a single contention term
``delta * ell`` per phase.

Model hierarchy (each row adds one of the paper's contributions):

==============  =====================================================
``postal``      T = alpha + s / Rb                      (single class)
``maxrate``     T = alpha + ppn*s / min(RN, ppn*Rb)     (single class)
``node_aware``  per-locality (alpha, Rb, RN)            (Section 3)
``+queue``      + gamma * n_recv^2                      (Section 4.1)
``+contention`` + delta * ell                           (Section 4.2)
==============  =====================================================
"""
from __future__ import annotations

import dataclasses

import numpy as np

from repro.comm import obs
from repro.comm.delta import ARENA_TYPES as _ARENAS
from repro.comm.primitives import active_senders_per_node, transport_times
from repro.comm.stack import PhaseStack, as_stack

from .params import CommParams
from .topology import contention_ell

MODEL_LEVELS = ("postal", "maxrate", "node_aware", "queue", "contention")


@dataclasses.dataclass(frozen=True)
class CostBreakdown:
    """Seconds per phase, split by source (paper Figs. 10-11 stacked bars)."""

    transport: float       # max-rate (or postal) term, max over processes
    queue: float           # gamma * n^2, worst process
    contention: float      # delta * ell
    total: float

    def as_dict(self) -> dict[str, float]:
        return dataclasses.asdict(self)


# -- per-message time ------------------------------------------------------

def message_time(params: CommParams, size, loc, ppn=1, node_aware: bool = True,
                 use_maxrate: bool = True) -> np.ndarray:
    """Vectorized single-message time.

    ``ppn`` is the number of *actively communicating* processes on the sending
    node (scalar or per-message array).  With ``node_aware=False`` every
    message is priced with the network-class parameters (the paper's Fig.-2
    baseline).  With ``use_maxrate=False`` the injection cap is ignored
    (pure postal).
    """
    size = np.asarray(size, dtype=np.float64)
    loc = np.asarray(loc, dtype=np.int64)
    if not node_aware:
        loc = np.full_like(loc, params.network_locality)
    proto = params.protocol_of(size)
    alpha = params.alpha[loc, proto]
    Rb = params.Rb[loc, proto]
    if not use_maxrate:
        return transport_times(size, alpha, Rb, None, 1.0, False,
                               use_maxrate=False)
    # only network-class messages contend for injection bandwidth; a node's
    # active senders divide across its NICs (CommParams.n_rails)
    return transport_times(size, alpha, Rb, params.RN[loc, proto], ppn,
                           loc >= params.network_locality,
                           rails=params.n_rails)


def queue_time(params: CommParams, n_messages) -> np.ndarray:
    """Paper Eq. (3): T_q = gamma * n^2 (upper bound, adverse receive order)."""
    n = np.asarray(n_messages, dtype=np.float64)
    return params.gamma * n * n


def contention_time(params: CommParams, n_torus_nodes: int, torus_ndim: int,
                    avg_net_bytes_per_proc: float, procs_per_torus_node: int) -> float:
    """Paper Eqs. (5)-(7): T_c = delta * ell, cube-partition estimate."""
    ell = contention_ell(n_torus_nodes, torus_ndim, avg_net_bytes_per_proc,
                         procs_per_torus_node)
    return float(params.delta * ell)


# -- phase-level aggregation ------------------------------------------------

def _sender_nodes(src: np.ndarray, node_of) -> np.ndarray:
    """Resolve a process->node map (array or callable) to per-message nodes."""
    if callable(node_of):
        try:
            nodes = np.asarray(node_of(src), dtype=np.int64)
            if nodes.shape != src.shape:
                raise TypeError
        except (TypeError, ValueError):   # scalar-only callable fallback
            nodes = np.asarray([node_of(int(p)) for p in src], dtype=np.int64)
        return nodes
    return np.asarray(node_of, dtype=np.int64)[src]


def phase_cost(params: CommParams, src, dst, size, loc, *,
               node_of=None,
               n_torus_nodes: int | None = None,
               torus_ndim: int = 3,
               procs_per_torus_node: int = 1,
               n_procs: int | None = None,
               level: str = "contention",
               active_ppn=None, validate: bool = False) -> CostBreakdown:
    """Model the cost of one communication phase (e.g. one SpMV halo exchange).

    Parameters
    ----------
    src, dst, size, loc : per-message arrays.
    node_of : process -> node map (callable or array); required for max-rate.
    n_torus_nodes, torus_ndim, procs_per_torus_node : contention geometry.
    level : which rung of the model ladder to evaluate (``MODEL_LEVELS``).
    active_ppn : precomputed active-senders-per-node array (e.g. the cached
        ``CommPhase.active_ppn``); skips the ``node_of`` recomputation.
    validate : run the typed validation layer
        (:func:`repro.comm.guard.validate_messages`) over the message
        arrays first — NaN/negative sizes and out-of-range ranks raise a
        precise :class:`repro.comm.guard.PatternError` subclass instead of
        pricing garbage.
    """
    if level not in MODEL_LEVELS:
        raise ValueError(f"unknown model level {level!r}")
    if validate:
        from repro.comm.guard import validate_messages
        validate_messages(np.asarray(src).ravel(), np.asarray(dst).ravel(),
                          np.asarray(size).ravel(), n_procs=n_procs,
                          where="phase_cost")
    src = np.asarray(src, dtype=np.int64)
    dst = np.asarray(dst, dtype=np.int64)
    size = np.asarray(size, dtype=np.float64)
    loc = np.asarray(loc, dtype=np.int64)
    node_aware = MODEL_LEVELS.index(level) >= MODEL_LEVELS.index("node_aware")
    use_maxrate = MODEL_LEVELS.index(level) >= MODEL_LEVELS.index("maxrate")

    if src.size == 0:
        return CostBreakdown(0.0, 0.0, 0.0, 0.0)

    if use_maxrate and active_ppn is not None:
        ppn = np.asarray(active_ppn, dtype=np.float64)
    elif use_maxrate and node_of is not None:
        ppn = active_senders_per_node(src, _sender_nodes(src, node_of),
                                      loc >= params.network_locality)
    else:
        ppn = np.ones_like(size)
    t_msg = message_time(params, size, loc, ppn=ppn, node_aware=node_aware,
                         use_maxrate=use_maxrate)

    # transport: worst process over (send-side sums)
    n_procs = int(n_procs if n_procs is not None else max(src.max(), dst.max()) + 1)
    per_proc = np.bincount(src, weights=t_msg, minlength=n_procs)
    transport = float(per_proc.max())

    queue = 0.0
    if MODEL_LEVELS.index(level) >= MODEL_LEVELS.index("queue"):
        n_recv = np.bincount(dst, minlength=n_procs)
        queue = float(queue_time(params, n_recv.max()))

    cont = 0.0
    if level == "contention" and n_torus_nodes is not None and n_torus_nodes > 1:
        is_net = loc >= params.network_locality
        net_bytes = float(size[is_net].sum())
        if net_bytes > 0.0:
            b = net_bytes / n_procs   # avg bytes sent per process (paper's b)
            cont = contention_time(params, n_torus_nodes, torus_ndim, b,
                                   procs_per_torus_node)

    return CostBreakdown(transport, queue, cont, transport + queue + cont)


def model_ladder(params: CommParams, src, dst, size, loc, **kw) -> dict[str, CostBreakdown]:
    """Evaluate every model level on the same phase (for accuracy tables)."""
    return {lvl: phase_cost(params, src, dst, size, loc, level=lvl, **kw)
            for lvl in MODEL_LEVELS}


# -- batched entry points over CommPhase objects ----------------------------

def phase_cost_phase(phase, level: str = "contention",
                     params: CommParams | None = None) -> CostBreakdown:
    """Price one bound :class:`repro.comm.CommPhase` (duck-typed).

    Locality, active-sender counts and contention geometry all come from the
    phase's cached arrays and machine; ``params`` overrides the machine's
    ground-truth table (e.g. with a fitted one) while keeping the machine's
    locality classification.
    """
    m = phase.machine
    p = params if params is not None else m.params
    if p.network_locality == m.params.network_locality:
        ppn = phase.active_ppn
    else:
        # the cached counts were gated on the machine's network locality;
        # an override that reclassifies localities needs them recomputed
        ppn = active_senders_per_node(phase.src, phase.send_node,
                                      phase.loc >= p.network_locality)
    return phase_cost(p, phase.src, phase.dst, phase.size, phase.loc,
                      n_torus_nodes=m.torus.size, torus_ndim=m.torus.ndim,
                      procs_per_torus_node=m.procs_per_torus_node,
                      n_procs=phase.n_procs, level=level,
                      active_ppn=ppn)


def _stack_costs(stack: PhaseStack, level: str,
                 params: CommParams | None,
                 backend: str | None = None,
                 agg_cache: dict | None = None) -> list[CostBreakdown]:
    """Price a stacked sweep: one segmented pass per quantity, bit-identical
    to the :func:`phase_cost_phase` loop (see DESIGN.md §8).

    ``agg_cache`` memoizes the raw aggregates by (node_aware, use_maxrate):
    the three ladder levels at or above ``node_aware`` share the exact same
    transport pass, so a full-ladder sweep prices messages three times, not
    five (queue/net aggregates are level-independent stack caches anyway).
    """
    if stack.n_phases == 0:
        return []
    m = stack.machine
    p = params if params is not None else m.params
    rank = MODEL_LEVELS.index(level)
    with_queue = rank >= MODEL_LEVELS.index("queue")
    with_cont = level == "contention" and m.torus.size > 1
    flags = (rank >= MODEL_LEVELS.index("node_aware"),
             rank >= MODEL_LEVELS.index("maxrate"))
    if agg_cache is not None and flags in agg_cache:
        transport, max_recv, net_bytes = agg_cache[flags]
    else:
        transport, max_recv, net_bytes = stack.cost_arrays(
            p, node_aware=flags[0], use_maxrate=flags[1],
            # when memoizing, request the (cached, level-independent) queue
            # counts up front: the queue/contention levels reuse this entry.
            # Net bytes only matter on the node-aware branch — the levels
            # below never serve a contention row.
            with_queue=with_queue or agg_cache is not None,
            with_net_bytes=with_cont or (agg_cache is not None and flags[0]),
            backend=backend)
        if agg_cache is not None:
            agg_cache[flags] = (transport, max_recv, net_bytes)
    queue = queue_time(p, max_recv) if with_queue else np.zeros_like(transport)
    cont = np.zeros_like(transport)
    if with_cont:
        b = net_bytes / stack.n_procs    # avg bytes sent per process
        ell = contention_ell(m.torus.size, m.torus.ndim, b,
                             m.procs_per_torus_node)
        cont = np.where(net_bytes > 0.0, p.delta * ell, 0.0)
    return [CostBreakdown(float(t), float(q), float(c), float(t) + float(q)
                          + float(c))
            for t, q, c in zip(transport, queue, cont)]


def phase_cost_many(phases, level: str = "contention",
                    params: CommParams | None = None,
                    backend: str | None = None) -> list[CostBreakdown]:
    """Price a whole sweep of phases (an AMG hierarchy, a partition or
    machine scan) in one call.

    Fast path: phases bound to one machine (or an already-built
    :class:`repro.comm.PhaseStack` / :class:`repro.comm.DeltaStack`) are
    priced in one segmented pass via the arena — bit-identical to the
    per-phase loop, which remains the fallback for single phases and
    mixed-machine sweeps.  A ``DeltaStack`` is priced from its incremental
    caches (even for a single phase, which is the partition-optimizer case).
    ``backend`` selects the arena's reduction backend: numpy (``None``, the
    default) or ``'jax'``/``'pallas'`` device-resident.
    """
    if level not in MODEL_LEVELS:
        raise ValueError(f"unknown model level {level!r}")
    with obs.span("repro.plan.model"):
        if isinstance(phases, _ARENAS):
            return _stack_costs(phases, level, params, backend=backend)
        phases = list(phases)
        stack = as_stack(phases)
        if stack is None:
            return [phase_cost_phase(ph, level=level, params=params)
                    for ph in phases]
        return _stack_costs(stack, level, params, backend=backend)


def model_ladder_many(phases, params: CommParams | None = None,
                      backend: str | None = None
                      ) -> list[dict[str, CostBreakdown]]:
    """Evaluate the full model ladder on a sweep of phases: the arena is
    stacked once and swept once per ladder level (a :class:`PhaseStack` or
    :class:`repro.comm.DeltaStack` passes straight through)."""
    if isinstance(phases, _ARENAS):
        stack = phases
    else:
        phases = list(phases)
        stack = as_stack(phases)
    if stack is None:
        return [{lvl: phase_cost_phase(ph, level=lvl, params=params)
                 for lvl in MODEL_LEVELS} for ph in phases]
    out: list[dict[str, CostBreakdown]] = [{} for _ in range(stack.n_phases)]
    agg_cache: dict = {}
    for lvl in MODEL_LEVELS:
        for row, cb in zip(out, _stack_costs(stack, lvl, params,
                                             backend=backend,
                                             agg_cache=agg_cache)):
            row[lvl] = cb
    return out


def sequence_cost(phases, level: str = "contention",
                  params: CommParams | None = None) -> CostBreakdown:
    """Price a multi-phase *sequence* (e.g. a strategy rewrite's
    gather -> inter -> scatter).  Phases execute back-to-back — each must
    complete before the next posts — so per-phase costs add.  This is what
    lets the strategy layer reuse the cost code unchanged: a rewrite only
    produces more CommPhases, never new cost formulas."""
    parts = phase_cost_many(phases, level=level, params=params)
    return CostBreakdown(
        transport=sum(p.transport for p in parts),
        queue=sum(p.queue for p in parts),
        contention=sum(p.contention for p in parts),
        total=sum(p.total for p in parts))
