"""Node-aware communication strategies: pure phase -> phase-sequence rewrites.

The paper's node-aware model explains *why* aggregating inter-node traffic
helps; its successors (Lockhart et al., Collom et al.) turn the insight into
concrete multi-step strategies.  This module makes those strategies
first-class: a strategy is a **rewrite** that transforms one bound
:class:`~repro.comm.CommPhase` into a *sequence* of CommPhases carrying the
same payload along a different route.  Because each step is itself an
ordinary CommPhase, the existing cost code prices every strategy unchanged —
the model ladder via :func:`repro.core.models.sequence_cost` and the event
simulator via :func:`repro.net.simulator.simulate_sequence` simply sum the
steps.

Strategies (``STRATEGIES``):

``standard``
    Identity: every message travels directly, one phase.
``two_step``
    Node-aware aggregation.  Each node designates a leader (its lowest
    process).  Sequence: **gather** (every process ships its off-node payload
    to its node leader, intra-node), **inter** (one aggregated message per
    (send-node, recv-node) pair, leader to leader), **scatter** (the
    receiving leader forwards each final destination its payload,
    intra-node).  Original intra-node messages ride in a ``local`` phase.
``three_step``
    As ``two_step``, but the aggregated inter-node traffic of every node
    pair is dedup-split into ``k`` equal shares injected by ``k`` distinct
    processes on the sender node (``k`` = processes available on both ends),
    spreading the node's injection load so the max-rate cap ``R_N`` — rather
    than a single process's ``R_b`` — bounds throughput.  The gather/scatter
    phases fan shares across the same ``k`` ranks.

GPU-aware strategies (``GPU_STRATEGIES``, heterogeneous machines only —
Lockhart et al. 2022's comparison):

``host_staged``
    Copy-to-host aggregation: each off-node payload is staged to host memory
    (a ``d2h`` copy phase, one coalesced self-copy per sending process at the
    ``h2d`` rate class), node-aggregated and k-way split like ``three_step``,
    sent over the *host* NIC path (the inter phase carries an explicit
    ``host_staged`` class override), scattered, and copied back device-side
    (the ``h2d`` phase).  Pays two copy phases, rides the full multi-rail
    host NIC bandwidth.
``device_direct``
    Per-device 3-step: each device's traffic is gathered to its device
    leader (intra-device), aggregated per (send-device, recv-device) pair,
    and injected GPU-NIC direct (``device_direct`` class) — every node's
    devices become its injectors.  No copies, but the device-direct network
    rates bound throughput.

On-node share movement inside both GPU strategies is machine-classified
(intra-device / cross-device), a deliberate simplification — the copy phases
carry the staging cost.  ``strategies_for(machine)`` returns the sweep set a
machine supports (the GPU pair requires device endpoints and the staged rate
classes); ``best_strategy``/``best_strategy_many`` default to it.

All rewrites are array passes, with no per-message Python loop.  Pair
sums (the inter phase's node pairs, the staging copies, ``device_direct``)
come from :func:`repro.comm.primitives.sum_by_pairs`, which groups by
direct index where the pair keys are dense and sorts them otherwise.  The
k-way fan-out of the gather and scatter phases is summed per (endpoint,
injector rank) by ``bincount`` over the endpoint (:func:`_fan_sums`): a
message is never expanded into its ``k`` shares, and the sums are
bit-identical to summing the expanded shares.  "Off-node" means the
sender's and receiver's *nodes* differ, which coincides with the machine's
network locality classes on both shipped machines (Blue Waters and TPU
v5e).

Layering: the rewrites are numpy-only and sit below both consumers, like the
rest of :mod:`repro.comm`.  :func:`best_strategy` is the one function that
reaches *up* to the model ladder and the simulator; it imports them lazily
inside the call so the package layering stays acyclic.
"""
from __future__ import annotations

import dataclasses

import numpy as np

from . import obs
from .phase import CommPhase
from .primitives import segmented_arange, sum_by_pairs
from .stack import PhaseStack, as_stack

STRATEGIES = ("standard", "two_step", "three_step")

#: Heterogeneous-machine strategies (Lockhart's host-staged vs GPU-direct).
GPU_STRATEGIES = ("host_staged", "device_direct")

#: Phase roles, in execution order, as they appear in ``StrategyPlan.roles``.
#: ``d2h`` / ``h2d`` are the staging copy phases (coalesced per-process
#: self-copies at the ``h2d`` rate class) of the ``host_staged`` strategy.
ROLES = ("standard", "local", "d2h", "gather", "inter", "scatter", "h2d")

#: Row dtype of :meth:`StrategyPlan.schedule`: one row per rewritten message.
SCHEDULE_DTYPE = np.dtype([("phase", np.int32), ("role", np.int32),
                           ("src", np.int64), ("dst", np.int64),
                           ("size", np.float64)])


def strategies_for(machine) -> tuple[str, ...]:
    """The strategy names worth sweeping on ``machine``: the three node-aware
    CPU strategies everywhere, plus ``GPU_STRATEGIES`` when the machine has
    device endpoints and its rate table carries the staged classes."""
    p = machine.params
    if getattr(machine, "devices_per_node", 0) and all(
            p.has_class(c) for c in ("h2d", "host_staged", "device_direct")):
        return STRATEGIES + GPU_STRATEGIES
    return STRATEGIES


def _require_hetero(machine, name: str) -> None:
    """GPU-aware rewrites need device endpoints and the staged rate classes."""
    if name not in strategies_for(machine):
        raise ValueError(
            f"the {name!r} strategy needs a heterogeneous machine (device "
            f"endpoints plus h2d/host_staged/device_direct rate classes); "
            f"{getattr(machine, 'name', machine)!r} has "
            f"{machine.params.locality_names}")


@dataclasses.dataclass(frozen=True)
class StrategyPlan:
    """A strategy applied to one phase: the rewritten phase sequence.

    ``phases[i]`` plays role ``roles[i]`` (see ``ROLES``).  A ``standard``
    role marks an unrewritten phase (the identity strategy, or a rewrite of
    a phase with no inter-node traffic, where every strategy degenerates to
    the identity).
    """

    strategy: str
    original: CommPhase
    phases: tuple[CommPhase, ...]
    roles: tuple[str, ...]

    @property
    def n_phases(self) -> int:
        return len(self.phases)

    @property
    def total_msgs(self) -> int:
        return sum(ph.n_msgs for ph in self.phases)

    @property
    def inter_node_msgs(self) -> int:
        """Messages that cross a node boundary, summed over the sequence."""
        return sum(int(_remote_mask(ph).sum()) for ph in self.phases)

    def phase_by_role(self, role: str) -> CommPhase | None:
        """The first phase playing ``role`` (see ``ROLES``), or None."""
        for ph, r in zip(self.phases, self.roles):
            if r == role:
                return ph
        return None

    def schedule(self) -> np.ndarray:
        """The plan's executable message schedule, one structured row per
        rewritten message (dtype ``SCHEDULE_DTYPE``): ``phase`` indexes into
        ``phases``, ``role`` into ``ROLES``, and ``src`` / ``dst`` / ``size``
        are the message endpoints and payload bytes.  This is the contract
        the execution layer (:mod:`repro.exec`) lowers from — a lowered
        schedule's per-role (src, dst) pair set must be a subset of these
        rows (see ``repro.exec.plan.pairs_subset_of_plan``)."""
        out = np.empty(self.total_msgs, dtype=SCHEDULE_DTYPE)
        at = 0
        for i, (ph, role) in enumerate(zip(self.phases, self.roles)):
            rows = out[at:at + ph.n_msgs]
            rows["phase"] = i
            rows["role"] = ROLES.index(role)
            rows["src"] = ph.src
            rows["dst"] = ph.dst
            rows["size"] = ph.size
            at += ph.n_msgs
        return out

    def inter_node_pair_bytes(self) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
        """(send_node, recv_node, bytes) actually crossing node boundaries.

        Invariant under every rewrite (payload conservation): aggregation
        changes message *counts* and *sizes*, never which node owes how many
        payload bytes to which node.
        """
        sn, dn, sz = [], [], []
        for ph in self.phases:
            rem = _remote_mask(ph)
            if rem.any():
                sn.append(ph.send_node[rem])
                dn.append(np.asarray(ph.machine.node_of(ph.dst[rem]),
                                     dtype=np.int64))
                sz.append(ph.size[rem])
        if not sn:
            z = np.zeros(0, dtype=np.int64)
            return z, z, np.zeros(0)
        return sum_by_pairs(np.concatenate(sn), np.concatenate(dn),
                            np.concatenate(sz))


def _remote_mask(phase: CommPhase) -> np.ndarray:
    """Messages whose sender and receiver live on different nodes."""
    dst_node = np.asarray(phase.machine.node_of(phase.dst), dtype=np.int64)
    return phase.send_node != dst_node


def _avail(machine, nodes: np.ndarray, n_procs: int) -> np.ndarray:
    """Processes of each node that exist within the phase's process range.

    A phase may span fewer processes than the machine hosts (a coarse AMG
    level on a big partition); shares are only fanned across ranks that are
    actually in ``[0, n_procs)``.  Every node that appears in the phase hosts
    at least its leader, so the result is always >= 1.
    """
    ppn = machine.procs_per_node
    return np.minimum(np.int64(ppn), n_procs - nodes * np.int64(ppn))


def _build(machine, parts, n_procs: int) -> tuple[tuple[CommPhase, ...],
                                                  tuple[str, ...]]:
    phases, roles = [], []
    for part in parts:
        role, src, dst, size = part[:4]
        loc = part[4] if len(part) > 4 else None    # explicit class override
        if len(src):
            phases.append(CommPhase.build(machine, src, dst, size,
                                          n_procs=n_procs, loc=loc))
            roles.append(role)
    return tuple(phases), tuple(roles)


def standard(phase: CommPhase) -> StrategyPlan:
    """Identity strategy: the phase as given, in a one-phase sequence."""
    return StrategyPlan("standard", phase, (phase,), ("standard",))


def two_step(phase: CommPhase) -> StrategyPlan:
    """Node-aware aggregation of one bound phase: gather -> one inter-node
    message per node pair -> scatter."""
    return _aggregated(phase, "two_step", split=False)


def three_step(phase: CommPhase) -> StrategyPlan:
    """Two-step of one bound phase with each node pair's traffic split
    across k injectors."""
    return _aggregated(phase, "three_step", split=True)


def host_staged(phase: CommPhase) -> StrategyPlan:
    """Copy-to-host aggregation of one bound phase (hetero machines only):
    d2h copies -> node-level k-way-split aggregation over the *host* NIC
    path -> h2d copies on the receiving side."""
    _require_hetero(phase.machine, "host_staged")
    return _aggregated(phase, "host_staged", split=True, staged=True)


def _fan_sums(end, share, k) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
    """One side of an aggregated rewrite's fan-out, without expanding
    messages into shares.

    Message ``i`` sends an equal ``share[i]`` to (gather) or from (scatter)
    each injector rank ``r < k[i]`` of ``end[i]``'s node.  Returns
    ``(ends, ranks, sums)``, one row per (endpoint, rank) that some message
    reaches, by endpoint and then rank; ``sums`` adds each row's shares in
    message order, the same terms in the same order as summing the
    expanded shares, so the result is bit-identical to that.  Every
    message reaches the ranks ``r < k.min()``: they share one pass over
    the messages.  Each rank above that takes one masked pass, counted as
    ``rewrite.fan_passes`` (nonzero only where ``k`` varies, as on a
    partially filled node).
    """
    present = np.bincount(end) > 0
    ends = np.flatnonzero(present)
    row = (np.cumsum(present) - 1)[end]          # endpoint -> row
    kmin, kmax = int(k.min()), int(k.max())
    sums = np.empty((ends.size, kmax))
    hit = np.ones((ends.size, kmax), dtype=bool)
    sums[:, :kmin] = np.bincount(row, weights=share,
                                 minlength=ends.size)[:, None]
    for r in range(kmin, kmax):
        on = k > r
        reached = row[on]
        hit[:, r] = np.bincount(reached, minlength=ends.size) > 0
        sums[:, r] = np.bincount(reached, weights=share[on],
                                 minlength=ends.size)
    obs.count("rewrite.fan_passes", kmax - kmin)
    at, rank = np.nonzero(hit)
    return ends[at], rank, sums[at, rank]


def _aggregated(phase: CommPhase, name: str, split: bool,
                staged: bool = False) -> StrategyPlan:
    m, P = phase.machine, phase.n_procs
    ppn = np.int64(m.procs_per_node)
    remote = _remote_mask(phase)
    if not remote.any():            # nothing to aggregate: identity
        return StrategyPlan(name, phase, (phase,), ("standard",))

    parts = [("local", phase.src[~remote], phase.dst[~remote],
              phase.size[~remote])]
    rs, rd, rsz = phase.src[remote], phase.dst[remote], phase.size[remote]
    rsn = phase.send_node[remote]
    rdn = np.asarray(m.node_of(rd), dtype=np.int64)

    inter_loc = None
    if staged:
        # the staging decision, as explicit class overrides: each process
        # coalesces its off-node payload into one host<->device copy, and
        # the aggregated traffic rides the host NIC path
        h2d = m.params.class_index("h2d")
        inter_loc = m.params.class_index("host_staged")
        parts.append(("d2h", *sum_by_pairs(rs, rs, rsz), h2d))

    # shares per message: 1 (leader only) or k = procs available on both ends
    if split:
        k = np.minimum(_avail(m, rsn, P), _avail(m, rdn, P))
    else:
        k = np.ones(rs.size, dtype=np.int64)
    share = rsz / k

    # gather: origin -> the k injector ranks on its own node (equal shares;
    # the share an injector originates itself needs no message).  Rows come
    # out by origin, then injector: sorted by (src, dst).
    g_src, rank, g_size = _fan_sums(rs, share, k)
    g_dst = np.asarray(m.node_of(g_src), dtype=np.int64) * ppn + rank
    keep = g_src != g_dst
    parts.append(("gather", g_src[keep], g_dst[keep], g_size[keep]))

    # inter: aggregate payload per (send node, recv node), then one message
    # per injector rank r: (S, r) -> (D, r)
    Sn, Dn, B = sum_by_pairs(rsn, rdn, rsz)
    if split:
        kp = np.minimum(_avail(m, Sn, P), _avail(m, Dn, P))
    else:
        kp = np.ones(Sn.size, dtype=np.int64)
    prep = np.repeat(np.arange(Sn.size), kp)
    prank = segmented_arange(kp)
    parts.append(("inter", Sn[prep] * ppn + prank, Dn[prep] * ppn + prank,
                  B[prep] / kp[prep], inter_loc))

    # scatter: the k receiving ranks on the destination node forward each
    # final destination its shares (a rank's own share needs no message),
    # sorted by (src, dst) like every other aggregated phase
    s_dst, rank, s_size = _fan_sums(rd, share, k)
    s_src = np.asarray(m.node_of(s_dst), dtype=np.int64) * ppn + rank
    keep = np.flatnonzero(s_src != s_dst)
    keep = keep[np.argsort(s_src[keep] * P + s_dst[keep])]
    parts.append(("scatter", s_src[keep], s_dst[keep], s_size[keep]))

    if staged:
        parts.append(("h2d", *sum_by_pairs(rd, rd, rsz), h2d))

    phases, roles = _build(m, parts, P)
    return StrategyPlan(name, phase, phases, roles)


def device_direct(phase: CommPhase) -> StrategyPlan:
    """Per-device 3-step of one bound phase (hetero machines only): gather
    to device leaders -> one GPU-NIC-direct message per (send-device,
    recv-device) pair -> scatter.  Every node's devices are its injectors;
    no host staging, so no copy phases."""
    m, P = phase.machine, phase.n_procs
    _require_hetero(m, "device_direct")
    ppd = np.int64(m.procs_per_device)
    dd = m.params.class_index("device_direct")
    remote = _remote_mask(phase)
    if not remote.any():            # nothing to aggregate: identity
        return StrategyPlan("device_direct", phase, (phase,), ("standard",))

    parts = [("local", phase.src[~remote], phase.dst[~remote],
              phase.size[~remote])]
    rs, rd, rsz = phase.src[remote], phase.dst[remote], phase.size[remote]
    rsd = rs // ppd                 # global device of origin / destination
    rdd = rd // ppd

    # gather: origin -> its device leader (the device's lowest rank; the
    # leader's own payload needs no message).  Intra-device traffic.
    g_src, g_dst = rs, rsd * ppd
    keep = g_src != g_dst
    parts.append(("gather", *sum_by_pairs(g_src[keep], g_dst[keep],
                                          rsz[keep])))

    # inter: one aggregated leader-to-leader message per (send-device,
    # recv-device) pair, explicitly on the device-direct network path
    # (remote pairs always cross nodes, so the override is consistent with
    # pair geometry even when the machine's default path is host_staged)
    Sd, Dd, B = sum_by_pairs(rsd, rdd, rsz)
    parts.append(("inter", Sd * ppd, Dd * ppd, B, dd))

    # scatter: the receiving device leader forwards each final destination
    # its payload (a leader's own payload needs no message)
    s_src, s_dst = rdd * ppd, rd
    keep = s_src != s_dst
    parts.append(("scatter", *sum_by_pairs(s_src[keep], s_dst[keep],
                                           rsz[keep])))

    phases, roles = _build(m, parts, P)
    return StrategyPlan("device_direct", phase, phases, roles)


_REWRITES = {"standard": standard, "two_step": two_step,
             "three_step": three_step,
             "host_staged": host_staged, "device_direct": device_direct}


def rewrite(phase: CommPhase, strategy: str) -> StrategyPlan:
    """Apply one named ``strategy`` rewrite to a bound ``phase``."""
    try:
        fn = _REWRITES[strategy]
    except KeyError:
        raise ValueError(f"unknown strategy {strategy!r}; expected one of "
                         f"{STRATEGIES + GPU_STRATEGIES}") from None
    return fn(phase)


# -- payload-conservation accessors -----------------------------------------
#
# Both are flow identities over the rewritten message arrays alone (no use of
# the original payload), so tests can compare them against the original phase
# to certify a rewrite delivers exactly what was sent.

def injected_payload(plan: StrategyPlan) -> np.ndarray:
    """Per-process payload bytes *originated*, reconstructed from the plan.

    An injector's inter-phase sends equal its gather-phase receipts plus the
    shares it originated itself, so ``local + gather + inter - gather_recv``
    telescopes back to the original per-source payload.
    """
    P = plan.original.n_procs
    out = np.zeros(P)
    for ph, role in zip(plan.phases, plan.roles):
        if role in ("standard", "local", "gather", "inter"):
            out += np.bincount(ph.src, weights=ph.size, minlength=P)
        if role == "gather":
            out -= np.bincount(ph.dst, weights=ph.size, minlength=P)
    return out


def delivered_payload(plan: StrategyPlan) -> np.ndarray:
    """Per-process payload bytes *finally delivered* by ``plan`` (mirror
    identity: ``local + scatter + inter - scatter_sent``)."""
    P = plan.original.n_procs
    out = np.zeros(P)
    for ph, role in zip(plan.phases, plan.roles):
        if role in ("standard", "local", "scatter", "inter"):
            out += np.bincount(ph.dst, weights=ph.size, minlength=P)
        if role == "scatter":
            out -= np.bincount(ph.src, weights=ph.size, minlength=P)
    return out


# -- the strategy sweep ------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class StrategyVerdict:
    """Every strategy priced by the model ladder and judged by the simulator.

    ``model[s]`` is the model-ladder total (at the requested level) summed
    over strategy ``s``'s phase sequence; ``sim[s]`` is the simulator's.  The
    *predicted* winner comes from the model alone — the simulator's verdict
    is the ground truth the prediction is scored against, across the same
    inferential gap the paper has between model and machine.

    ``degraded`` marks a verdict priced under the degradation policy: some
    backend call failed and fell back to the numpy reference during this
    sweep (the triggering events are in
    :func:`repro.comm.health.get_health`'s ledger).  The numbers are still
    correct — the fallback is the bit-identity reference — but the device
    path did not serve them.
    """

    plans: dict[str, StrategyPlan]
    model: dict[str, float]
    sim: dict[str, float]
    model_winner: str
    sim_winner: str
    degraded: bool = False

    @property
    def agree(self) -> bool:
        return self.model_winner == self.sim_winner


def best_strategy(pattern, machine=None, *, strategies=None,
                  level: str = "contention", arrival: str = "random",
                  seed: int = 0, params=None, backend=None,
                  validate: bool = False) -> StrategyVerdict:
    """Sweep strategies over one phase; return the model's pick and the
    simulator's verdict.

    ``pattern`` is a :class:`repro.sparse.CommPattern` (bound to ``machine``)
    or an already-bound :class:`CommPhase`.  ``strategies`` defaults to
    :func:`strategies_for` the bound machine — the three node-aware
    strategies, plus the GPU-aware pair on heterogeneous machines.
    ``arrival='random'`` drives the simulator with the paper's Sec.-5
    irregular regime (random envelope arrival, from a generator seeded with
    ``seed`` per candidate); ``'posted'`` uses best-case in-order arrival.
    The model prices phases at ladder ``level``; ``params`` substitutes a
    fitted parameter table for the machine's ground truth on the model side
    only.  ``backend`` routes the stacked passes through a device backend;
    ``validate=True`` runs the typed validation layer over the pattern
    first (see :func:`best_strategy_many` for both).

    The whole candidate set — every strategy's phase sequence — is priced in
    one stacked model pass and one stacked simulator pass: this is the
    one-pattern case of :func:`best_strategy_many`.
    """
    return best_strategy_many([pattern], machine, strategies=strategies,
                              level=level, arrival=arrival, seed=seed,
                              params=params, backend=backend,
                              validate=validate)[0]


def _machine_groups(phases) -> list[list[int]]:
    """Partition ``phases`` indices by machine identity, first-seen order.

    Each group's phases share one machine, so each can stack into its own
    arena; the groups together cover every index exactly once.
    """
    groups: dict[int, list[int]] = {}
    for i, ph in enumerate(phases):
        groups.setdefault(id(ph.machine), []).append(i)
    return list(groups.values())


def best_strategy_many(patterns, machine=None, *, strategies=None,
                       level: str = "contention", arrival: str = "random",
                       seed: int = 0, params=None, backend=None,
                       validate: bool = False) -> list[StrategyVerdict]:
    """:func:`best_strategy` for a whole sweep of ``patterns`` in ONE arena
    (same ``machine`` / ``strategies`` / ``level`` / ``arrival`` / ``seed``
    / ``params`` arguments).

    Every (pattern, strategy) candidate's phase sequence is rewritten and
    concatenated into a single :class:`~repro.comm.PhaseStack`, then the
    model ladder and the simulator each price the entire candidate set in
    one segmented pass — the strategy-sweep analogue of
    :func:`repro.core.models.phase_cost_many`.  Already-bound phases from
    *different* machines (a cross-machine scenario sweep, e.g.
    :func:`repro.workloads.sweep`) are also one arena call: the candidate
    set is partitioned by machine and stacked per machine group.  Results
    are element-wise identical to ``[best_strategy(p, ...) for p in
    patterns]`` (each candidate keeps its own seeded arrival stream); only
    the number of arena walks changes.

    Hardening (DESIGN.md §12): ``validate=True`` runs the typed validation
    layer over every pattern before anything is rewritten
    (:func:`repro.comm.guard.validate_messages` — precise
    :class:`~repro.comm.guard.PatternError` subclasses).  ``backend``
    (``None`` is numpy; an unknown name raises ``ValueError`` before any
    work) routes the stacked passes through a device backend; every device
    site already degrades to numpy on failure, and should the pricing
    passes still raise on a device backend, the sweep is retried once on
    ``backend='numpy'``.  Verdicts priced under any fallback carry
    ``degraded=True`` with the events recorded in
    :func:`repro.comm.health.get_health`.
    """
    if arrival not in ("random", "posted"):
        raise ValueError(f"unknown arrival regime {arrival!r}; "
                         "expected 'random' or 'posted'")
    PhaseStack._backend(backend)            # eager: a bad name never retries
    patterns = list(patterns)
    stats = (_sweep_stats(patterns, machine, strategies) if obs.enabled()
             else {})
    with obs.span("repro.plan.sweep", **stats):
        return _sweep(patterns, machine, strategies, level, arrival, seed,
                      params, backend, validate)


def _sweep_stats(patterns, machine, strategies) -> dict:
    """The ``repro.plan.sweep`` span's stats: patterns, (pattern,
    strategy) candidates and source messages of one sweep."""
    candidates = 0
    for pat in patterns:
        m = machine if machine is not None else getattr(pat, "machine", None)
        if strategies is not None:
            candidates += len(strategies)
        elif m is not None:
            candidates += len(strategies_for(m))
    return {"patterns": len(patterns), "candidates": candidates,
            "messages": sum(len(pat.src) for pat in patterns)}


def _sweep(patterns, machine, strategies, level, arrival, seed, params,
           backend, validate) -> list[StrategyVerdict]:
    """The body of :func:`best_strategy_many`, under its sweep span."""
    from repro.core.models import phase_cost_many
    from repro.net.simulator import simulate_many
    from .health import get_health

    phases = []
    for pat in patterns:
        if hasattr(pat, "bind"):
            if machine is None:
                raise ValueError("a CommPattern needs a machine to bind to")
            phases.append(pat.bind(machine, validate=validate))
        elif machine is not None and machine is not pat.machine:
            with obs.span("repro.plan.bind"):
                phases.append(CommPhase.build(machine, pat.src, pat.dst,
                                              pat.size, n_procs=pat.n_procs,
                                              validate=validate))
        else:
            if validate:
                from .guard import validate_phase
                validate_phase(pat)
            phases.append(pat)

    # rewrites first, then each candidate's arrivals from its own
    # generator seeded with ``seed``: the same draws as one interleaved loop
    plan_rows, spans, all_phases = [], [], []
    with obs.span("repro.plan.rewrite"):
        for phase in phases:
            plans, row_spans = {}, {}
            names = (strategies if strategies is not None
                     else strategies_for(phase.machine))
            for name in names:
                plan = rewrite(phase, name)
                plans[name] = plan
                row_spans[name] = slice(len(all_phases),
                                        len(all_phases) + plan.n_phases)
                all_phases.extend(plan.phases)
            plan_rows.append(plans)
            spans.append(row_spans)
    all_arrivals = []
    with obs.span("repro.plan.arrivals"):
        for plans in plan_rows:
            for plan in plans.values():
                if arrival == "random":
                    rng = np.random.default_rng(seed)
                    all_arrivals.extend(ph.random_arrival_flat(rng)
                                        for ph in plan.phases)
                else:
                    all_arrivals.extend([None] * plan.n_phases)

    health = get_health()
    events_before = health.n_events

    def _price(be):
        # one shared arena for both passes; a mixed-machine candidate set
        # (bound phases from different machines — a cross-machine scenario
        # sweep) is partitioned by machine and runs one arena per machine
        # group, results scattered back in place (bit-identical to one arena
        # by the PhaseStack contract: segmented passes never mix rows across
        # phases)
        stack = as_stack(all_phases)
        if stack is not None:
            costs = phase_cost_many(stack, level=level, params=params,
                                    backend=be)
            sims = simulate_many(stack, arrival_orders=all_arrivals,
                                 backend=be)
            return costs, sims
        costs = [None] * len(all_phases)
        sims = [None] * len(all_phases)
        for idx in _machine_groups(all_phases):
            sub = [all_phases[i] for i in idx]
            sub_stack = as_stack(sub)
            if sub_stack is None:       # single phase / degenerate group
                sub_stack = sub
            sub_costs = phase_cost_many(sub_stack, level=level,
                                        params=params, backend=be)
            sub_sims = simulate_many(
                sub_stack, arrival_orders=[all_arrivals[i] for i in idx],
                backend=be)
            for i, c, r in zip(idx, sub_costs, sub_sims):
                costs[i] = c
                sims[i] = r
        return costs, sims

    try:
        costs, sims = _price(backend)
    except Exception as e:  # noqa: BLE001 - serve-layer degradation
        if backend in (None, "numpy"):
            raise       # the reference path itself failed: a real error
        # a device backend failed outside its own guards: price on numpy; a
        # genuine input error re-raises from the retry unchanged
        health.record_failure(str(backend), "strategies.best_strategy_many",
                              e)
        costs, sims = _price("numpy")

    with obs.span("repro.plan.verdict"):
        degraded = health.n_events > events_before
        out = []
        for plans, row_spans in zip(plan_rows, spans):
            model = {name: sum(c.total for c in costs[row_spans[name]])
                     for name in plans}
            sim = {name: sum(r.time for r in sims[row_spans[name]])
                   for name in plans}
            out.append(StrategyVerdict(
                plans=plans, model=model, sim=sim,
                model_winner=min(model, key=model.get),
                sim_winner=min(sim, key=sim.get), degraded=degraded))
    return out
