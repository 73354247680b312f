"""DeltaStack: incremental re-pricing of a mutated sweep arena.

PR 3's :class:`~repro.comm.PhaseStack` made one-shot sweeps fast; this module
makes *search* fast.  A local-search move — shift a partition boundary,
re-aggregate one node — changes a few dozen messages, yet re-pricing the
candidate through ``PhaseStack.build`` pays the full O(total messages) cost
again: machine classification, ``np.unique`` active-sender counting, torus
routing, every segmented reduction.  ``DeltaStack`` wraps the same arena as
a sequence of per-phase incremental states and supports

    ``delta.apply(removed_idx, added) -> DeltaStack``

where the cost of re-deriving every ladder-level and simulator aggregate is
proportional to the *changed phases*, not the whole sweep — and, inside a
changed phase, the expensive derived quantities are delta-updated rather
than recomputed:

* **active-sender / node tables** — integer per-(phase, sender) network-send
  counts and per-(phase, node) active-sender counts are point-updated
  (``np.add.at``); ``active_ppn`` is then a table lookup, with re-pricing
  limited to network messages of nodes whose active count actually changed
  (plus the added messages) — no ``np.unique`` sort ever runs again;
* **per-(phase, process) transport sums** — the node-aware per-message
  transport times survive the move except at the re-priced subset; the dense
  send-side rows are re-binned per dirty phase in canonical order (survivors
  first, additions appended), which keeps them bit-identical to a fresh
  packed-key ``bincount``.  The postal / flat-max-rate rungs are pure
  elementwise functions of the phase arrays and are priced lazily, on first
  query per generation;
* **receive counts / queue terms** — integer point updates into the
  per-receiver count rows, with the per-phase worst receiver maintained by a
  point-updatable max tree (:class:`_MaxTree`) instead of a row rebuild;
* **routing / link contention** — lazy until the simulator first asks, then
  only *added* messages are routed: the surviving rows of the stored
  ``(message, link)`` expansion are filtered and re-merged in the
  dimension-major order ``route_link_ids`` emits, so the per-(link, source)
  histogram replays the fresh aggregation bit for bit.  A model-guided
  search that never simulates never routes at all (the ladder's contention
  term is the cube-partition estimate, a function of net bytes).

Bit-identity contract: every aggregate a ``DeltaStack`` serves equals a fresh
``PhaseStack.build`` over the mutated phases *bit for bit* (numpy backend).
Mutated phases are canonical: surviving messages keep their order, additions
append at the end — exactly the phase a caller would rebuild.  Floating-point
sums that depend on accumulation order (send-side ``bincount`` rows, the
pairwise-summed per-phase net bytes) are *replayed* over the dirty phase's
arrays rather than patched, because patching a float sum cannot reproduce
the fresh accumulation order; everything integer (receive counts, sender
tables, queue steps) is patched point-wise.  ``verify=True`` re-checks the
contract against a fresh build after every ``apply`` — use it in tests and
when debugging a new move generator, never in hot search loops.

Layering: numpy-only, below both consumers like the rest of
:mod:`repro.comm`.  :func:`repro.core.models.phase_cost_many` /
:func:`model_ladder_many` and :func:`repro.net.simulator.simulate_many`
accept a ``DeltaStack`` anywhere they accept a ``PhaseStack``; the
model-guided partition optimizer (:mod:`repro.sparse.optimize`) is the
intended driver.  Fitted-params overrides and the JAX/Pallas backends fall
back to a fresh arena (built once per generation and cached) — the delta
fast path serves the machine's own tables, which is what a search loop
prices.
"""
from __future__ import annotations

import hashlib

import numpy as np

from .phase import CommPhase
from .primitives import transport_times
from .stack import PhaseStack, StackSimArrays

__all__ = ["DeltaStack", "ARENA_TYPES", "phase_fingerprint",
           "pattern_fingerprint", "message_delta"]

def phase_fingerprint(src, dst, size, n_procs) -> str:
    """Content-hash of one phase's raw message arrays, as a hex string.

    SHA-256 over a canonical byte stream: a version tag, ``n_procs`` and the
    message count as int64, then the ``src`` / ``dst`` endpoint arrays as
    int64 and the ``size`` array as float64, **in message order**.  The hash
    is deliberately order-sensitive: simulator verdicts depend on message
    order (per-candidate seeded arrival streams), so two phases that differ
    only by a permutation must *not* share a cache entry.  Used by the
    strategy service's arena cache to key priced arenas.
    """
    src = np.ascontiguousarray(src, dtype=np.int64)
    dst = np.ascontiguousarray(dst, dtype=np.int64)
    size = np.ascontiguousarray(size, dtype=np.float64)
    h = hashlib.sha256()
    h.update(b"repro.phase.v1")
    h.update(np.asarray([int(n_procs), src.size], dtype=np.int64).tobytes())
    h.update(src.tobytes())
    h.update(dst.tobytes())
    h.update(size.tobytes())
    return h.hexdigest()


def pattern_fingerprint(pattern) -> str:
    """Content-hash of a :class:`repro.sparse.CommPattern`, as a hex string.

    Delegates to :func:`phase_fingerprint` over ``pattern``'s raw
    ``src`` / ``dst`` / ``size`` arrays and ``n_procs`` — anything with
    those four attributes (a ``CommPattern``, a bound ``CommPhase``) hashes
    identically, so a cache keyed on the unbound pattern hits for its bound
    phase too.
    """
    return phase_fingerprint(pattern.src, pattern.dst, pattern.size,
                             pattern.n_procs)


def message_delta(old, new):
    """The multiset message diff turning pattern ``old`` into pattern ``new``.

    Both ``old`` and ``new`` expose raw ``src`` / ``dst`` / ``size`` arrays
    (``CommPattern`` or bound ``CommPhase``).  Returns
    ``(removed_idx, (src, dst, size))`` suitable for
    :meth:`DeltaStack.apply` on a single-phase arena built from ``old``:
    ``removed_idx`` are message indices into ``old``'s order, the added
    arrays are the messages of ``new`` not covered by ``old``.

    Messages match as exact ``(src, dst, size)`` triples, multiset-style:
    when a triple appears ``a`` times in ``old`` and ``b`` times in ``new``,
    ``min(a, b)`` copies survive.  Removals take the *last* duplicate
    occurrences so the earliest survivors keep their slots, matching the
    canonical mutated order ``DeltaStack.apply`` produces (survivors in
    place, additions appended).  Note the resulting order is that canonical
    order, not ``new``'s own order — fingerprint the applied arena's phase,
    not ``new``, when caching the result.
    """
    os_ = np.asarray(old.src, dtype=np.int64).ravel()
    od = np.asarray(old.dst, dtype=np.int64).ravel()
    oz = np.asarray(old.size, dtype=np.float64).ravel()
    ns = np.asarray(new.src, dtype=np.int64).ravel()
    nd = np.asarray(new.dst, dtype=np.int64).ravel()
    nz = np.asarray(new.size, dtype=np.float64).ravel()
    n_old, n_new = os_.size, ns.size
    rec = np.empty(n_old + n_new, dtype=[("s", np.int64), ("d", np.int64),
                                         ("z", np.float64)])
    rec["s"] = np.concatenate([os_, ns])
    rec["d"] = np.concatenate([od, nd])
    rec["z"] = np.concatenate([oz, nz])
    _, inv = np.unique(rec, return_inverse=True)
    inv = inv.ravel()                      # numpy 2.x keeps input shape
    inv_old, inv_new = inv[:n_old], inv[n_old:]
    n_groups = int(inv.max(initial=-1)) + 1
    c_old = np.bincount(inv_old, minlength=n_groups)
    c_new = np.bincount(inv_new, minlength=n_groups)
    keep = np.minimum(c_old, c_new)

    def _ranks(invs, counts):
        # within-group occurrence rank, stable in original message order
        order = np.argsort(invs, kind="stable")
        starts = np.r_[0, np.cumsum(counts)[:-1]]
        r = np.empty(invs.size, dtype=np.int64)
        r[order] = np.arange(invs.size) - starts[invs[order]]
        return r

    removed = np.nonzero(_ranks(inv_old, c_old) >= keep[inv_old])[0]
    add = _ranks(inv_new, c_new) >= keep[inv_new]
    return removed, (ns[add], nd[add], nz[add])


#: The (node_aware, use_maxrate) flag pairs the model ladder prices.  The
#: ladder's five levels collapse onto these three transport passes (postal /
#: max-rate / node-aware; queue and contention reuse the node-aware pass).
_POSTAL = (False, False)
_MAXRATE = (False, True)
_NODE_AWARE = (True, True)
_FLAGS = (_POSTAL, _MAXRATE, _NODE_AWARE)


class _MaxTree:
    """Point-updatable maximum over a fixed slot span.

    A complete binary tree in one flat array (the segment-tree sibling of
    the Fenwick trees in :mod:`repro.comm.primitives`): ``update`` rewrites
    one leaf and climbs to the root, so the per-phase worst receive count
    survives removals — which a plain running max cannot — in O(log slots)
    instead of an O(slots) row rebuild.
    """

    __slots__ = ("n", "tree")

    def __init__(self, values: np.ndarray):
        values = np.asarray(values, dtype=np.int64)
        n = 1
        while n < values.size:
            n *= 2
        self.n = n
        t = np.zeros(2 * n, dtype=np.int64)
        t[n:n + values.size] = values
        size = n
        while size > 1:
            size //= 2
            lvl = t[2 * size:4 * size]
            t[size:2 * size] = np.maximum(lvl[0::2], lvl[1::2])
        self.tree = t

    def update(self, i: int, value: int) -> None:
        i += self.n
        t = self.tree
        t[i] = value
        i //= 2
        while i:
            t[i] = max(t[2 * i], t[2 * i + 1])
            i //= 2

    def update_many(self, idx: np.ndarray, values: np.ndarray) -> None:
        """Batch point updates: rewrite the leaves, then climb all the
        affected chains level by level (one vectorized gather-max per level,
        shared ancestors deduplicated)."""
        t = self.tree
        i = np.asarray(idx, dtype=np.int64) + self.n
        t[i] = values
        i = np.unique(i // 2)
        i = i[i > 0]
        while i.size:
            t[i] = np.maximum(t[2 * i], t[2 * i + 1])
            i = np.unique(i // 2)
            i = i[i > 0]

    def max(self) -> int:
        return int(self.tree[1])

    def copy(self) -> "_MaxTree":
        new = _MaxTree.__new__(_MaxTree)
        new.n = self.n
        new.tree = self.tree.copy()
        return new


class _PhaseState:
    """One phase's incrementally-maintained arrays and cached aggregates.

    Eager members are exactly what a model-guided search loop queries every
    move (node-aware transport, receive counts, byte totals) plus the integer
    tables the increments ride on.  Everything only the simulator or the
    lower ladder rungs need — the routing expansion, link contention, the
    postal / flat-max-rate rows — is lazy: priced on first query for a
    generation and, for the routing expansion, maintained incrementally from
    then on.  A search that never touches the simulator never routes.
    """

    __slots__ = ("phase", "span", "t_na", "row_na", "recv", "recv_tree",
                 "net_bytes", "total_bytes", "net_sends", "node_active",
                 "proc_nodes", "_exp", "_max_link", "_flag_rows")

    phase: CommPhase          # current bound phase (canonical message order)
    span: int                 # row length: covers n_procs and every src/dst
    t_na: np.ndarray          # node-aware per-message transport times
    row_na: np.ndarray        # node-aware send-side sums per process [span]
    recv: np.ndarray          # per-receiver message counts [span], int64
    recv_tree: _MaxTree       # point-updatable max over ``recv``
    net_bytes: float          # network-class bytes (pairwise .sum() replay)
    total_bytes: float        # all bytes (for node_aware=False net bytes)
    net_sends: np.ndarray     # per-sender count of network messages [span]
    node_active: np.ndarray   # per-node count of active senders
    proc_nodes: np.ndarray    # node of each process [span]
    # lazy: (exp_msg, exp_link) routing expansion | hottest contended bytes |
    # dense rows for the postal / flat-max-rate flag pairs
    _exp: tuple | None
    _max_link: float | None
    _flag_rows: dict

    def row(self, flags) -> np.ndarray:
        """Dense send-side transport sums for one ladder flag pair.

        The node-aware pair rides the incremental path (it depends on the
        point-updated active-sender tables); the postal and flat max-rate
        pairs are pure elementwise functions of the phase arrays, so they
        are priced fresh on first query per generation and cached — same
        bits as a full build, no ``np.unique`` involved either way.
        """
        if flags == _NODE_AWARE:
            return self.row_na
        row = self._flag_rows.get(flags)
        if row is None:
            ph = self.phase
            t = _price(ph.machine.params,
                       (ph.size, ph.loc, ph.proto, ph.is_net, ph.active_ppn),
                       flags)
            row = np.bincount(ph.src, weights=t, minlength=self.span)
            self._flag_rows[flags] = row
        return row

    def exp(self) -> tuple:
        """The (message id, link id) routing expansion, dimension-major.

        Routed fresh on first demand when no ancestor ever materialized it;
        once it exists, :func:`_mutate_state` maintains it incrementally
        (survivors filtered in place, only additions routed).
        """
        if self._exp is None:
            ph = self.phase
            sel = ph.is_net & (ph.torus_src != ph.torus_dst)
            if sel.any():
                sel_idx = np.nonzero(sel)[0]
                midx, link = ph.machine.torus.route_link_ids(
                    ph.torus_src[sel], ph.torus_dst[sel])
                self._exp = (sel_idx[midx], link)
            else:
                z = np.zeros(0, dtype=np.int64)
                self._exp = (z, z.copy())
        return self._exp

    def link_contention(self) -> float:
        """Hottest contended-link bytes (lazy; simulator-side only)."""
        if self._max_link is None:
            ph = self.phase
            exp_msg, exp_link = self.exp()
            self._max_link = _exp_contention(ph.machine.torus, ph.size,
                                             ph.torus_src, exp_msg, exp_link)
        return self._max_link


def _exp_contention(torus, size, torus_src, exp_msg, exp_link) -> float:
    """Hottest contended-link bytes from a stored routing expansion.

    Replays :meth:`CommPhase.link_contention`'s aggregation over the
    ``(message, link)`` rows — provided the rows are in the dimension-major
    order ``route_link_ids`` emits, the per-(link, source) ``bincount``
    accumulates in the identical order and the result is bit-equal.
    """
    if exp_link.size == 0:
        return 0.0
    tsrc = torus_src[exp_msg]
    span = np.int64(max(torus.size, int(tsrc.max()) + 1))
    key = exp_link * span + tsrc
    uk, inv = np.unique(key, return_inverse=True)
    per_src = np.bincount(inv, weights=size[exp_msg])
    pair_link = uk // span
    starts = np.nonzero(np.r_[True, pair_link[1:] != pair_link[:-1]])[0]
    totals = np.add.reduceat(per_src, starts)
    largest = np.maximum.reduceat(per_src, starts)
    return float((totals - largest).max(initial=0.0))


def _price(params, phase_arrays, flags, idx=None):
    """Transport times for one flag pair, on the whole phase or a subset.

    ``phase_arrays`` is ``(size, loc, proto, is_net, active_ppn)``; ``idx``
    restricts the evaluation to the re-priced subset.  Elementwise and
    deterministic, so a subset evaluation equals the same positions of a
    full fresh pass.
    """
    size, loc, proto, is_net, ppn = phase_arrays
    if idx is not None:
        size, loc, proto = size[idx], loc[idx], proto[idx]
        is_net, ppn = is_net[idx], ppn[idx]
    node_aware, use_maxrate = flags
    if node_aware:
        return transport_times(size, params.alpha[loc, proto],
                               params.Rb[loc, proto],
                               params.RN[loc, proto], ppn, is_net,
                               rails=params.n_rails)
    nl = params.network_locality
    alpha = params.alpha[nl][proto]
    Rb = params.Rb[nl][proto]
    if not use_maxrate:
        return transport_times(size, alpha, Rb, None, 1.0, False,
                               use_maxrate=False)
    # the flat max-rate level treats every message as network-class but keeps
    # the machine-classified active-sender counts (mirrors cost_arrays)
    return transport_times(size, alpha, Rb, params.RN[nl][proto], ppn, True,
                           rails=params.n_rails)


def _build_state(ph: CommPhase) -> _PhaseState:
    """Full (non-incremental) state for one bound phase — the generation-0
    cost, paid once per phase like ``PhaseStack.build``."""
    m = ph.machine
    p = m.params
    if getattr(ph, "loc_overridden", False):
        raise ValueError(
            "DeltaStack needs machine-classified phases: a phase built with "
            "an explicit loc override (a staged strategy step) cannot be "
            "mutated consistently — apply() would classify additions with "
            "the machine's locality()")
    span = int(max(ph.n_procs, ph.src.max(initial=-1) + 1,
                   ph.dst.max(initial=-1) + 1, 1))
    st = _PhaseState.__new__(_PhaseState)
    st.phase = ph
    st.span = span
    st.proc_nodes = np.asarray(m.node_of(np.arange(span)), dtype=np.int64)
    st.net_sends = np.bincount(ph.src[ph.is_net], minlength=span)
    n_nodes = int(st.proc_nodes.max(initial=-1)) + 1
    st.node_active = np.bincount(st.proc_nodes[st.net_sends > 0],
                                 minlength=n_nodes)
    arrays = (ph.size, ph.loc, ph.proto, ph.is_net, ph.active_ppn)
    st.t_na = _price(p, arrays, _NODE_AWARE)
    st.row_na = np.bincount(ph.src, weights=st.t_na, minlength=span)
    st.recv = np.bincount(ph.dst, minlength=span)
    st.recv_tree = _MaxTree(st.recv)
    st.net_bytes = float(ph.size[ph.is_net].sum())
    st.total_bytes = float(ph.size.sum())
    st._exp = None
    st._max_link = None
    st._flag_rows = {}
    return st


def _mutate_state(st: _PhaseState, rm_local: np.ndarray,
                  add: tuple | None) -> _PhaseState:
    """Apply one phase's delta: drop ``rm_local``, append ``add`` messages.

    The canonical mutated order — survivors in place, additions at the end —
    is what every replayed reduction runs over, so each cached aggregate
    equals a fresh build of the mutated phase.
    """
    ph = st.phase
    m = ph.machine
    p = m.params
    P = ph.n_procs
    n_old = ph.n_msgs

    if add is not None:
        # typed validation (PatternError is a ValueError, so existing
        # callers catching ValueError keep working): rejects length
        # mismatches, NaN/negative sizes and endpoints outside the phase's
        # fixed process count before any cached aggregate is touched
        from .guard import validate_messages
        validate_messages(np.asarray(add[0]).ravel(),
                          np.asarray(add[1]).ravel(),
                          np.asarray(add[2]).ravel(), n_procs=P,
                          where="DeltaStack.apply(added)")
        src_a = np.asarray(add[0], dtype=np.int64).ravel()
        dst_a = np.asarray(add[1], dtype=np.int64).ravel()
        size_a = np.asarray(add[2], dtype=np.float64).ravel()
    else:
        src_a = dst_a = np.zeros(0, dtype=np.int64)
        size_a = np.zeros(0)
    na = src_a.size

    keep = np.ones(n_old, dtype=bool)
    keep[rm_local] = False
    nkeep = n_old - rm_local.size

    # machine-derived fields: computed for the additions only
    loc_a = np.asarray(m.locality(src_a, dst_a), dtype=np.int64)
    proto_a = p.protocol_of(size_a)
    is_net_a = loc_a >= p.network_locality
    send_node_a = np.asarray(m.node_of(src_a), dtype=np.int64)

    cat = lambda old, new: np.concatenate([old[keep], new])
    src = cat(ph.src, src_a)
    dst = cat(ph.dst, dst_a)
    size = cat(ph.size, size_a)
    loc = cat(ph.loc, loc_a)
    proto = cat(ph.proto, proto_a)
    is_net = cat(ph.is_net, is_net_a)
    send_node = cat(ph.send_node, send_node_a)
    torus_src = cat(ph.torus_src,
                    np.asarray(m.torus_node_of(src_a), dtype=np.int64))
    torus_dst = cat(ph.torus_dst,
                    np.asarray(m.torus_node_of(dst_a), dtype=np.int64))

    out = _PhaseState.__new__(_PhaseState)
    out.span = st.span
    out.proc_nodes = st.proc_nodes

    # -- active-sender tables: integer point updates --------------------------
    rm_net_src = ph.src[rm_local][ph.is_net[rm_local]]
    net_sends = st.net_sends.copy()
    np.subtract.at(net_sends, rm_net_src, 1)
    np.add.at(net_sends, src_a[is_net_a], 1)
    touched = np.unique(np.concatenate([rm_net_src, src_a[is_net_a]]))
    was = st.net_sends[touched] > 0
    now = net_sends[touched] > 0
    node_active = st.node_active
    if (was != now).any():
        node_active = node_active.copy()
        np.add.at(node_active, st.proc_nodes[touched[now & ~was]], 1)
        np.subtract.at(node_active, st.proc_nodes[touched[was & ~now]], 1)
    changed_nodes = np.nonzero(node_active != st.node_active)[0]
    out.net_sends = net_sends
    out.node_active = node_active

    # -- active_ppn: lookup for additions + nodes whose count changed ---------
    active_ppn = np.concatenate([ph.active_ppn[keep], np.zeros(na)])
    active_ppn[nkeep:] = np.where(is_net_a, node_active[send_node_a], 1.0)
    if changed_nodes.size:
        nc = np.zeros(node_active.size, dtype=bool)
        nc[changed_nodes] = True
        aff = np.nonzero(is_net[:nkeep] & nc[send_node[:nkeep]])[0]
        active_ppn[aff] = node_active[send_node[aff]]
    else:
        aff = np.zeros(0, dtype=np.int64)

    out.phase = CommPhase(
        machine=m, src=src, dst=dst, size=size, n_procs=P, loc=loc,
        proto=proto, is_net=is_net, send_node=send_node,
        torus_src=torus_src, torus_dst=torus_dst, active_ppn=active_ppn)

    # -- node-aware transport times: re-price only what a fresh build would
    #    price differently (additions + ppn-affected network messages) --------
    arrays = (size, loc, proto, is_net, active_ppn)
    ppn_idx = np.concatenate([aff, np.arange(nkeep, nkeep + na)])
    t_na = np.concatenate([st.t_na[keep], np.zeros(na)])
    if ppn_idx.size:
        t_na[ppn_idx] = _price(p, arrays, _NODE_AWARE, ppn_idx)
    out.t_na = t_na
    out.row_na = np.bincount(src, weights=t_na, minlength=st.span)
    out._flag_rows = {}

    # -- receive counts: point updates + max-tree maintenance -----------------
    recv = st.recv.copy()
    np.subtract.at(recv, ph.dst[rm_local], 1)
    np.add.at(recv, dst_a, 1)
    tree = st.recv_tree.copy()
    touched_dst = np.unique(np.concatenate([ph.dst[rm_local], dst_a]))
    tree.update_many(touched_dst, recv[touched_dst])
    out.recv = recv
    out.recv_tree = tree

    # -- byte totals: pairwise-summation replay (order-sensitive) -------------
    out.net_bytes = float(size[is_net].sum())
    out.total_bytes = float(size.sum())

    # -- routing: once materialized, filter surviving expansion rows and
    #    route additions only; contention itself stays lazy ------------------
    if st._exp is None:
        out._exp = None                  # never queried: stay lazy
    else:
        old_msg, old_link = st._exp
        keep_exp = keep[old_msg]
        remap = np.cumsum(keep) - 1                   # old local -> new local
        exp_msg = remap[old_msg[keep_exp]]
        exp_link = old_link[keep_exp]
        sel_a = is_net_a & (torus_src[nkeep:] != torus_dst[nkeep:])
        if sel_a.any():
            sidx = nkeep + np.nonzero(sel_a)[0]
            midx, link = m.torus.route_link_ids(torus_src[sidx],
                                                torus_dst[sidx])
            exp_msg = np.concatenate([exp_msg, sidx[midx]])
            exp_link = np.concatenate([exp_link, link])
            # restore the dimension-major emission order of a fresh
            # route_link_ids call; the sort is stable, so the per-(dim,
            # message) hop order survives and the per-(link, source)
            # histogram replay stays bit-identical
            order = np.lexsort((exp_msg, exp_link % m.torus.ndim))
            exp_msg, exp_link = exp_msg[order], exp_link[order]
        out._exp = (exp_msg, exp_link)
    out._max_link = None
    return out


class DeltaStack:
    """A sweep arena that prices *mutations* at O(changed) cost.

    Construction (``from_phases``) pays the same one-time cost as
    ``PhaseStack.build``; every subsequent :meth:`apply` touches only the
    phases named by the delta.  ``apply`` is functional: it returns a new
    ``DeltaStack`` sharing every clean phase's state with its parent, so a
    rejected local-search candidate is discarded by dropping the object —
    no undo log.  The query surface mirrors :class:`~repro.comm.PhaseStack`
    (``cost_arrays`` / ``sim_arrays`` / ``phases`` / ``n_procs``), and the
    batched entry points accept either.
    """

    def __init__(self, machine, states: tuple, verify: bool = False):
        self.machine = machine
        self._states = states
        self.verify = bool(verify)
        self.phases = tuple(st.phase for st in states)
        counts = np.asarray([ph.n_msgs for ph in self.phases], dtype=np.int64)
        self.offsets = np.concatenate([[0], np.cumsum(counts)]).astype(np.int64)
        self.n_procs = np.asarray([ph.n_procs for ph in self.phases],
                                  dtype=np.int64)
        self._fresh_cache = None

    # -- construction ---------------------------------------------------------
    @classmethod
    def from_phases(cls, phases, *, verify: bool = False) -> "DeltaStack":
        """Bind a sweep ``phases`` (bound ``CommPhase``s or a ``PhaseStack``)
        as a delta arena.  Same-machine validation matches
        ``PhaseStack.build``; ``verify=True`` re-checks the bit-identity
        contract after construction and every ``apply``."""
        if isinstance(phases, PhaseStack):
            phases = phases.phases
        phases = tuple(phases)
        for ph in phases:
            if not isinstance(ph, CommPhase):
                raise TypeError(
                    f"DeltaStack wraps bound CommPhases, got {type(ph).__name__}")
        machine = phases[0].machine if phases else None
        for ph in phases:
            if ph.machine is not machine:
                raise ValueError(
                    "mixed machines: every phase in a DeltaStack must be "
                    "bound to the same machine object (rebind with "
                    "CommPhase.build / CommPattern.bind first)")
        out = cls(machine, tuple(_build_state(ph) for ph in phases),
                  verify=verify)
        if verify:
            out.check()
        return out

    # -- basic stats ----------------------------------------------------------
    @property
    def n_phases(self) -> int:
        return len(self._states)

    @property
    def total_msgs(self) -> int:
        return int(self.offsets[-1]) if self.offsets.size else 0

    def __len__(self) -> int:
        return self.n_phases

    def __iter__(self):
        return iter(self.phases)

    def fingerprint(self) -> str:
        """Content-hash of the arena's current phases, as a hex string.

        SHA-256 over the per-phase :func:`phase_fingerprint` digests in
        phase order — so a ``DeltaStack`` and a fresh arena over the same
        phases (same message order) hash identically, and any ``apply``
        changes the fingerprint.  This is the cache key the strategy
        service's :class:`repro.serve.ArenaCache` stores priced verdicts
        under.
        """
        h = hashlib.sha256()
        h.update(b"repro.delta.v1")
        for ph in self.phases:
            h.update(bytes.fromhex(
                phase_fingerprint(ph.src, ph.dst, ph.size, ph.n_procs)))
        return h.hexdigest()

    # -- mutation -------------------------------------------------------------
    def apply(self, removed_idx=None, added=None, *,
              verify: bool | None = None) -> "DeltaStack":
        """One delta step: drop messages, append messages, re-price.

        Parameters
        ----------
        removed_idx : arena indices (into the current concatenated message
            order, ``offsets[p] + local``) of messages to remove.  Must be
            unique and in range.
        added : ``{phase_index: (src, dst, size)}`` mapping (or a sequence
            with one entry — possibly None — per phase).  Added endpoints
            must lie inside the phase's fixed process count.
        verify : override the stack's debug flag for this step.

        Returns a new ``DeltaStack``; phases outside the delta share state
        with ``self``.  An empty delta returns an equal-valued stack.
        """
        verify = self.verify if verify is None else bool(verify)
        rm = (np.zeros(0, dtype=np.int64) if removed_idx is None
              else np.asarray(removed_idx, dtype=np.int64).ravel())
        if rm.size:
            uniq = np.unique(rm)
            if uniq.size != rm.size:
                raise ValueError("removed_idx contains duplicate indices")
            rm = uniq
            if rm[0] < 0 or rm[-1] >= self.total_msgs:
                raise ValueError(
                    f"removed_idx out of range for an arena of "
                    f"{self.total_msgs} messages")
        if added is None:
            added = {}
        elif not isinstance(added, dict):
            added = {i: a for i, a in enumerate(added) if a is not None}
        added = {int(k): v for k, v in added.items()}
        for k in added:
            if not 0 <= k < self.n_phases:
                raise ValueError(
                    f"added phase index {k} out of range for "
                    f"{self.n_phases} phases")
        pid = np.searchsorted(self.offsets, rm, side="right") - 1
        local = rm - self.offsets[pid]
        dirty = sorted(set(pid.tolist()) | {int(k) for k, v in added.items()
                                            if np.asarray(v[0]).size})
        states = list(self._states)
        for i in dirty:
            states[i] = _mutate_state(self._states[i], local[pid == i],
                                      added.get(i))
        out = DeltaStack(self.machine, tuple(states), verify=verify)
        if verify:
            out.check()
        return out

    # -- fallback arena -------------------------------------------------------
    def _fresh(self) -> PhaseStack:
        """A fresh ``PhaseStack`` over the current phases — the delegate for
        fitted-params overrides and non-numpy backends, and the reference
        :meth:`check` compares against.  Built once per generation."""
        if self._fresh_cache is None:
            self._fresh_cache = PhaseStack.build(self.phases)
        return self._fresh_cache

    # -- model-side aggregates ------------------------------------------------
    def cost_arrays(self, params=None, *, node_aware: bool = True,
                    use_maxrate: bool = True, with_queue: bool = True,
                    with_net_bytes: bool = True, backend=None):
        """Per-phase ``(transport, max_recv, net_bytes)`` from the delta
        caches — same contract (and same ``params`` / ``node_aware`` /
        ``use_maxrate`` / ``with_queue`` / ``with_net_bytes`` / ``backend``
        arguments) as :meth:`PhaseStack.cost_arrays`.

        The fast path serves the machine's own parameter tables on the numpy
        backend; a fitted-params override or an accelerator backend
        delegates to a fresh arena over the current phases (built once per
        generation), so results stay correct either way.
        """
        backend_name, _ = PhaseStack._backend(backend)  # eager validation
        N = self.n_phases
        zeros = np.zeros(N)
        if N == 0 or self.total_msgs == 0:
            return zeros, zeros.copy(), zeros.copy()
        m = self.machine
        p = params if params is not None else m.params
        flags = (node_aware, use_maxrate)
        if p is not m.params or backend_name != "numpy" or flags not in _FLAGS:
            return self._fresh().cost_arrays(
                params, node_aware=node_aware, use_maxrate=use_maxrate,
                with_queue=with_queue, with_net_bytes=with_net_bytes,
                backend=backend)
        transport = np.asarray([st.row(flags).max(initial=0.0)
                                for st in self._states], dtype=np.float64)
        max_recv = (np.asarray([st.recv_tree.max() for st in self._states],
                               dtype=np.float64)
                    if with_queue else zeros.copy())
        if not with_net_bytes:
            net_bytes = zeros.copy()
        elif node_aware:
            net_bytes = np.asarray([st.net_bytes for st in self._states])
        else:                       # every message priced as network-class
            net_bytes = np.asarray([st.total_bytes for st in self._states])
        return transport, max_recv, net_bytes

    # -- simulator-side aggregates --------------------------------------------
    def sim_arrays(self, recv_post_orders=None, arrival_orders=None,
                   backend=None) -> StackSimArrays:
        """Raw simulator aggregates — same contract (and same
        ``recv_post_orders`` / ``arrival_orders`` / ``backend`` arguments)
        as :meth:`PhaseStack.sim_arrays`.  Transport and link contention
        come from the delta caches; default-order queue steps are the
        maintained receive counts, custom orders pay the per-phase Fenwick
        walk.
        """
        backend_name, _ = PhaseStack._backend(backend)
        if backend_name != "numpy":
            return self._fresh().sim_arrays(recv_post_orders, arrival_orders,
                                            backend=backend_name)
        if self.n_phases == 0:
            z = np.zeros(0)
            return StackSimArrays(z, [], [], z.copy(), z.copy())
        empty_f = np.zeros(0)
        empty_i = np.zeros(0, dtype=np.int64)
        per_proc, qsteps = [], []
        default_orders = recv_post_orders is None and arrival_orders is None
        for i, st in enumerate(self._states):
            ph = st.phase
            if ph.n_msgs == 0:
                per_proc.append(empty_f)
                qsteps.append(empty_i)
                continue
            per_proc.append(st.row_na[:ph.n_procs].copy())
            if default_orders:
                qsteps.append(st.recv[:ph.n_procs].copy())
            else:
                qsteps.append(ph.queue_steps(
                    recv_post_orders[i] if recv_post_orders else None,
                    arrival_orders[i] if arrival_orders else None))
        transport = np.asarray([st.row_na.max(initial=0.0)
                                for st in self._states], dtype=np.float64)
        return StackSimArrays(
            transport=transport, per_proc=per_proc, qsteps=qsteps,
            max_link=np.asarray([st.link_contention()
                                 for st in self._states]),
            net_bytes=np.asarray([st.net_bytes for st in self._states]))

    # -- the debug contract ---------------------------------------------------
    def check(self) -> None:
        """Assert bit-identity against a freshly built arena.

        Three layers: the mutated phases' cached per-message fields must
        equal ``CommPhase.build`` from their raw arrays; every ladder flag
        pair's ``cost_arrays`` must equal the fresh stack's; and the
        default-order ``sim_arrays`` must match field for field.  Raises
        ``AssertionError`` on the first divergence.
        """
        for i, ph in enumerate(self.phases):
            rb = CommPhase.build(ph.machine, ph.src, ph.dst, ph.size,
                                 n_procs=ph.n_procs)
            for f in ("loc", "proto", "is_net", "send_node", "torus_src",
                      "torus_dst", "active_ppn"):
                assert np.array_equal(getattr(ph, f), getattr(rb, f)), \
                    f"phase {i}: cached {f} drifted from a fresh build"
        fresh = PhaseStack.build(self.phases)
        for flags in _FLAGS:
            got = self.cost_arrays(node_aware=flags[0], use_maxrate=flags[1])
            want = fresh.cost_arrays(node_aware=flags[0],
                                     use_maxrate=flags[1])
            for g, w, name in zip(got, want,
                                  ("transport", "max_recv", "net_bytes")):
                assert np.array_equal(g, w), \
                    f"cost_arrays{flags} {name} drifted from a fresh build"
        got = self.sim_arrays()
        want = fresh.sim_arrays()
        assert np.array_equal(got.transport, want.transport)
        assert np.array_equal(got.max_link, want.max_link)
        assert np.array_equal(got.net_bytes, want.net_bytes)
        for g, w in zip(got.per_proc, want.per_proc):
            assert np.array_equal(g, w), "per-proc transport drifted"
        for g, w in zip(got.qsteps, want.qsteps):
            assert np.array_equal(g, w), "queue steps drifted"
        self._fresh_cache = fresh


#: The arena types the batched entry points price straight from cached
#: aggregates (both expose the cost_arrays / sim_arrays query surface).
#: Import this instead of spelling the pair out so a future arena type has
#: one edit point.
ARENA_TYPES = (PhaseStack, DeltaStack)
