"""Vectorized primitives shared by the model ladder and the event simulator.

Both sides of the paper's model/measurement gap — the closed-form models in
:mod:`repro.core.models` and the mechanistic simulator in
:mod:`repro.net.simulator` — need the same per-phase quantities: how many
processes on each node are actively injecting into the network, what the
max-rate transport time of each message is, and how many receive-queue slots
each envelope walks.  This module computes all of them with array ops
(``np.unique`` / ``bincount`` / batched Fenwick rounds) so neither consumer
keeps a per-message Python loop.

Imports numpy only: it sits *below* both ``repro.core`` and ``repro.net`` in
the layering, so either package can build on it without import cycles.
"""
from __future__ import annotations

import numpy as np


# -- active senders per node -------------------------------------------------

def active_senders_per_node(src, node, is_net) -> np.ndarray:
    """Per-message count of actively-communicating processes on the sender's node.

    ``src[i]`` / ``node[i]`` / ``is_net[i]`` are message ``i``'s sending
    process, that process's node, and whether the message is network-class.
    A process is *active* on its node if it sends at least one network-class
    message; every network message then contends with its node's active-sender
    count for injection bandwidth (the max-rate mechanism).  Non-network
    messages get 1.  Computed via ``np.unique`` over (node, sender) pairs —
    no dict-of-sets walk.
    """
    src = np.asarray(src, dtype=np.int64)
    node = np.asarray(node, dtype=np.int64)
    is_net = np.asarray(is_net, dtype=bool)
    ppn = np.ones(src.shape, dtype=np.float64)
    if src.size == 0 or not is_net.any():
        return ppn
    nd, sp = node[is_net], src[is_net]
    span = np.int64(sp.max()) + 1
    pair_node = np.unique(nd * span + sp) // span     # distinct (node, sender)
    nodes_u, senders = np.unique(pair_node, return_counts=True)
    ppn[is_net] = senders[np.searchsorted(nodes_u, nd)]
    return ppn


# -- max-rate message pricing ------------------------------------------------

def transport_times(size, alpha, Rb, RN, ppn, is_net,
                    use_maxrate: bool = True, rails: int = 1, xp=np):
    """Per-message transport time under the (node-aware) max-rate model.

    ``size`` is bytes per message, ``ppn`` the active-senders count on each
    sender's node; ``alpha``/``Rb``/``RN`` are the already-indexed per-message parameter
    arrays (locality x protocol lookup done by the caller, which owns the
    table layout).  Only network-class messages (``is_net``) contend for the
    node injection cap ``RN``; with ``use_maxrate=False`` the cap is ignored
    (pure postal model).

    ``rails`` is the node's NIC count (``CommParams.n_rails``): a node's
    ``ppn`` active senders divide across its rails, so only
    ``ceil(ppn / rails)`` processes contend per NIC and ``RN`` is the
    *per-rail* cap.  ``rails=1`` is bit-identical to the pre-rail formula.

    ``xp`` is the array namespace (:func:`repro.comm.xp.get_xp`): the
    default :mod:`numpy` is the bit-identity reference; with ``jax.numpy``
    the same formula runs device-resident in float32 (inputs already on
    device stay there — the stack's device pricing path).
    """
    f = np.float64 if xp is np else xp.float32
    size = xp.asarray(size, dtype=f)
    if not use_maxrate:
        return alpha + size / Rb
    eff = xp.asarray(ppn, dtype=f)
    if rails != 1:
        eff = xp.ceil(eff / rails)
    eff = xp.where(xp.asarray(is_net, dtype=bool), xp.maximum(eff, 1.0), 1.0)
    rate = xp.minimum(RN, eff * Rb)
    return alpha + eff * size / rate


def per_proc_sums(idx, values, n: int) -> np.ndarray:
    """Sum ``values`` into ``n`` bins by ``idx`` (send-side transport sums)."""
    return np.bincount(np.asarray(idx, dtype=np.int64),
                       weights=np.asarray(values, dtype=np.float64),
                       minlength=n)


#: Widest packed key range, per input pair, that :func:`sum_by_pairs`
#: groups by direct index; a wider one is sorted (sorting wins on a CPU
#: host from about 4-8 keys a pair).
_DENSE_KEYS_PER_PAIR = 4


def sum_by_pairs(a, b, w) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Aggregate weights ``w`` over distinct ``(a, b)`` pairs.

    Returns ``(ua, ub, sums)`` sorted by ``(a, b)``; ``sums[i]`` is the total
    weight of pair ``(ua[i], ub[i])``, added in array order.  The pairs are
    packed into one key; where the key range is at most
    ``_DENSE_KEYS_PER_PAIR`` times the input, ``bincount`` on the key itself
    groups them (node pairs, self-pairs), else ``np.unique`` sorts the key
    and ``bincount`` sums on its inverse.  Both add the same terms in the
    same order: the result does not depend on the path.  ``a`` and ``b``
    must be non-negative integers.
    """
    a = np.asarray(a, dtype=np.int64)
    b = np.asarray(b, dtype=np.int64)
    w = np.asarray(w, dtype=np.float64)
    if a.size == 0:
        return a, b, w
    span = np.int64(b.max()) + 1
    key = a * span + b
    if int(key.max()) < _DENSE_KEYS_PER_PAIR * a.size:
        uk = np.flatnonzero(np.bincount(key))
        sums = np.bincount(key, weights=w)[uk]
    else:
        uk, inv = np.unique(key, return_inverse=True)
        sums = np.bincount(inv, weights=w)
    return (uk // span).astype(np.int64), (uk % span).astype(np.int64), sums


def segmented_arange(counts) -> np.ndarray:
    """``[0..counts[0]), [0..counts[1]), ...`` concatenated (one arange per
    segment, no Python loop) — the rank index of each expanded element within
    its segment, used to fan a message out across ``counts[i]`` peers."""
    counts = np.asarray(counts, dtype=np.int64)
    total = int(counts.sum())
    if total == 0:
        return np.zeros(0, dtype=np.int64)
    offsets = np.concatenate([[0], np.cumsum(counts)[:-1]])
    return np.arange(total) - np.repeat(offsets, counts)


def group_by_receiver(dst, n_procs: int) -> tuple[np.ndarray, np.ndarray]:
    """Stable grouping of message indices by destination process ``dst``.

    Returns ``(order, bounds)``: ``order[bounds[p]:bounds[p+1]]`` are the
    indices of messages destined to process ``p`` (of ``n_procs``), in
    posting (array) order.
    """
    dst = np.asarray(dst, dtype=np.int64)
    order = np.argsort(dst, kind="stable")
    bounds = np.searchsorted(dst[order], np.arange(n_procs + 1))
    return order, bounds


# -- grouped receive-queue accounting ---------------------------------------

def flat_orders(orders):
    """Normalize a per-slot order spec to flat ``(slots, lens, ids)`` form.

    ``orders`` is either already flat — ``slots`` strictly increasing,
    ``ids`` the concatenated per-slot permutations of global message indices
    in slot order, ``lens`` their lengths — or a dict mapping each slot to
    its permutation (the per-receiver form, normalized here with one sort
    and one concatenate).  Returns None when there is nothing custom.
    """
    if orders is None:
        return None
    if isinstance(orders, tuple):
        slots, lens, ids = orders
        slots = np.asarray(slots, dtype=np.int64)
        if slots.size == 0:
            return None
        return (slots, np.asarray(lens, dtype=np.int64),
                np.asarray(ids, dtype=np.int64))
    if not orders:
        return None
    pairs = sorted((int(s), np.asarray(v, dtype=np.int64))
                   for s, v in orders.items())
    return (np.asarray([s for s, _ in pairs], dtype=np.int64),
            np.asarray([v.size for _, v in pairs], dtype=np.int64),
            np.concatenate([v for _, v in pairs]))


def _assemble_orders(flat, slots, counts, cbounds, local, group,
                     describe) -> np.ndarray:
    """Region-local permutation array for every custom slot, in slot order.

    ``flat`` is a normalized :func:`flat_orders` spec (or None); slots it
    does not cover — and covered slots outside the custom set ``slots``,
    mirroring the per-phase behaviour of silently ignoring orders for
    receivers with no messages — default to array order.  Assembly and
    validation (length, destination, permutation) are single vectorized
    passes.
    """
    out = segmented_arange(counts)                    # default: array order
    if flat is None:
        return out
    pslots, lens, ids_cat = flat
    keep = np.isin(pslots, slots, assume_unique=True)
    if not keep.all():
        sel = np.repeat(keep, lens)
        pslots, lens, ids_cat = pslots[keep], lens[keep], ids_cat[sel]
    if pslots.size == 0:
        return out
    rank = np.searchsorted(slots, pslots)             # position among customs
    bad = np.nonzero(lens != counts[rank])[0]
    if bad.size:
        raise ValueError(
            f"order for {describe(int(pslots[bad[0]]))} must be a "
            f"permutation of the {int(counts[rank[bad[0]]])} message "
            f"indices destined to it")
    slot_rep = np.repeat(pslots, lens)
    rank_rep = np.repeat(rank, lens)
    pos = cbounds[rank_rep] + segmented_arange(lens)
    ok = group[ids_cat] == slot_rep           # ids destined to another slot?
    if not ok.all():
        bad = int(np.argmax(~ok))
        raise ValueError(
            f"order for {describe(int(slot_rep[bad]))} must be a "
            f"permutation of the message indices destined to it")
    vals = local[ids_cat]                     # in [0, counts[slot]) given ok
    hits = np.bincount(cbounds[rank_rep] + vals, minlength=int(cbounds[-1]))
    if hits.max(initial=0) > 1:
        bad = int(np.argmax(hits[cbounds[rank_rep] + vals] > 1))
        raise ValueError(
            f"order for {describe(int(slot_rep[bad]))} must be a "
            f"permutation of the message indices destined to it")
    out[pos] = vals
    return out


def grouped_queue_steps(group, n_slots, recv_post_order=None,
                        arrival_order=None, groups=None,
                        describe=None, backend=None) -> np.ndarray:
    """Exact receive-queue traversal-step totals for ``n_slots`` receiver slots.

    ``group[i]`` is the receiver slot of message ``i`` (a process id, or a
    packed ``(phase, process)`` key for a stacked sweep).  The order specs —
    ``recv_post_order`` (posting order) and ``arrival_order``
    (envelope-arrival order) — give each custom slot a permutation of the
    global indices of its messages, as a dict or in the flat
    :func:`flat_orders` form; missing slots use array order (one
    step per arrival).  All custom slots pay the exact Fenwick walk in one
    batched sweep; assembly and validation of the custom permutations are
    vectorized (:func:`_assemble_orders`).

    ``groups`` optionally supplies a precomputed ``(order, bounds)`` stable
    grouping (e.g. :meth:`repro.comm.CommPhase.receiver_groups`); ``describe``
    renders a slot id in error messages.  ``backend`` selects where the
    walk itself runs (``None``/``'numpy'`` = the in-process numpy Fenwick
    rounds; ``'jax'``/``'pallas'`` = the device walk in
    :func:`repro.kernels.comm_stack.queue_walk`, dominance counts in
    dense tiles — bit-equal, it is integer work).
    """
    group = np.asarray(group, dtype=np.int64)
    if describe is None:
        describe = "receiver {}".format
    if groups is not None:
        order, bounds = groups
    else:
        order, bounds = group_by_receiver(group, n_slots)
    counts = np.diff(bounds)
    qsteps = counts.astype(np.int64).copy()           # array order: 1/arrival
    if group.size == 0:
        return qsteps
    post = flat_orders(recv_post_order)
    arr = flat_orders(arrival_order)
    if post is None and arr is None:
        return qsteps
    cand = (post[0] if arr is None else
            arr[0] if post is None else np.union1d(post[0], arr[0]))
    cand = cand[(cand >= 0) & (cand < n_slots)]
    slots = cand[counts[cand] > 0]                    # silent slots excluded
    if slots.size == 0:
        return qsteps
    # local index of every message within its slot's group
    local = np.empty(group.size, dtype=np.int64)
    local[order] = np.arange(group.size) - np.repeat(bounds[:-1], counts)
    ccounts = counts[slots]
    cbounds = np.concatenate([[0], np.cumsum(ccounts)])
    posted = _assemble_orders(post, slots, ccounts, cbounds, local, group,
                              describe)
    arrive = _assemble_orders(arr, slots, ccounts, cbounds, local, group,
                              describe)
    if backend in (None, "numpy"):
        steps = batched_queue_traversal_steps(posted, arrive, cbounds)
    else:
        from repro.kernels.comm_stack import queue_walk
        steps = queue_walk(posted, arrive, cbounds, backend=backend)
    qsteps[slots] = np.add.reduceat(steps, cbounds[:-1])
    return qsteps


# -- receive-queue walk ------------------------------------------------------

class _Fenwick:
    """Binary indexed tree over n slots holding 0/1 'still unmatched' flags."""

    def __init__(self, n: int):
        self.n = n
        idx = np.arange(n + 1, dtype=np.int64)
        self.t = idx & -idx          # prefix tree of all-ones
        self.t[0] = 0

    def _add(self, i: int, v: int) -> None:
        while i <= self.n:
            self.t[i] += v
            i += i & -i

    def prefix(self, i: int) -> int:
        s = 0
        while i > 0:
            s += self.t[i]
            i -= i & -i
        return int(s)

    def remove(self, i: int) -> None:
        self._add(i, -1)


def queue_traversal_steps(posted_order, arrival_order) -> np.ndarray:
    """Exact queue-walk lengths for one receiving process (reference Fenwick).

    ``posted_order[k]`` = message id posted k-th; ``arrival_order[j]`` =
    message id of the j-th arriving envelope.  Returns steps per arrival: the
    1-based position of the match in the still-unmatched posted queue —
    exactly what CrayMPI's linear receive-queue search pays.

    This is the scalar per-process reference; the simulator uses
    :func:`batched_queue_traversal_steps` across all receivers at once.
    """
    posted_order = np.asarray(posted_order)
    n = len(posted_order)
    pos = np.empty(n, dtype=np.int64)
    pos[posted_order] = np.arange(n)
    fen = _Fenwick(n)
    steps = np.empty(n, dtype=np.int64)
    for j, mid in enumerate(np.asarray(arrival_order)):
        p = int(pos[mid]) + 1               # 1-based slot
        steps[j] = fen.prefix(p)            # unmatched entries at/before slot
        fen.remove(p)
    return steps


def _prefix_many(tree: np.ndarray, base: np.ndarray, i: np.ndarray,
                 depth: int) -> np.ndarray:
    """Fenwick prefix sums for an array of region-local 1-based indices.

    ``base[r]`` offsets region r's private tree inside the shared ``tree``
    array; the Fenwick index arithmetic runs on the *local* index, so walk
    depth is the bit-length of the region's padded span, not the global
    one.  Maskless: an index that reaches 0 stays 0 (``0 & -0 == 0``) and
    keeps adding the region's always-zero slot 0 — pure gathers, no
    reductions.
    """
    i = np.array(i, dtype=np.int64, copy=True)
    out = np.zeros(i.shape, dtype=np.int64)
    for _ in range(depth):
        out += tree[base + i]
        i -= i & -i
    return out


def _add_many(tree: np.ndarray, base: np.ndarray, i: np.ndarray,
              bound: np.ndarray, v: int, depth: int) -> None:
    """Fenwick point updates for distinct region-local 1-based indices.

    Maskless like :func:`_prefix_many`: a chain that climbs past its
    region's padded span ``bound[r]`` parks at the shared sink slot (the
    last tree cell, never read), so every round is one scatter-add plus
    index arithmetic.
    """
    sink = tree.size - 1
    i = np.array(i, dtype=np.int64, copy=True)
    idx = base + i
    for _ in range(depth):
        np.add.at(tree, idx, v)             # ancestors may collide across slots
        i += i & -i
        idx = np.where(i > bound, sink, base + i)


def batched_queue_traversal_steps(posted, arrival, bounds) -> np.ndarray:
    """Queue-walk lengths for many receiving processes in one batched sweep.

    Region ``r`` (one receiver) occupies slots ``bounds[r]:bounds[r+1]`` of
    the concatenated ``posted`` / ``arrival`` arrays, which hold region-local
    message indices.  Returns per-arrival steps in the same layout — equal to
    stacking :func:`queue_traversal_steps` per region.

    All regions advance in lock-step: one round per arrival *depth*, each
    round one maskless vectorized Fenwick prefix + one removal over every
    still-active receiver.  Every region owns a private Fenwick tree (padded
    to a power of two) inside one shared array, so walk depth is the
    bit-length of the *largest region*, not of the whole sweep, and the walk
    length is a single local prefix (no start-offset subtraction).
    Python-level work is O(max msgs-per-receiver * log max msgs-per-receiver)
    rounds-times-depth, with every array op spanning all active receivers.
    """
    posted = np.asarray(posted, dtype=np.int64)
    arrival = np.asarray(arrival, dtype=np.int64)
    bounds = np.asarray(bounds, dtype=np.int64)
    N = int(posted.size)
    steps = np.zeros(N, dtype=np.int64)
    if N == 0:
        return steps
    starts = bounds[:-1]
    counts = np.diff(bounds)
    region_of = np.repeat(np.arange(counts.size), counts)
    start_of = starts[region_of]
    pos = np.empty(N, dtype=np.int64)                 # local id -> local slot
    pos[start_of + posted] = np.arange(N) - start_of
    b = pos[start_of + arrival]                       # slot of j-th arrival
    # private per-region Fenwick trees in one shared array: region r owns
    # slots [toff[r], toff[r] + span[r]] (local 0 is its always-zero root),
    # spans padded to powers of two, one shared sink slot at the very end
    span = np.ones(counts.size, dtype=np.int64)
    while (span < counts).any():
        span = np.where(span < counts, span * 2, span)
    blk = span + 1
    toff = np.concatenate([[0], np.cumsum(blk)])
    tree = np.zeros(toff[-1] + 1, dtype=np.int64)     # +1: shared sink
    li = segmented_arange(blk)                        # local 0..span per region
    c_rep = np.repeat(counts, blk)
    lo = li - (li & -li)
    tree[:-1] = np.minimum(li, c_rep) - np.minimum(lo, c_rep)
    depth = int(span.max()).bit_length()              # chains: <= log2 + 1
    regions = np.nonzero(counts)[0]
    for j in range(int(counts.max())):
        act = regions[counts[regions] > j]
        if act.size == 0:
            break
        s = starts[act]
        p = b[s + j] + 1                              # local 1-based slot
        base = toff[act]
        steps[s + j] = _prefix_many(tree, base, p, depth)
        _add_many(tree, base, p, span[act], -1, depth)
    return steps
