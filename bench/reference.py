"""Plain reference of the planner's answer, independent of the program.

For one message set ``(src, dst, size)`` on ``n_procs`` ranks of a machine
it builds every node-aware strategy's phase sequence and prices each
candidate twice, as the planner's verdict does:

* **model**: the paper's ladder at its top rung (Bienz, Gropp & Olson,
  EuroMPI'18, Sections 3-4): per message ``alpha + eff * s / min(RN,
  eff * Rb)`` with ``eff`` the sender node's active network senders (over
  the node's rails), summed per sender and maximised over ranks; plus
  ``gamma * n^2`` for the busiest receiver's ``n`` envelopes; plus
  ``delta * ell`` with the cube-partition estimate ``ell = 2 h^d b ppn``.
* **simulator**: the same transport term; the exact receive-queue walk
  with receives posted in message order and envelopes arriving in an order
  drawn from ``numpy.random.default_rng(seed)`` (one generator per
  candidate, one uniform key per message of each phase in turn, arrival by
  ascending key within each receiver), priced ``gamma`` per step of the
  busiest receiver; and ``delta`` per contended byte of the hottest torus
  link under dimension-ordered routing, where a link's contended bytes are
  its bytes beyond the largest single source unit's.

Strategies: ``standard`` (as given); ``two_step`` (gather to the node
leader, one message per node pair, scatter); ``three_step`` (the node
pair's bytes split over ``k`` injector ranks, ``k`` the ranks present on
both nodes); on GPU nodes (Lockhart et al. 2022) ``host_staged``
(three_step over the host path with a device-to-host copy before and a
host-to-device copy after) and ``device_direct`` (gather to the device
leader, one message per device pair on the device-direct path, scatter).
A message set with no inter-node traffic is its own plan under every
strategy.  The rewritten messages of every aggregated phase come sorted by
``(src, dst)``; messages that stay on their node keep their order.  The
arrival draw gives keys by message index, so that order is part of the
answer.

Everything is float64 numpy.  ``lowp=True`` rounds every per-message
input and time to bfloat16 first: the lower precision that the control of
``correct`` prices in.
"""
from __future__ import annotations

import math

import numpy as np

SHORT, EAGER, REND = 0, 1, 2


class Machine:
    """A machine as a configuration file states it (``reference_machine``).

    Keys: ``procs_per_node``, ``nodes_per_torus_node``, ``sockets_per_node``,
    ``torus_dims``, ``torus_wrap``, ``torus_over_procs``,
    ``cross_node_class``, ``devices_per_node``, ``procs_per_device``, and
    ``params``: ``classes`` (locality class names, closest first),
    ``alpha`` / ``Rb`` / ``RN`` as ``[protocol][class]`` rows (short, eager,
    rendezvous; ``null`` for no cap), ``gamma``, ``delta``, ``short_max``,
    ``eager_max``, ``network_class`` and ``rails``.
    """

    def __init__(self, spec: dict):
        self.ppn = int(spec["procs_per_node"])
        self.nodes_per_unit = int(spec.get("nodes_per_torus_node", 1))
        self.sockets = int(spec.get("sockets_per_node", 1))
        self.dims = tuple(int(d) for d in spec["torus_dims"])
        self.wrap = bool(spec.get("torus_wrap", False))
        self.over_procs = bool(spec.get("torus_over_procs", False))
        self.dev_per_node = int(spec.get("devices_per_node", 0))
        self.ppd = int(spec.get("procs_per_device", 0))
        p = spec["params"]
        self.classes = tuple(p["classes"])
        self.cross = self.classes.index(spec["cross_node_class"])

        def table(rows):
            a = np.array([[np.inf if v is None else float(v) for v in row]
                          for row in rows], dtype=np.float64)
            return a.T                                  # [class, protocol]

        self.alpha, self.Rb, self.RN = (table(p["alpha"]), table(p["Rb"]),
                                        table(p["RN"]))
        self.gamma, self.delta = float(p["gamma"]), float(p["delta"])
        self.short_max, self.eager_max = float(p["short_max"]), float(
            p["eager_max"])
        self.net = self.classes.index(p["network_class"])
        self.rails = int(p.get("rails", 1))

    @property
    def n_units(self) -> int:
        return math.prod(self.dims)

    @property
    def procs_per_unit(self) -> int:
        return 1 if self.over_procs else self.nodes_per_unit * self.ppn

    def node(self, p):
        return np.asarray(p, dtype=np.int64) // self.ppn

    def unit(self, p):
        p = np.asarray(p, dtype=np.int64)
        return p if self.over_procs else self.node(p) // self.nodes_per_unit

    def locality(self, a, b) -> np.ndarray:
        same_node = self.node(a) == self.node(b)
        if self.dev_per_node:
            near = same_node & (a // self.ppd == b // self.ppd)
        elif self.sockets > 1:
            per = max(1, self.ppn // self.sockets)
            near = same_node & ((a % self.ppn) // per == (b % self.ppn) // per)
        else:
            return np.where(same_node, 0, self.cross).astype(np.int64)
        return np.where(near, 0, np.where(same_node, 1, self.cross)
                        ).astype(np.int64)

    def strategies(self) -> tuple[str, ...]:
        base = ("standard", "two_step", "three_step")
        if self.dev_per_node and all(c in self.classes for c in
                                     ("h2d", "host_staged", "device_direct")):
            return base + ("host_staged", "device_direct")
        return base


# -- rewrites ----------------------------------------------------------------

def _pairs(a, b, w):
    """Distinct ``(a, b)`` pairs in ascending order and their summed ``w``."""
    a = np.asarray(a, dtype=np.int64)
    b = np.asarray(b, dtype=np.int64)
    if a.size == 0:
        return a, b, np.zeros(0)
    width = np.int64(b.max()) + 1
    keys, inv = np.unique(a * width + b, return_inverse=True)
    return keys // width, keys % width, np.bincount(inv, weights=w)


def _fan(counts):
    """0..counts[i]-1 for each i, concatenated."""
    counts = np.asarray(counts, dtype=np.int64)
    starts = np.cumsum(counts) - counts
    return np.arange(counts.sum()) - np.repeat(starts, counts)


def rewrite(m: Machine, src, dst, size, n_procs: int, strategy: str):
    """The phase sequence of ``strategy``: a list of ``(src, dst, size,
    cls)`` with ``cls`` an explicit class index or None."""
    src = np.asarray(src, dtype=np.int64)
    dst = np.asarray(dst, dtype=np.int64)
    size = np.asarray(size, dtype=np.float64)
    remote = m.node(src) != m.node(dst)
    if strategy == "standard" or not remote.any():
        return [(src, dst, size, None)]
    parts = [(src[~remote], dst[~remote], size[~remote], None)]
    rs, rd, rz = src[remote], dst[remote], size[remote]
    if strategy == "device_direct":
        sd, dd = rs // m.ppd, rd // m.ppd
        keep = rs != sd * m.ppd
        parts.append((*_pairs(rs[keep], sd[keep] * m.ppd, rz[keep]), None))
        a, b, w = _pairs(sd, dd, rz)
        parts.append((a * m.ppd, b * m.ppd, w,
                      m.classes.index("device_direct")))
        keep = rd != dd * m.ppd
        parts.append((*_pairs(dd[keep] * m.ppd, rd[keep], rz[keep]), None))
    else:
        split = strategy in ("three_step", "host_staged")
        staged = strategy == "host_staged"
        sn, dn = m.node(rs), m.node(rd)

        def avail(nodes):
            return np.minimum(m.ppn, n_procs - nodes * m.ppn)

        if staged:
            parts.append((*_pairs(rs, rs, rz), m.classes.index("h2d")))
        k = (np.minimum(avail(sn), avail(dn)) if split
             else np.ones(rs.size, dtype=np.int64))
        msg = np.repeat(np.arange(rs.size), k)
        slot = _fan(k)
        share = rz[msg] / k[msg]
        g_src, g_dst = rs[msg], sn[msg] * m.ppn + slot
        keep = g_src != g_dst
        parts.append((*_pairs(g_src[keep], g_dst[keep], share[keep]), None))
        a, b, w = _pairs(sn, dn, rz)
        kp = (np.minimum(avail(a), avail(b)) if split
              else np.ones(a.size, dtype=np.int64))
        pair = np.repeat(np.arange(a.size), kp)
        pslot = _fan(kp)
        parts.append((a[pair] * m.ppn + pslot, b[pair] * m.ppn + pslot,
                      w[pair] / kp[pair],
                      m.classes.index("host_staged") if staged else None))
        s_src, s_dst = dn[msg] * m.ppn + slot, rd[msg]
        keep = s_src != s_dst
        parts.append((*_pairs(s_src[keep], s_dst[keep], share[keep]), None))
        if staged:
            parts.append((*_pairs(rd, rd, rz), m.classes.index("h2d")))
    return [p for p in parts if p[0].size]


# -- pricing -----------------------------------------------------------------

def _bf16(x):
    import ml_dtypes
    return np.asarray(x, dtype=np.float64).astype(ml_dtypes.bfloat16
                                                  ).astype(np.float64)


def _inversions(vals, starts, lens) -> np.ndarray:
    """Pairs ``i < j`` with ``vals[i] > vals[j]`` inside each run
    ``vals[starts[r]:starts[r] + lens[r]]``, counted pair by pair."""
    out = np.zeros(lens.size, dtype=np.int64)
    top = int(lens.max(initial=0))
    width = 1
    while width < top:
        width *= 2
    lo = 0
    w = 1
    while w <= max(width, 1):
        sel = np.nonzero((lens > lo) & (lens <= w))[0]
        step = max(1, (1 << 24) // (w * w))
        upper = np.triu(np.ones((w, w), dtype=bool), k=1)
        for c in range(0, sel.size, step):
            rows = sel[c:c + step]
            col = np.arange(w)
            idx = starts[rows, None] + col[None, :]
            ok = col[None, :] < lens[rows, None]
            v = np.where(ok, vals[np.where(ok, idx, 0)], np.inf)
            gt = (v[:, :, None] > v[:, None, :]) & upper
            out[rows] = gt.sum(axis=(1, 2))
        lo = w
        w *= 2
    return out


def _link_contention(m: Machine, tsrc, tdst, w) -> float:
    """Hottest link's bytes beyond its largest single-source share."""
    lk, _, per_src = _link_sources(m, tsrc, tdst, w)
    if lk.size == 0:
        return 0.0
    first = np.nonzero(np.r_[True, lk[1:] != lk[:-1]])[0]
    total = np.add.reduceat(per_src, first)
    largest = np.maximum.reduceat(per_src, first)
    return float((total - largest).max(initial=0.0))


def _link_sources(m: Machine, tsrc, tdst, w):
    """Bytes per (link, source unit) under dimension-ordered routing, each
    step owning the link at the lower coordinate of the two nodes it
    joins: ``(link, source, bytes)`` sorted by link, then source."""
    nd = len(m.dims)
    stride = [math.prod(m.dims[i + 1:]) for i in range(nd)]
    ca = np.stack([(tsrc // stride[i]) % m.dims[i] for i in range(nd)], 1)
    cb = np.stack([(tdst // stride[i]) % m.dims[i] for i in range(nd)], 1)
    links, srcs, byts = [], [], []
    for i in range(nd):
        n = m.dims[i]
        if m.wrap:
            fwd = (cb[:, i] - ca[:, i]) % n
            back = fwd - n
            delta = np.where(np.abs(back) < fwd, back, fwd)
        else:
            delta = cb[:, i] - ca[:, i]
        hops = np.abs(delta)
        msg = np.repeat(np.arange(tsrc.size), hops)
        k = _fan(hops)
        c0 = ca[msg, i]
        coord = np.where(delta[msg] < 0, c0 - k - 1, c0 + k) % n
        base = np.zeros(msg.size, dtype=np.int64)
        for j in range(nd):
            if j != i:
                base += (cb[msg, j] if j < i else ca[msg, j]) * stride[j]
        links.append((base + coord * stride[i]) * nd + i)
        srcs.append(tsrc[msg])
        byts.append(w[msg])
    if not links:
        z = np.zeros(0, dtype=np.int64)
        return z, z, np.zeros(0)
    return _pairs(np.concatenate(links), np.concatenate(srcs),
                  np.concatenate(byts))


def price_phase(m: Machine, src, dst, size, cls, n_procs: int, rng,
                lowp: bool = False) -> tuple[float, float]:
    """``(model, simulator)`` seconds of one phase; draws the phase's
    arrival keys from ``rng`` (or posts in order when ``rng`` is None)."""
    q = _bf16 if lowp else (lambda x: np.asarray(x, dtype=np.float64))
    loc = (m.locality(src, dst) if cls is None
           else np.full(src.size, cls, dtype=np.int64))
    proto = np.where(size <= m.short_max, SHORT,
                     np.where(size <= m.eager_max, EAGER, REND))
    is_net = loc >= m.net
    node = m.node(src)
    ppn = np.ones(src.size)
    if is_net.any():
        pairs = np.unique(np.stack([node[is_net], src[is_net]], 1), axis=0)
        nodes, senders = np.unique(pairs[:, 0], return_counts=True)
        ppn[is_net] = senders[np.searchsorted(nodes, node[is_net])]
    eff = np.ceil(ppn / m.rails) if m.rails != 1 else ppn
    eff = np.where(is_net, np.maximum(eff, 1.0), 1.0)
    z = q(size)
    t = q(q(m.alpha[loc, proto]) + eff * z
          / np.minimum(q(m.RN[loc, proto]), eff * q(m.Rb[loc, proto])))
    transport = float(np.bincount(src, weights=t, minlength=n_procs).max())

    recv = np.bincount(dst, minlength=n_procs)
    queue_model = m.gamma * float(recv.max()) ** 2
    cont_model = 0.0
    net_bytes = float(z[is_net].sum())
    if m.n_units > 1 and net_bytes > 0.0:
        d = len(m.dims)
        c = max(1, math.ceil(m.n_units ** (1.0 / d) - 1e-9))
        h = 0.0 if c <= 1 else d * (c * c - 1.0) / (3.0 * c)
        cont_model = m.delta * 2.0 * h ** d * (net_bytes / n_procs) \
            * m.procs_per_unit
    model = transport + queue_model + cont_model

    steps = recv.astype(np.int64)
    if rng is not None:
        keys = rng.random(src.size)
        order = np.argsort(dst, kind="stable")
        starts = np.searchsorted(dst[order], np.arange(n_procs))
        steps = steps + _inversions(keys[order], starts, recv)
    tsrc, tdst = m.unit(src), m.unit(dst)
    sel = is_net & (tsrc != tdst)
    contended = _link_contention(m, tsrc[sel], tdst[sel], z[sel])
    sim = transport + m.gamma * float(steps.max()) + m.delta * contended
    return model, sim


def verdict(m: Machine, src, dst, size, n_procs: int, seed: int,
            lowp: bool = False) -> dict:
    """Every strategy's model and simulator cost of one message set, and
    the winners (the cheaper candidate; the first on a tie)."""
    model, sim = {}, {}
    for name in m.strategies():
        rng = np.random.default_rng(seed)
        costs = [price_phase(m, s, d, z, c, n_procs, rng, lowp)
                 for s, d, z, c in rewrite(m, src, dst, size, n_procs, name)]
        model[name] = sum(c[0] for c in costs)
        sim[name] = sum(c[1] for c in costs)
    return {"model": model, "sim": sim,
            "model_winner": min(model, key=model.get),
            "sim_winner": min(sim, key=sim.get)}


def arena_sizes(m: Machine, message_sets) -> dict:
    """Sizes of the arena that prices every strategy of every
    ``(src, dst, size, n_procs)`` set at once: candidate phases, their
    messages, their distinct (phase, sender) pairs, and the (link, source
    unit) pairs and links that their routed network messages load."""
    phases = messages = senders = link_sources = links = 0
    for src, dst, size, n_procs in message_sets:
        for name in m.strategies():
            for s, d, z, c in rewrite(m, src, dst, size, n_procs, name):
                phases += 1
                messages += s.size
                senders += np.unique(s).size
                loc = (m.locality(s, d) if c is None
                       else np.full(s.size, c, dtype=np.int64))
                ts, td = m.unit(s), m.unit(d)
                sel = (loc >= m.net) & (ts != td)
                lk, _, _ = _link_sources(m, ts[sel], td[sel], z[sel])
                link_sources += lk.size
                links += np.unique(lk).size
    return {"phases": phases, "messages": messages, "senders": senders,
            "link_sources": link_sources, "links": links}


def verdict_gap(got: dict, want: dict) -> float:
    """How far a verdict ``got`` lies from the reference ``want``: the
    largest relative gap of any candidate's cost, or of the reference cost
    of ``got``'s winner above the reference's best, on either side."""
    gap = 0.0
    for side in ("model", "sim"):
        ref = want[side]
        if set(got[side]) != set(ref):
            return math.inf
        for k, v in ref.items():
            gap = max(gap, abs(got[side][k] - v) / max(abs(v), 1e-300))
        best = min(ref.values())
        gap = max(gap, (ref[got[side + "_winner"]] - best)
                  / max(best, 1e-300))
    return gap
