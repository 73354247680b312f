"""Tests for the unified CommPhase engine: vectorized routing, batched queue
walk, shared active-sender primitive, and model/simulator agreement with the
pre-refactor scalar implementations (golden values captured from the seed
code paths)."""
import numpy as np
import pytest

from _hypothesis_compat import given, settings, st

from repro.comm import (CommPhase, active_senders_per_node,
                        queue_traversal_steps, batched_queue_traversal_steps,
                        sum_by_pairs)
from repro.comm import primitives
from repro.core import phase_cost, phase_cost_many, model_ladder, model_ladder_many
from repro.core.topology import TorusTopology
from repro.net import (blue_waters_machine, tpu_v5e_machine, simulate,
                       simulate_phase, simulate_many)


# ------------------------------------------------- vectorized routing -------
TORI = [((4, 4), True), ((4, 4), False), ((3, 4, 5), True), ((3, 4, 5), False),
        ((8,), True), ((2, 1, 3), True)]


@pytest.mark.parametrize("dims,wrap", TORI)
def test_route_link_ids_matches_scalar(dims, wrap):
    """Vectorized per-dimension segment expansion == per-message route_links."""
    t = TorusTopology(dims, wrap=wrap)
    rng = np.random.default_rng(0)
    n = 150
    src = rng.integers(0, t.size, n)
    dst = rng.integers(0, t.size, n)
    size = rng.integers(1, 1000, n).astype(float)
    ref: dict = {}
    for s, d, z in zip(src, dst, size):
        for link in t.route_links(int(s), int(d)):
            ref[link] = ref.get(link, 0.0) + float(z)
    got = t.accumulate_link_bytes(src, dst, size)
    assert set(got) == set(ref)
    for k in ref:
        assert got[k] == pytest.approx(ref[k])


@pytest.mark.parametrize("dims,wrap", TORI)
def test_route_link_bytes_conservation(dims, wrap):
    """Per-link byte sum == sum over messages of size * hops."""
    t = TorusTopology(dims, wrap=wrap)
    rng = np.random.default_rng(1)
    n = 200
    src = rng.integers(0, t.size, n)
    dst = rng.integers(0, t.size, n)
    size = rng.integers(1, 1000, n).astype(float)
    dense = t.link_bytes(src, dst, size)
    assert dense.size == t.link_slots
    expect = float((size * t.hops(src, dst)).sum())
    assert dense.sum() == pytest.approx(expect)
    # per-message emitted-link counts equal hop counts
    midx, _ = t.route_link_ids(src, dst)
    assert np.array_equal(np.bincount(midx, minlength=n), t.hops(src, dst))


# ------------------------------------------------- batched queue walk -------
def test_batched_queue_steps_matches_per_process():
    rng = np.random.default_rng(2)
    for _ in range(20):
        counts = rng.integers(1, 50, rng.integers(1, 8))
        bounds = np.concatenate([[0], np.cumsum(counts)])
        posted = np.concatenate([rng.permutation(c) for c in counts])
        arrive = np.concatenate([rng.permutation(c) for c in counts])
        got = batched_queue_traversal_steps(posted, arrive, bounds)
        for r, c in enumerate(counts):
            s, e = bounds[r], bounds[r + 1]
            ref = queue_traversal_steps(posted[s:e], arrive[s:e])
            assert np.array_equal(got[s:e], ref)


def test_batched_queue_steps_extremes():
    n = 64
    b = [0, n]
    same = batched_queue_traversal_steps(np.arange(n), np.arange(n), b)
    assert same.sum() == n                       # every arrival matches head
    rev = batched_queue_traversal_steps(np.arange(n)[::-1], np.arange(n), b)
    assert rev.sum() == n * (n + 1) // 2         # full queue walk each time
    assert batched_queue_traversal_steps([], [], [0]).size == 0


def test_phase_queue_steps_matches_reference():
    """CommPhase.queue_steps == per-receiver scalar Fenwick, mixed defaults."""
    m = blue_waters_machine((2, 1, 1))
    rng = np.random.default_rng(3)
    n = 300
    src = rng.integers(0, 16, n)
    dst = 32 + rng.integers(0, 12, n)
    size = rng.integers(8, 1 << 16, n).astype(float)
    phase = CommPhase.build(m, src, dst, size)
    receivers = np.unique(dst)
    # custom arrival for half the receivers, custom posting for a third
    arrival = {int(p): rng.permutation(np.nonzero(dst == p)[0])
               for p in receivers[::2]}
    posted = {int(p): np.nonzero(dst == p)[0][::-1] for p in receivers[::3]}
    got = phase.queue_steps(posted, arrival)
    for p in receivers:
        ids = np.nonzero(dst == p)[0]
        local = {mid: k for k, mid in enumerate(ids)}
        po = (np.asarray([local[x] for x in posted[int(p)]])
              if int(p) in posted else np.arange(ids.size))
        ao = (np.asarray([local[x] for x in arrival[int(p)]])
              if int(p) in arrival else np.arange(ids.size))
        assert got[p] == queue_traversal_steps(po, ao).sum()
    assert got.sum() == got[receivers].sum()     # silent procs pay nothing


def test_queue_steps_rejects_foreign_message_index():
    """An order entry naming a message not destined to that receiver is a
    silent-corruption hazard — it must fail loudly (the pre-refactor dict
    lookup raised KeyError)."""
    m = tpu_v5e_machine((4, 4))
    ph = CommPhase.build(m, [0, 0, 1], [5, 5, 6], [1e4, 1e4, 1e4])
    with pytest.raises(ValueError):
        ph.queue_steps(arrival_order={5: np.array([0, 2])})   # msg 2 -> proc 6
    with pytest.raises(ValueError):
        ph.queue_steps(recv_post_order={5: np.array([0])})    # wrong length
    with pytest.raises(ValueError):
        ph.queue_steps(arrival_order={5: np.array([0, 0])})   # duplicate index


def test_link_contention_source_ids_beyond_torus_size():
    """torus_over_procs machines can have source ids >= torus.size; the
    per-(link, source) grouping must not bleed source bits into the link key.
    Golden value from the pre-refactor scalar dict implementation."""
    mt = tpu_v5e_machine((4, 4))
    rng = np.random.default_rng(11)
    n = 400
    src = rng.integers(0, 256, n)
    dst = (src + rng.integers(1, 256, n)) % 256
    size = rng.integers(8, 1 << 16, n).astype(float)
    r = simulate_phase(mt, src, dst, size)
    assert r.max_link_bytes == pytest.approx(1124767.0, rel=1e-12)
    assert r.contention == pytest.approx(5.623835e-05, rel=1e-10)


def test_default_order_queue_is_linear():
    m = blue_waters_machine((2, 1, 1))
    src = np.zeros(40, dtype=np.int64)
    dst = np.full(40, 32)
    phase = CommPhase.build(m, src, dst, np.full(40, 1e4))
    assert phase.queue_steps().sum() == 40


# ------------------------------------------------- active senders -----------
def test_active_senders_matches_dict_of_sets():
    rng = np.random.default_rng(4)
    n = 500
    src = rng.integers(0, 128, n)
    node = src // 16
    is_net = rng.random(n) < 0.7
    got = active_senders_per_node(src, node, is_net)
    active: dict = {}
    for p, nd, net in zip(src, node, is_net):
        if net:
            active.setdefault(int(nd), set()).add(int(p))
    for i in range(n):
        expect = len(active.get(int(node[i]), ())) if is_net[i] else 1
        assert got[i] == max(expect, 1)


def test_active_senders_no_net():
    assert (active_senders_per_node([1, 2], [0, 0], [False, False]) == 1).all()
    assert active_senders_per_node([], [], []).size == 0


# ------------------------------------------------- pair sums ----------------
def _pairs_input(case):
    """``(a, b, w)`` with many repeated pairs and weights over many decades
    (another order of addition would change the sums' low bits).
    ``at_threshold`` and ``above_threshold`` put the packed key range at the
    densest-path limit and one key past it; ``wide`` is far past it."""
    rng = np.random.default_rng(8)
    if case == "empty":
        z = np.zeros(0, dtype=np.int64)
        return z, z, np.zeros(0)
    n = 400
    hi = {"dense": n // 3, "at_threshold": primitives._DENSE_KEYS_PER_PAIR * n,
          "above_threshold": primitives._DENSE_KEYS_PER_PAIR * n + 1,
          "wide": 1000 * n}[case]
    key = rng.integers(0, hi, n)
    key[:2] = hi - 1, 9                 # the range's top; 9 fixes b's span
    w = rng.random(n) * 10.0 ** rng.integers(-3, 9, n)
    w[::7] = 0.0
    return key // 10, key % 10, w


@pytest.mark.parametrize("path", ["dense", "sorted", "chosen"])
@pytest.mark.parametrize("case", ["empty", "dense", "at_threshold",
                                  "above_threshold", "wide"])
def test_sum_by_pairs_paths_are_bit_identical(monkeypatch, case, path):
    """Grouping by direct index and by sorting give the same pairs and the
    same sums, bit for bit; the chosen path follows the key range."""
    a, b, w = _pairs_input(case)
    want = None
    if a.size:
        span = np.int64(b.max()) + 1
        uk, inv = np.unique(a * span + b, return_inverse=True)
        want = (uk // span, uk % span, np.bincount(inv, weights=w))
    sorts = []
    if path == "dense":
        monkeypatch.setattr(primitives, "_DENSE_KEYS_PER_PAIR", 10 ** 9)
    elif path == "sorted":
        monkeypatch.setattr(primitives, "_DENSE_KEYS_PER_PAIR", 0)
    else:
        unique = np.unique

        def counted(*args, **kw):
            sorts.append(args)
            return unique(*args, **kw)
        monkeypatch.setattr(np, "unique", counted)
    got = sum_by_pairs(a, b, w)
    if want is None:
        assert [g.size for g in got] == [0, 0, 0]
        assert [g.dtype for g in got] == [np.int64, np.int64, np.float64]
        return
    for g, x in zip(got, want):
        assert g.dtype == x.dtype
        np.testing.assert_array_equal(g, x)
    np.testing.assert_array_equal(got[2].view(np.uint64),
                                  want[2].view(np.uint64))
    if path == "chosen":
        assert bool(sorts) == (case in ("above_threshold", "wide"))


# ------------------------------------------------- CommPhase caching --------
def test_comm_phase_caches_machine_views():
    m = blue_waters_machine((2, 2, 1))
    rng = np.random.default_rng(5)
    n = 200
    src = rng.integers(0, m.n_procs, n)
    dst = (src + rng.integers(1, m.n_procs, n)) % m.n_procs
    size = rng.integers(8, 1 << 18, n).astype(float)
    ph = CommPhase.build(m, src, dst, size)
    assert np.array_equal(ph.loc, m.locality(src, dst))
    assert np.array_equal(ph.send_node, m.node_of(src))
    assert np.array_equal(ph.torus_src, m.torus_node_of(src))
    assert np.array_equal(ph.proto, m.params.protocol_of(size))
    assert ph.n_procs == int(max(src.max(), dst.max())) + 1
    assert ph.total_bytes == pytest.approx(size.sum())
    assert ph.net_bytes == pytest.approx(size[ph.is_net].sum())


def test_comm_phase_empty():
    m = blue_waters_machine((2, 1, 1))
    ph = CommPhase.build(m, [], [], [])
    assert ph.n_msgs == 0 and ph.n_procs == 0
    assert simulate(ph).time == 0.0


# ---------------------------------------- model/simulator agreement ---------
def _random_phase(machine, n, seed):
    rng = np.random.default_rng(seed)
    src = rng.integers(0, machine.n_procs, n)
    dst = (src + rng.integers(1, machine.n_procs, n)) % machine.n_procs
    size = rng.integers(8, 1 << 18, n).astype(float)
    return src, dst, size


def test_phase_cost_many_matches_phase_cost():
    m = blue_waters_machine((2, 2, 2))
    src, dst, size = _random_phase(m, 300, 6)
    ph = CommPhase.build(m, src, dst, size)
    batched = model_ladder_many([ph])[0]
    arrays = model_ladder(m.params, src, dst, size, m.locality(src, dst),
                          node_of=m.node_of, n_torus_nodes=m.torus.size,
                          torus_ndim=m.torus.ndim,
                          procs_per_torus_node=m.procs_per_torus_node,
                          n_procs=ph.n_procs)
    for lvl, cb in arrays.items():
        assert batched[lvl].total == pytest.approx(cb.total)
        assert batched[lvl].transport == pytest.approx(cb.transport)
        assert batched[lvl].queue == pytest.approx(cb.queue)
        assert batched[lvl].contention == pytest.approx(cb.contention)
    assert len(phase_cost_many([ph, ph], level="queue")) == 2


def test_phase_cost_phase_params_override_recomputes_ppn():
    """An override params table that reclassifies localities must not reuse
    active-sender counts cached under the machine's network_locality."""
    from repro.core import phase_cost_phase
    m = blue_waters_machine((2, 2, 1))           # network_locality = 2
    src, dst, size = _random_phase(m, 200, 10)
    ph = CommPhase.build(m, src, dst, size)
    override = m.params.replace(network_locality=1)
    got = phase_cost_phase(ph, level="maxrate", params=override)
    from repro.comm import active_senders_per_node
    ppn = active_senders_per_node(src, m.node_of(src),
                                  ph.loc >= override.network_locality)
    want = phase_cost(override, src, dst, size, ph.loc,
                      n_torus_nodes=m.torus.size, torus_ndim=m.torus.ndim,
                      procs_per_torus_node=m.procs_per_torus_node,
                      n_procs=ph.n_procs, level="maxrate", active_ppn=ppn)
    assert got.total == pytest.approx(want.total)
    # the reclassification genuinely produces different active-sender counts
    # (totals may still coincide when RN never binds, so compare the arrays)
    assert not np.array_equal(ppn, ph.active_ppn)


def test_simulate_many_matches_simulate_phase():
    m = tpu_v5e_machine((4, 4))
    phases, arrivals, singles = [], [], []
    for seed in (7, 8, 9):
        src, dst, size = _random_phase(m, 120, seed)
        ph = CommPhase.build(m, src, dst, size)
        rng = np.random.default_rng(seed)
        ao = ph.random_arrival_order(rng)
        phases.append(ph)
        arrivals.append(ao)
        singles.append(simulate_phase(m, src, dst, size, arrival_order=ao))
    for got, want in zip(simulate_many(phases, arrival_orders=arrivals), singles):
        assert got.time == pytest.approx(want.time)
        assert got.queue == pytest.approx(want.queue)
        assert got.contention == pytest.approx(want.contention)


# ------------------------------------------------- golden regression --------
# Values captured from the pre-refactor (seed) scalar simulator on the same
# deterministic phase: a seeded random pattern on a 4x4 wrapped v5e torus,
# with reversed posting and random arrival.  Guards the acceptance criterion
# that the vectorized engine reproduces the old PhaseResult exactly.
def _tpu_golden_phase():
    mt = tpu_v5e_machine((4, 4))
    rng = np.random.default_rng(3)
    src = rng.integers(0, 16, 60)
    dst = (src + rng.integers(1, 16, 60)) % 16
    size = rng.integers(8, 1 << 16, 60).astype(float)
    arrival = {int(p): rng.permutation(np.nonzero(dst == p)[0])
               for p in np.unique(dst)}
    post = {int(p): np.nonzero(dst == p)[0][::-1] for p in np.unique(dst)}
    return mt, src, dst, size, post, arrival


def test_simulator_golden_tpu_custom_orders():
    mt, src, dst, size, post, arrival = _tpu_golden_phase()
    r = simulate_phase(mt, src, dst, size,
                       recv_post_order=post, arrival_order=arrival)
    assert r.time == pytest.approx(2.335131111111111e-05, rel=1e-12)
    assert r.transport == pytest.approx(1.4821111111111112e-05, rel=1e-12)
    assert r.queue == pytest.approx(1.7e-07, rel=1e-12)
    assert r.contention == pytest.approx(8.3602e-06, rel=1e-12)
    assert r.max_link_bytes == 167204.0
    assert r.total_net_bytes == 1900397.0
    assert int(r.per_proc_queue_steps.sum()) == 105
    assert int(r.per_proc_queue_steps.max()) == 17


def test_simulator_golden_tpu_default_orders():
    mt, src, dst, size, _, _ = _tpu_golden_phase()
    r = simulate_phase(mt, src, dst, size)
    assert r.time == pytest.approx(2.3241311111111113e-05, rel=1e-12)
    assert int(r.per_proc_queue_steps.sum()) == 60
    assert int(r.per_proc_queue_steps.max()) == 6


@given(st.integers(1, 120), st.integers(0, 10**6))
@settings(max_examples=20, deadline=None)
def test_property_batched_queue_bounds(n, seed):
    """Any order costs between n (head hits) and n(n+1)/2 (worst case)."""
    rng = np.random.default_rng(seed)
    posted = rng.permutation(n)
    arrive = rng.permutation(n)
    total = batched_queue_traversal_steps(posted, arrive, [0, n]).sum()
    assert n <= total <= n * (n + 1) // 2
