"""Stack-vs-loop equivalence for the PhaseStack sweep engine.

The acceptance contract of the stacked fast path is *bit-identity*: for any
sweep of phases bound to one machine, the segmented passes must reproduce
the per-phase ``phase_cost_phase`` / ``simulate`` results exactly (numpy
backend), including empty phases, single-message phases and custom receive
orders.  The optional JAX/Pallas backends are held to allclose parity
(they run float32).
"""

import numpy as np
import pytest

from _hypothesis_compat import given, settings, st

from repro.comm import (CommPhase, PhaseStack, STRATEGIES, best_strategy,
                        grouped_queue_steps, rewrite)
from repro.core import (MODEL_LEVELS, model_ladder_many, phase_cost_many,
                        phase_cost_phase, sequence_cost)
from repro.net import (blue_waters_machine, frontier_machine, lassen_machine,
                       tpu_v5e_machine, simulate, simulate_many,
                       simulate_sequence)
from repro.sparse import (RowPartition, build_hierarchy, elasticity_like_3d,
                          spmv_comm_pattern, stack_patterns)

BW = blue_waters_machine((2, 2, 2))
TPU = tpu_v5e_machine((4, 4))
# the heterogeneous presets ride every bit-identity contract too
LASSEN = lassen_machine((2, 2, 2))
FRONTIER = frontier_machine((2, 2, 1))
MACHINES = [BW, TPU, LASSEN, FRONTIER]


def _random_phase(machine, n, seed, n_procs=None):
    rng = np.random.default_rng(seed)
    P = n_procs or machine.n_procs
    if n == 0:
        return CommPhase.build(machine, [], [], [], n_procs=P)
    src = rng.integers(0, P, n)
    dst = (src + rng.integers(1, P, n)) % P
    size = rng.integers(8, 1 << 18, n).astype(float)
    return CommPhase.build(machine, src, dst, size, n_procs=P)


def _sweep(machine, seed=0):
    """A ragged sweep: empty, single-message, small and message-heavy phases."""
    return [_random_phase(machine, n, seed + i)
            for i, n in enumerate((0, 1, 40, 300, 800, 2))]


def _assert_results_equal(got, want):
    for g, w in zip(got, want):
        assert g.time == w.time
        assert g.transport == w.transport
        assert g.queue == w.queue
        assert g.contention == w.contention
        assert g.max_link_bytes == w.max_link_bytes
        assert g.total_net_bytes == w.total_net_bytes
        assert np.array_equal(g.per_proc_transport, w.per_proc_transport)
        assert np.array_equal(g.per_proc_queue_steps, w.per_proc_queue_steps)


# ------------------------------------------------------ construction --------
def test_build_concatenates_cached_arrays():
    phases = _sweep(BW)
    stack = PhaseStack.build(phases)
    assert stack.n_phases == len(phases)
    assert stack.total_msgs == sum(ph.n_msgs for ph in phases)
    for i, ph in enumerate(phases):
        s = slice(stack.offsets[i], stack.offsets[i + 1])
        assert np.array_equal(stack.src[s], ph.src)
        assert np.array_equal(stack.loc[s], ph.loc)
        assert np.array_equal(stack.active_ppn[s], ph.active_ppn)
        assert (stack.phase_id[s] == i).all()
        assert stack.n_procs[i] == ph.n_procs


def test_build_rejects_mixed_machines():
    with pytest.raises(ValueError, match="mixed machines"):
        PhaseStack.build([_random_phase(BW, 10, 0), _random_phase(TPU, 10, 0)])


def test_build_rejects_unbound_patterns():
    from repro.sparse import CommPattern
    cp = CommPattern(np.array([0]), np.array([1]), np.array([8.0]), 2)
    with pytest.raises(TypeError, match="bound CommPhase"):
        PhaseStack.build([cp, cp])


def test_empty_stack():
    stack = PhaseStack.build([])
    assert stack.n_phases == 0 and stack.total_msgs == 0
    t, q, b = stack.cost_arrays()
    assert t.size == q.size == b.size == 0
    assert phase_cost_many(stack) == []
    assert simulate_many(stack) == []


# ------------------------------------------------- model-side identity ------
@pytest.mark.parametrize("machine", MACHINES, ids=lambda m: m.name)
@pytest.mark.parametrize("level", MODEL_LEVELS)
def test_phase_cost_many_bit_identical(machine, level):
    phases = _sweep(machine)
    got = phase_cost_many(phases, level=level)
    want = [phase_cost_phase(ph, level=level) for ph in phases]
    assert got == want              # CostBreakdown is a frozen dataclass: ==


def test_phase_cost_many_accepts_a_stack():
    phases = _sweep(BW)
    stack = PhaseStack.build(phases)
    assert phase_cost_many(stack) == phase_cost_many(phases)
    assert model_ladder_many(stack) == model_ladder_many(phases)


def test_model_ladder_many_bit_identical():
    phases = _sweep(BW, seed=3)
    got = model_ladder_many(phases)
    want = [{lvl: phase_cost_phase(ph, level=lvl) for lvl in MODEL_LEVELS}
            for ph in phases]
    assert got == want


def test_params_override_reclassifies_localities():
    """An override table with a different network locality must recompute the
    active-sender counts per phase, exactly like phase_cost_phase does."""
    phases = _sweep(BW, seed=5)
    override = BW.params.replace(network_locality=1)
    for level in ("maxrate", "contention"):
        got = phase_cost_many(phases, level=level, params=override)
        want = [phase_cost_phase(ph, level=level, params=override)
                for ph in phases]
        assert got == want


def test_mixed_machine_sweep_falls_back_to_loop():
    phases = [_random_phase(BW, 30, 0), _random_phase(TPU, 30, 0)]
    got = phase_cost_many(phases)
    want = [phase_cost_phase(ph) for ph in phases]
    assert got == want


def test_unknown_level_raises():
    with pytest.raises(ValueError, match="unknown model level"):
        phase_cost_many(_sweep(BW), level="psychic")


# --------------------------------------------------- sim-side identity ------
@pytest.mark.parametrize("machine", MACHINES, ids=lambda m: m.name)
def test_simulate_many_bit_identical_default_orders(machine):
    phases = _sweep(machine, seed=7)
    _assert_results_equal(simulate_many(phases),
                          [simulate(ph) for ph in phases])


@pytest.mark.parametrize("machine", MACHINES, ids=lambda m: m.name)
def test_simulate_many_bit_identical_custom_orders(machine):
    phases = _sweep(machine, seed=9)
    rng = np.random.default_rng(0)
    arrivals = [ph.random_arrival_order(rng) for ph in phases]
    posts = []
    for ph in phases:                    # reversed posting, every 2nd receiver
        posts.append({int(p): np.nonzero(ph.dst == p)[0][::-1]
                      for p in np.unique(ph.dst)[::2]})
    got = simulate_many(phases, recv_post_orders=posts,
                        arrival_orders=arrivals)
    want = [simulate(ph, recv_post_order=po, arrival_order=ao)
            for ph, po, ao in zip(phases, posts, arrivals)]
    _assert_results_equal(got, want)


def test_simulate_many_noise_stream_matches_loop():
    """The stacked path must consume the shared rng exactly like the loop —
    including skipping the draw for empty phases, which the per-phase early
    return never reaches."""
    phases = [_random_phase(BW, n, 11 + n) for n in (50, 0, 80, 120)]
    got = simulate_many(phases, rng=np.random.default_rng(5), noise=0.1)
    rng = np.random.default_rng(5)
    want = [simulate(ph, rng=rng, noise=0.1) for ph in phases]
    assert [r.time for r in got] == [r.time for r in want]


def test_simulate_requires_rng_for_noise():
    ph = _random_phase(BW, 10, 0)
    with pytest.raises(ValueError, match="noise > 0 needs an explicit rng"):
        simulate(ph, noise=0.1)


def test_simulate_many_default_seed_documented():
    """noise without an rng seeds default_rng(0) once for the whole sweep."""
    phases = [_random_phase(BW, 50, 21), _random_phase(BW, 60, 22)]
    a = simulate_many(phases, noise=0.05)
    b = simulate_many(phases, rng=np.random.default_rng(0), noise=0.05)
    assert [r.time for r in a] == [r.time for r in b]


def test_stacked_queue_rejects_foreign_and_duplicate_ids():
    phases = [_random_phase(BW, 20, 1), _random_phase(BW, 60, 2)]
    receivers = np.unique(phases[1].dst)
    p, q = int(receivers[0]), int(receivers[1])    # both have messages
    ids_p = np.nonzero(phases[1].dst == p)[0]
    ids_q = np.nonzero(phases[1].dst == q)[0]
    # p's messages offered as q's order: wrong receiver (or wrong length)
    with pytest.raises(ValueError, match="permutation"):
        simulate_many(phases, arrival_orders=[None, {q: ids_p, p: ids_p}])
    if ids_q.size >= 2:
        dup = ids_q.copy()
        dup[0] = dup[1]
        with pytest.raises(ValueError, match="permutation"):
            simulate_many(phases, arrival_orders=[None, {q: dup}])


def test_grouped_queue_steps_matches_phase_queue_steps():
    """The shared grouped primitive == CommPhase.queue_steps, slot for slot."""
    ph = _random_phase(BW, 200, 13)
    ao = ph.random_arrival_order(np.random.default_rng(1))
    got = grouped_queue_steps(ph.dst, ph.n_procs, arrival_order=ao)
    assert np.array_equal(got, ph.queue_steps(arrival_order=ao))


def test_flat_and_dict_orders_agree():
    """random_arrival_flat and random_arrival_order share the rng stream and
    the flat (slots, lens, ids) form prices identically to the dict form."""
    ph = _random_phase(BW, 250, 15)
    flat = ph.random_arrival_flat(np.random.default_rng(2))
    dct = ph.random_arrival_order(np.random.default_rng(2))
    slots, lens, ids = flat
    assert np.array_equal(np.sort(slots), np.asarray(sorted(dct)))
    assert np.array_equal(
        ph.queue_steps(arrival_order=flat),
        ph.queue_steps(arrival_order=dct))
    _assert_results_equal(
        [simulate(ph, arrival_order=flat)],
        [simulate(ph, arrival_order=dct)])


# ------------------------------------------------- strategy sweep -----------
def test_best_strategy_many_mixed_machines_falls_back():
    """A candidate set spanning machines can't share one arena — it must
    fall back to the loop, element-wise identical to per-pattern calls."""
    from repro.comm import best_strategy_many
    phases = [_random_phase(BW, 120, 41), _random_phase(TPU, 120, 42)]
    got = best_strategy_many(phases, seed=0)
    want = [best_strategy(ph, seed=0) for ph in phases]
    assert [v.model for v in got] == [v.model for v in want]
    assert [v.sim for v in got] == [v.sim for v in want]


def test_best_strategy_matches_per_phase_loop():
    """One stacked sweep over all strategies == the per-strategy loop."""
    phase = _random_phase(BW, 400, 17)
    v = best_strategy(phase, seed=0)
    model, sim = {}, {}
    for name in STRATEGIES:
        plan = rewrite(phase, name)
        rng = np.random.default_rng(0)
        arrs = [p.random_arrival_order(rng) for p in plan.phases]
        model[name] = sum(phase_cost_phase(p).total for p in plan.phases)
        sim[name] = sum(simulate(p, arrival_order=a).time
                        for p, a in zip(plan.phases, arrs))
    assert v.model == model
    assert v.sim == sim


def test_sequence_cost_rides_the_stack():
    plan = rewrite(_random_phase(BW, 300, 19), "three_step")
    seq = sequence_cost(plan.phases)
    want = [phase_cost_phase(p) for p in plan.phases]
    assert seq.total == sum(p.total for p in want)
    sim = simulate_sequence(plan.phases)
    assert sim.time == sum(simulate(p).time for p in plan.phases)


# ------------------------------------------------- sparse sweep entry -------
def test_stack_patterns_amg_hierarchy():
    A = elasticity_like_3d(8)
    levels = build_hierarchy(A)
    pats = []
    for lvl in levels:
        part = RowPartition.balanced(lvl.A.n_rows, max(lvl.A.n_rows // 2, 2))
        cp = spmv_comm_pattern(lvl.A, part)
        if cp.n_msgs:
            pats.append(cp)
    stack = stack_patterns(pats, BW)
    assert stack.n_phases == len(pats)
    got = phase_cost_many(stack)
    want = [phase_cost_phase(cp.bind(BW)) for cp in pats]
    assert got == want


# ------------------------------------------------- backend parity -----------
from repro.kernels.comm_stack import have_jax  # numpy-safe import

needs_jax = pytest.mark.skipif(not have_jax(), reason="jax not installed")


@needs_jax
@pytest.mark.parametrize("backend", ["jax", "pallas"])
def test_backend_parity_cost_arrays(backend):
    stack = PhaseStack.build(_sweep(BW, seed=23))
    t0, q0, b0 = stack.cost_arrays()
    t1, q1, b1 = stack.cost_arrays(backend=backend)
    np.testing.assert_allclose(t1, t0, rtol=1e-4)
    np.testing.assert_array_equal(q1, q0)     # counts stay numpy-exact
    np.testing.assert_array_equal(b1, b0)     # net bytes stay numpy-exact


@needs_jax
@pytest.mark.parametrize("backend", ["jax", "pallas"])
def test_backend_parity_link_contention(backend):
    stack = PhaseStack.build(_sweep(BW, seed=29))
    m0, n0 = stack.link_contention_many()
    m1, n1 = stack.link_contention_many(backend=backend)
    np.testing.assert_allclose(m1, m0, rtol=1e-4)
    np.testing.assert_array_equal(n1, n0)


def test_unknown_backend_raises():
    stack = PhaseStack.build(_sweep(BW, seed=31))
    with pytest.raises(ValueError, match="unknown stack backend"):
        stack.cost_arrays(backend="cuda")


def test_backend_error_is_eager_and_lists_allowed_values():
    """Validation happens before any reduction and names every legal value
    plus where the bad name came from (the backend argument)."""
    stack = PhaseStack.build(_sweep(BW, seed=33))
    with pytest.raises(ValueError, match=r"numpy.*jax.*pallas"):
        stack.cost_arrays(backend="rocm")
    with pytest.raises(ValueError, match="backend argument"):
        stack.sim_arrays(backend="rocm")
    with pytest.raises(ValueError, match="unknown stack backend"):
        phase_cost_many(stack, backend="rocm")
    with pytest.raises(ValueError, match="unknown stack backend"):
        stack.link_contention_many(backend="rocm")


def test_env_backend_validated_eagerly():
    """The retired ``'auto'`` is rejected before any pass runs, and the
    error names the three backends."""
    stack = PhaseStack.build(_sweep(BW, seed=35))
    allowed = r"\('numpy', 'jax', 'pallas'\)"
    with pytest.raises(ValueError, match=allowed):
        stack.cost_arrays(backend="auto")
    with pytest.raises(ValueError, match=allowed):
        simulate_many(stack, backend="auto")


# ------------------------------------------------- kernels backend list ---
from repro.kernels import comm_stack as _cs  # numpy-safe import


def test_stack_backends_mirror_kernels():
    """The eagerly-validated tuple (kept kernels-import-free in stack.py)
    must never drift from the kernels module's own backend list, and both
    are exactly the three backends."""
    from repro.comm import STACK_BACKENDS
    assert STACK_BACKENDS == _cs.BACKENDS == ("numpy", "jax", "pallas")


@needs_jax
@pytest.mark.parametrize("op", ["sum", "max"])
def test_pallas_handles_sizes_beyond_retired_one_hot_limit(op):
    """Sizes that the retired one-hot kernel had to reroute to jax now run
    directly on the fused pallas kernel and match numpy."""
    fn = _cs.segment_sum if op == "sum" else _cs.segment_max
    rng = np.random.default_rng(0)
    n_seg = (1 << 24) // _cs._CHUNK + 1   # over the one-hot kernel's limit
    vals = rng.random(4 * _cs._CHUNK)
    ids = rng.integers(0, n_seg, vals.size)
    want = fn(vals, ids, n_seg, backend="numpy")
    got = fn(vals, ids, n_seg, backend="pallas")
    np.testing.assert_allclose(got, want, rtol=1e-4)


@needs_jax
def test_env_backend_cannot_poison_numpy_caches():
    """Pricing an arena on a device backend first must not leak float32
    results into its bit-exact numpy caches (they pin backend='numpy')."""
    phases = _sweep(BW, seed=37)
    want = phase_cost_many(PhaseStack.build(phases))      # clean numpy run
    stack = PhaseStack.build(phases)
    phase_cost_many(stack, backend="jax")
    simulate_many(stack, backend="jax")
    assert phase_cost_many(stack, backend="numpy") == want
    assert phase_cost_many(stack) == want                 # cache stayed clean
    _assert_results_equal(simulate_many(stack),
                          [simulate(ph) for ph in phases])


# ------------------------------------------------- property test ------------
@given(st.integers(0, 500), st.integers(0, 500), st.integers(0, 10**6))
@settings(max_examples=15, deadline=None)
def test_property_stack_matches_loop(n1, n2, seed):
    """Any two-phase sweep is priced and simulated bit-identically."""
    rng = np.random.default_rng(seed)
    phases = [_random_phase(BW, n1, int(rng.integers(1 << 30))),
              _random_phase(BW, n2, int(rng.integers(1 << 30)))]
    got = phase_cost_many(phases)
    want = [phase_cost_phase(ph) for ph in phases]
    assert got == want
    _assert_results_equal(simulate_many(phases),
                          [simulate(ph) for ph in phases])
