"""Parity and policy tests for the fused device kernels (PR 6 tentpole).

Three surfaces:

* ``fused_segment_reduce`` / ``segment_sum`` / ``segment_max`` — the tiled
  scatter-accumulate bincount kernel that replaced the one-hot matmul, on
  ragged / empty / single-message inputs, against the numpy reference.
* ``queue_walk`` — the device queue walk (dominance tiles; the numpy
  walk past ``TILE_MAX``), bit-equal to
  :func:`repro.comm.primitives.batched_queue_traversal_steps` (the walk is
  integer-exact, so every backend must agree exactly).
* ``backend=None`` — numpy at every entry point that takes a backend,
  and ``'auto'`` rejected at each of them.

Property tests ride the optional-hypothesis shim and skip cleanly when
hypothesis is absent; the deterministic parity tests always run.
"""
import dataclasses

import numpy as np
import pytest

from _hypothesis_compat import HAVE_HYPOTHESIS, given, settings, st
from repro.comm import CommPhase, PhaseStack, obs
from repro.comm.health import get_health
from repro.comm.primitives import (batched_queue_traversal_steps,
                                   grouped_queue_steps)
from repro.kernels import comm_stack as cs
from repro.net import blue_waters_machine

needs_jax = pytest.mark.skipif(not cs.have_jax(), reason="jax not installed")

DEVICE_BACKENDS = ("jax", "pallas")


def _random_segments(rng, n, n_seg):
    # non-negative, like the byte counts / times the stacked reductions see
    # (segment_max documents 0.0 for empty segments under that contract)
    vals = np.abs(rng.standard_normal(n)) * 10.0
    ids = rng.integers(0, n_seg, n) if n else np.zeros(0, dtype=np.int64)
    return vals, ids


def _np_sum(vals, ids, n_seg):
    return np.bincount(ids, weights=vals, minlength=n_seg).astype(np.float64)


def _np_max(vals, ids, n_seg):
    out = np.zeros(n_seg, dtype=np.float64)
    if len(vals):
        np.maximum.at(out, ids, vals)
    return out


# ------------------------------------------------ fused scatter reduce ------
@needs_jax
@pytest.mark.parametrize("n,n_seg", [(0, 5), (1, 1), (7, 3), (513, 2),
                                     (2000, 300), (5000, 1)])
def test_fused_segment_reduce_matches_numpy(n, n_seg):
    rng = np.random.default_rng(n * 31 + n_seg)
    vals, ids = _random_segments(rng, n, n_seg)
    sums, maxs = cs.fused_segment_reduce(vals, ids, n_seg)
    np.testing.assert_allclose(sums, _np_sum(vals, ids, n_seg), rtol=1e-5,
                               atol=1e-5)
    np.testing.assert_allclose(maxs, _np_max(vals, ids, n_seg), rtol=1e-5,
                               atol=1e-5)


@needs_jax
@pytest.mark.parametrize("backend", DEVICE_BACKENDS)
def test_segment_ops_device_parity(backend):
    rng = np.random.default_rng(7)
    vals, ids = _random_segments(rng, 1234, 77)
    np.testing.assert_allclose(
        cs.segment_sum(vals, ids, 77, backend=backend),
        cs.segment_sum(vals, ids, 77, backend="numpy"), rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(
        cs.segment_max(vals, ids, 77, backend=backend),
        cs.segment_max(vals, ids, 77, backend="numpy"), rtol=1e-5, atol=1e-5)


@needs_jax
def test_fused_reduce_empty_segment_gets_zero_not_neg_inf():
    vals = np.array([3.0])
    ids = np.array([2])
    sums, maxs = cs.fused_segment_reduce(vals, ids, 4)
    np.testing.assert_allclose(sums, [0.0, 0.0, 3.0, 0.0])
    np.testing.assert_allclose(maxs, [0.0, 0.0, 3.0, 0.0])


def _one_heavy_segment(rng):
    # one segment spans many message chunks
    return rng.random(3000), np.full(3000, 130), 300


def _sparse_segments(rng):
    # sorted chunks straddle tiles, and whole tiles hold no segment
    return rng.random(1500), rng.choice([0, 127, 128, 1000, 4999], 1500), 5000


def _negative_values(rng):
    # maxima are floored at 0, exactly as the numpy reference
    return -rng.random(700), rng.integers(0, 40, 700), 40


def _no_messages(rng):
    return np.zeros(0), np.zeros(0, dtype=np.int64), 9


@needs_jax
@pytest.mark.parametrize("case", [_one_heavy_segment, _sparse_segments,
                                  _negative_values, _no_messages])
def test_fused_reduce_tiled_layouts_match_numpy(case):
    vals, ids, n_seg = case(np.random.default_rng(5))
    sums, maxs = cs.fused_segment_reduce(vals, ids, n_seg)
    np.testing.assert_allclose(sums, cs.segment_sum(vals, ids, n_seg, "numpy"),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(maxs, cs.segment_max(vals, ids, n_seg, "numpy"),
                               rtol=1e-5, atol=1e-5)


@needs_jax
@settings(max_examples=25, deadline=None)
@given(st.integers(min_value=0, max_value=400),
       st.integers(min_value=1, max_value=64),
       st.integers(min_value=0, max_value=2 ** 31 - 1))
def test_property_fused_reduce_parity(n, n_seg, seed):
    rng = np.random.default_rng(seed)
    vals, ids = _random_segments(rng, n, n_seg)
    sums, maxs = cs.fused_segment_reduce(vals, ids, n_seg)
    np.testing.assert_allclose(sums, _np_sum(vals, ids, n_seg), rtol=1e-4,
                               atol=1e-4)
    np.testing.assert_allclose(maxs, _np_max(vals, ids, n_seg), rtol=1e-4,
                               atol=1e-4)


# ------------------------------------------------ device queue walk ---------
def _random_regions(rng, n_regions, max_count):
    return _regions(rng, rng.integers(0, max_count + 1, n_regions))


def _regions(rng, counts):
    """Random posted and arrival orders for regions of ``counts`` messages."""
    bounds = np.concatenate([[0], np.cumsum(counts)])
    posted, arrival = [], []
    for c in counts:
        posted.append(rng.permutation(c))
        arrival.append(rng.permutation(c))
    cat = lambda xs: (np.concatenate(xs) if xs else
                      np.zeros(0, dtype=np.int64))
    return cat(posted), cat(arrival), bounds.astype(np.int64)


#: Walk arenas: ``(n_regions, max_count)`` for random region lengths, or
#: a list of region lengths.  Lengths on every tile class boundary; a
#: class of 256 with more than 256 regions, taken in blocks of rows; one
#: region of 5000 beside short ones, its class turned and taken in blocks;
#: and one region just past ``TILE_MAX``, which sends the call to the
#: numpy walk.
_WALK_ARENAS = (
    [pytest.param((n, m), id=f"{n}-{m}")
     for n, m in [(1, 1), (3, 0), (5, 9), (40, 25), (2, 200)]]
    + [pytest.param([L] * 3 + [0, 5], id=f"class-edge-{L}")
       for L in (1, 7, 8, 9, 63, 64, 65, 255, 256, 257)]
    + [pytest.param([200] * 300, id="blocked-rows"),
       pytest.param([3, 5000, 0, 40, 9], id="turned-blocked"),
       pytest.param([3, cs.TILE_MAX + 1, 0, 40, 9], id="past-tile-max")])


@needs_jax
@pytest.mark.parametrize("backend", DEVICE_BACKENDS)
@pytest.mark.parametrize("arena", _WALK_ARENAS)
def test_queue_walk_bit_equal_to_numpy(backend, arena):
    if isinstance(arena, tuple):
        n_regions, max_count = arena
        rng = np.random.default_rng(n_regions * 1000 + max_count)
        posted, arrival, bounds = _random_regions(rng, n_regions, max_count)
    else:
        posted, arrival, bounds = _regions(np.random.default_rng(len(arena)),
                                           arena)
    want = batched_queue_traversal_steps(posted, arrival, bounds)
    got = cs.queue_walk(posted, arrival, bounds, backend=backend)
    assert not get_health().events_for(backend, "kernel.queue_walk")
    np.testing.assert_array_equal(got, want)   # integer walk: exact


@needs_jax
@pytest.mark.parametrize("counts,fenwick", [
    ([4, 0, 8, 9, 200, 1], 0),
    ([cs.TILE_MAX, 2, 0], 0),
    ([cs.TILE_MAX + 1, 2, cs.TILE_MAX, 0], 3)])
def test_queue_walk_counts_regions_per_path(counts, fenwick):
    posted, arrival, bounds = _regions(np.random.default_rng(5), counts)
    obs.reset()
    obs.enable()
    try:
        cs.queue_walk(posted, arrival, bounds, backend="jax")
    finally:
        obs.disable()
    c = obs.counters()
    obs.reset()
    nonempty = sum(n > 0 for n in counts)
    tiles = c.get("walk.tile_regions", 0)
    assert tiles + c.get("walk.fenwick_regions", 0) == nonempty
    assert c.get("walk.fenwick_regions", 0) == fenwick
    assert c.get("walk.tile_cells", 0) >= (sum(counts) if tiles else 0)


@needs_jax
@pytest.mark.parametrize("backend", DEVICE_BACKENDS)
def test_queue_walk_handles_ragged_and_empty_regions(backend):
    # hand-built layout: empty region sandwiched between ragged ones
    posted = np.array([2, 0, 1,    0,    3, 1, 0, 2])
    arrival = np.array([1, 2, 0,   0,    2, 0, 3, 1])
    bounds = np.array([0, 3, 3, 4, 8])
    want = batched_queue_traversal_steps(posted, arrival, bounds)
    got = cs.queue_walk(posted, arrival, bounds, backend=backend)
    np.testing.assert_array_equal(got, want)


@needs_jax
@settings(max_examples=20, deadline=None)
@given(st.integers(min_value=1, max_value=12),
       st.integers(min_value=0, max_value=40),
       st.integers(min_value=0, max_value=2 ** 31 - 1))
def test_property_queue_walk_parity(n_regions, max_count, seed):
    rng = np.random.default_rng(seed)
    posted, arrival, bounds = _random_regions(rng, n_regions, max_count)
    want = batched_queue_traversal_steps(posted, arrival, bounds)
    for backend in DEVICE_BACKENDS:
        got = cs.queue_walk(posted, arrival, bounds, backend=backend)
        np.testing.assert_array_equal(got, want)


@needs_jax
@pytest.mark.parametrize("backend", DEVICE_BACKENDS)
def test_grouped_queue_steps_backend_parity(backend):
    rng = np.random.default_rng(11)
    group = rng.integers(0, 9, 120)
    want = grouped_queue_steps(group, 9)                 # numpy reference
    got = grouped_queue_steps(group, 9, backend=backend)
    np.testing.assert_array_equal(got, want)


# ------------------------------------------------ one default: numpy -------
BW = blue_waters_machine((2, 2, 2))


def _bw_phases(n_phases=3, n=150, seed=0):
    rng = np.random.default_rng(seed)
    P = BW.n_procs
    out = []
    for i in range(n_phases):
        src = rng.integers(0, P, n)
        dst = (src + rng.integers(1, P, n)) % P
        size = rng.integers(1, 1 << 14, n).astype(np.float64)
        out.append(CommPhase.build(BW, src, dst, size))
    return out


def test_stack_auto_high_crossover_is_bit_identical_to_numpy():
    """``backend=None`` is numpy: byte-for-byte the numpy path, on fresh
    arenas (no cache shared between the two calls)."""
    a = PhaseStack.build(_bw_phases()).cost_arrays(backend=None)
    b = PhaseStack.build(_bw_phases()).cost_arrays(backend="numpy")
    for x, y in zip(a, b):
        np.testing.assert_array_equal(x, y)


def _segments():
    return _random_segments(np.random.default_rng(3), 4000, 700) + (700,)


def _arrivals(phases):
    rng = np.random.default_rng(9)
    return [ph.random_arrival_flat(rng) for ph in phases]


def _delta():
    from repro.comm import DeltaStack
    return DeltaStack.from_phases(_bw_phases())


def _digest(**kw):
    from repro.exec import build_schedule, delivered_digest, run_reference
    phase = _bw_phases(n_phases=1, n=60, seed=4)[0]
    sched = build_schedule(phase, "standard")
    return delivered_digest(run_reference(sched), sched, **kw)


def _best_many(**kw):
    from repro.comm.strategies import best_strategy_many
    return [(v.model, v.sim, v.model_winner, v.sim_winner, v.degraded)
            for v in best_strategy_many(_bw_phases(n_phases=2, n=80),
                                        **kw)]


def _phase_cost_many(**kw):
    from repro.core.models import phase_cost_many
    return phase_cost_many(_bw_phases(), **kw)


def _simulate_many(**kw):
    from repro.net.simulator import simulate_many
    phases = _bw_phases()
    return simulate_many(phases, arrival_orders=_arrivals(phases), **kw)


def _sim_arrays(stack_of, **kw):
    phases = _bw_phases()
    out = stack_of(phases).sim_arrays(arrival_orders=_arrivals(phases),
                                      **kw)
    return (out.transport, out.per_proc, out.qsteps, out.max_link,
            out.net_bytes)


def _queue_steps_many(**kw):
    phases = _bw_phases()
    return PhaseStack.build(phases).queue_steps_many(
        arrival_orders=_arrivals(phases), **kw)


#: every pricing entry point that takes ``backend``, called on fresh inputs
ENTRY_POINTS = {
    "kernels.segment_sum": lambda **kw: cs.segment_sum(*_segments(), **kw),
    "kernels.segment_max": lambda **kw: cs.segment_max(*_segments(), **kw),
    "kernels.queue_walk": lambda **kw: cs.queue_walk(
        *_random_regions(np.random.default_rng(2), 30, 20), **kw),
    "kernels.resolve_backend": lambda **kw: cs.resolve_backend(
        kw.get("backend")),
    "PhaseStack.cost_arrays": lambda **kw: PhaseStack.build(
        _bw_phases()).cost_arrays(node_aware=False, **kw),
    "PhaseStack.sim_arrays": lambda **kw: _sim_arrays(PhaseStack.build,
                                                      **kw),
    "PhaseStack.queue_steps_many": _queue_steps_many,
    "PhaseStack.link_contention_many": lambda **kw: PhaseStack.build(
        _bw_phases()).link_contention_many(**kw),
    "DeltaStack.cost_arrays": lambda **kw: _delta().cost_arrays(**kw),
    "DeltaStack.sim_arrays": lambda **kw: _sim_arrays(
        lambda phases: _delta(), **kw),
    "phase_cost_many": _phase_cost_many,
    "simulate_many": _simulate_many,
    "best_strategy_many": _best_many,
    "exec.delivered_digest": _digest,
}


def _assert_bit_equal(got, want):
    if isinstance(got, (tuple, list)):
        assert type(got) is type(want) and len(got) == len(want)
        for g, w in zip(got, want):
            _assert_bit_equal(g, w)
    elif isinstance(got, np.ndarray):
        assert got.dtype == want.dtype
        np.testing.assert_array_equal(got, want)
    elif dataclasses.is_dataclass(got):
        assert type(got) is type(want)
        for f in dataclasses.fields(got):
            _assert_bit_equal(getattr(got, f.name), getattr(want, f.name))
    else:
        assert got == want


@needs_jax
@pytest.mark.parametrize("entry", sorted(ENTRY_POINTS))
def test_default_backend_is_numpy(entry):
    """Omitting ``backend`` prices exactly as ``backend='numpy'`` and never
    reaches a device call site."""
    from repro.comm import obs
    call = ENTRY_POINTS[entry]
    obs.reset()
    obs.enable()
    try:
        got = call()
        device_calls = {k: v for k, v in obs.counters().items()
                        if k.startswith("device.calls.")}
    finally:
        obs.disable()
        obs.reset()
    assert device_calls == {}
    _assert_bit_equal(got, call(backend="numpy"))


@pytest.mark.parametrize("entry", sorted(ENTRY_POINTS))
def test_auto_backend_rejected(entry):
    """``'auto'`` is no backend: every entry point names the three."""
    with pytest.raises(ValueError, match=r"numpy.*jax.*pallas"):
        ENTRY_POINTS[entry](backend="auto")


# ------------------------------------------------ streaming build -----------
@pytest.mark.parametrize("chunk", [1, 2, 3, 7, 100, 1 << 16])
def test_build_streaming_bit_identical(chunk):
    phases = _bw_phases(n_phases=4, n=37, seed=5)
    mono = PhaseStack.build(phases)
    stream = PhaseStack.build_streaming(iter(phases), chunk_msgs=chunk)
    from repro.comm.stack import _ARENA_FIELDS
    for f in _ARENA_FIELDS:
        a, b = getattr(mono, f), getattr(stream, f)
        assert a.dtype == b.dtype
        np.testing.assert_array_equal(a, b)
    for x, y in zip(mono.cost_arrays(), stream.cost_arrays()):
        np.testing.assert_array_equal(x, y)


@needs_jax
@settings(max_examples=10, deadline=None)
@given(st.integers(min_value=1, max_value=50),
       st.integers(min_value=0, max_value=2 ** 31 - 1))
def test_property_build_streaming_every_chunk_size(chunk, seed):
    phases = _bw_phases(n_phases=3, n=29, seed=seed)
    mono = PhaseStack.build(phases)
    stream = PhaseStack.build_streaming(iter(phases), chunk_msgs=chunk)
    np.testing.assert_array_equal(mono.phase_id, stream.phase_id)
    np.testing.assert_array_equal(mono.src, stream.src)
    np.testing.assert_array_equal(mono.size, stream.size)


def test_build_streaming_rejects_bad_chunk_and_empty_ok():
    with pytest.raises(ValueError, match="chunk_msgs"):
        PhaseStack.build_streaming([], chunk_msgs=0)
    # an empty iterable mirrors build([]): a valid zero-message stack
    empty = PhaseStack.build_streaming([])
    assert empty.total_msgs == 0
