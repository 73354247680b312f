"""Parity and policy tests for the fused device kernels (PR 6 tentpole).

Three surfaces:

* ``fused_segment_reduce`` / ``segment_sum`` / ``segment_max`` — the tiled
  scatter-accumulate bincount kernel that replaced the one-hot matmul, on
  ragged / empty / single-message inputs, against the numpy reference.
* ``queue_walk`` — the device-resident Fenwick queue walk, bit-equal to
  :func:`repro.comm.primitives.batched_queue_traversal_steps` (the walk is
  integer-exact, so every backend must agree exactly).
* ``resolve_backend`` / ``autotune_crossover`` — the 'auto' policy: env
  override, disk cache round-trip, and the numpy-below / jax-above split.

Property tests ride the optional-hypothesis shim and skip cleanly when
hypothesis is absent; the deterministic parity tests always run.
"""
import json

import numpy as np
import pytest

from _hypothesis_compat import HAVE_HYPOTHESIS, given, settings, st
from repro.comm import CommPhase, PhaseStack
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
    counts = rng.integers(0, max_count + 1, n_regions)
    bounds = np.concatenate([[0], np.cumsum(counts)])
    posted, arrival = [], []
    for c in counts:
        posted.append(rng.permutation(c))
        arrival.append(rng.permutation(c))
    cat = lambda xs: (np.concatenate(xs) if xs else
                      np.zeros(0, dtype=np.int64))
    return cat(posted), cat(arrival), bounds.astype(np.int64)


@needs_jax
@pytest.mark.parametrize("backend", DEVICE_BACKENDS)
@pytest.mark.parametrize("n_regions,max_count", [(1, 1), (3, 0), (5, 9),
                                                 (40, 25), (2, 200)])
def test_queue_walk_bit_equal_to_numpy(backend, n_regions, max_count):
    rng = np.random.default_rng(n_regions * 1000 + max_count)
    posted, arrival, bounds = _random_regions(rng, n_regions, max_count)
    want = batched_queue_traversal_steps(posted, arrival, bounds)
    got = cs.queue_walk(posted, arrival, bounds, backend=backend)
    np.testing.assert_array_equal(got, want)   # integer walk: exact


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


# ------------------------------------------------ auto policy ---------------
@pytest.fixture
def fresh_autotune(monkeypatch):
    """Reset the crossover memo and isolate env overrides per test."""
    monkeypatch.setattr(cs, "_crossover", None)
    monkeypatch.delenv("REPRO_STACK_AUTOTUNE", raising=False)
    monkeypatch.delenv("REPRO_STACK_AUTOTUNE_CACHE", raising=False)
    yield
    cs._crossover = None


def test_resolve_backend_auto_env_override(fresh_autotune, monkeypatch):
    monkeypatch.setenv("REPRO_STACK_AUTOTUNE", "1000")
    assert cs.resolve_backend("auto", n_values=999) == "numpy"
    if cs.have_jax():
        assert cs.resolve_backend("auto", n_values=1000) == "jax"
        assert cs.resolve_backend(None, n_values=10 ** 9) == "jax"
    assert cs.resolve_backend(None) == "auto"        # no size: defer


def test_resolve_backend_auto_inf_always_numpy(fresh_autotune, monkeypatch):
    monkeypatch.setenv("REPRO_STACK_AUTOTUNE", "inf")
    assert cs.resolve_backend("auto", n_values=1 << 40) == "numpy"


def test_autotune_disk_cache_round_trip(fresh_autotune, monkeypatch, tmp_path):
    if not cs.have_jax():
        pytest.skip("autotune probe needs jax")
    cache = tmp_path / "autotune.json"
    monkeypatch.setenv("REPRO_STACK_AUTOTUNE_CACHE", str(cache))
    first = cs.autotune_crossover(refresh=True)
    assert cache.exists()
    payload = json.loads(cache.read_text())
    assert payload["tag"] == cs._probe_tag()
    # a fresh memo must come from the cache file, not a re-probe: poison the
    # stored value and check it is believed verbatim
    payload["crossover"] = 12345.0
    cache.write_text(json.dumps(payload))
    cs._crossover = None
    assert cs.autotune_crossover() == 12345.0
    assert first == first                      # probe result itself was finite-or-inf


def test_autotune_cache_ignored_on_tag_mismatch(fresh_autotune, monkeypatch,
                                                tmp_path):
    monkeypatch.setenv("REPRO_STACK_AUTOTUNE", "2048")   # pin: no live probe
    cache = tmp_path / "autotune.json"
    cache.write_text(json.dumps({"tag": "someone-elses-machine",
                                 "crossover": 7.0}))
    monkeypatch.setenv("REPRO_STACK_AUTOTUNE_CACHE", str(cache))
    assert cs.autotune_crossover() == 2048.0   # env wins over a stale cache


def test_backends_tuple_includes_auto():
    assert "auto" in cs.BACKENDS
    assert "auto" in PhaseStack.__init__.__module__ or True  # sanity import
    from repro.comm.stack import STACK_BACKENDS
    assert STACK_BACKENDS == cs.BACKENDS


# ------------------------------------------------ stack-level auto ----------
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


def test_stack_auto_high_crossover_is_bit_identical_to_numpy(
        fresh_autotune, monkeypatch):
    """auto -> numpy below the crossover: byte-for-byte the numpy path."""
    monkeypatch.setenv("REPRO_STACK_AUTOTUNE", "inf")
    stack = PhaseStack.build(_bw_phases())
    a = stack.cost_arrays(backend="auto")
    b = stack.cost_arrays(backend="numpy")
    for x, y in zip(a, b):
        np.testing.assert_array_equal(x, y)


@needs_jax
def test_stack_auto_low_crossover_takes_device_path(fresh_autotune,
                                                    monkeypatch):
    monkeypatch.setenv("REPRO_STACK_AUTOTUNE", "1")
    stack = PhaseStack.build(_bw_phases())
    a = stack.cost_arrays(backend="auto")
    b = stack.cost_arrays(backend="numpy")
    for x, y in zip(a, b):
        np.testing.assert_allclose(x, y, rtol=2e-4, atol=1e-12)


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


def test_deprecated_one_hot_shim_still_importable():
    from repro.comm.health import reset_health
    assert cs.PALLAS_ONE_HOT_LIMIT == 1 << 24
    reset_health()                       # clear the warn-once registry
    with pytest.warns(DeprecationWarning, match="fused tiled kernel"):
        assert cs.pallas_within_limit(1 << 30, 1 << 20) is True
