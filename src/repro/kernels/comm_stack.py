"""Accelerator backends for the PhaseStack segmented passes.

The stacked sweep engine (:mod:`repro.comm.stack`) reduces per-message
quantities to per-(phase, process) / per-(phase, link) aggregates with
segmented sums/maxima over packed integer keys, and replays receive-queue
walks.  This module provides all three on three backends
(:data:`BACKENDS`):

``backend='numpy'`` (also what ``backend=None`` means)
    ``np.bincount`` / ``np.maximum.at`` and the host Fenwick walk of
    :func:`repro.comm.primitives.batched_queue_traversal_steps`: the
    bit-identity reference, and the fallback of both device backends.
``backend='jax'``
    ``jax.ops.segment_sum`` / ``segment_max`` under ``jax.jit`` and the
    jitted queue walk (:func:`queue_walk`): a walk length is a dominance
    count, ``1 + #{k > j : b_k < b_j}`` over the later arrivals of a
    receive queue (``b`` the posted slot of each arrival), so the queues
    are counted in dense compare-and-sum tiles, one per power-of-two
    length class, with no gather or scatter.  A call with a queue of more
    than :data:`TILE_MAX` arrivals runs the numpy walk instead.
``backend='pallas'``
    A fused Pallas segment reduce.  :func:`fused_segment_reduce` sorts the
    keys on the host and reduces one-hot membership tiles of (segment tile
    x message chunk) pairs — sums and maxima in one launch, with no vector
    scatter (Mosaic has no lowering for one) and a bounded VMEM tile at any
    arena size.  The queue walk is the jitted XLA walk of ``'jax'``.  On
    hosts without a TPU the kernel runs in interpret mode (parity, not
    speed).

:func:`resolve_backend` validates a name and falls back to numpy, with a
warning once, when jax is absent or the device backend is quarantined.
Backend parity for the float reductions is *allclose*, not bit-equal (the
device paths run float32); the queue walk is integer work and bit-equal on
every backend.

Robustness (DESIGN.md §12): every device call here runs inside
:func:`device_guard` — a named fault-injection site
(:mod:`repro.comm.faults`) plus the graceful-degradation policy: any
backend failure falls back to the numpy reference, warns once, and is
recorded in :class:`repro.comm.health.BackendHealth`, which quarantines a
backend after repeated consecutive failures.  The optional
``REPRO_STACK_VERIFY`` post-kernel check (``finite`` | ``parity``) detects
silent NaN/mismatch in device outputs and triggers the same fallback.

This module imports jax lazily so that importing it — and everything in
:mod:`repro.comm` — stays numpy-only.
"""
from __future__ import annotations

import contextlib
import functools
import os

import numpy as np

from repro.comm import faults, obs
from repro.comm.health import get_health

BACKENDS = ("numpy", "jax", "pallas")

_CHUNK = 512        # messages per fused-kernel grid step
_LANE = 128         # segments per fused-kernel output tile


def have_jax() -> bool:
    try:
        import jax  # noqa: F401
        return True
    except Exception:  # pragma: no cover - environment-dependent
        return False


def resolve_backend(backend: str | None = None) -> str:
    """Resolve a backend request to a concrete backend name.

    ``None`` means numpy.  An unknown name raises ``ValueError`` naming the
    allowed values (:data:`BACKENDS`).  ``'jax'`` / ``'pallas'`` fall back
    to numpy with a warning (once per process, via the resettable
    :class:`repro.comm.health.BackendHealth` registry) when jax is not
    importable or the backend is quarantined after repeated failures.
    """
    if backend is None:
        return "numpy"
    if backend not in BACKENDS:
        raise ValueError(
            f"unknown stack backend {backend!r}; expected one of {BACKENDS}")
    if backend == "numpy":
        return backend
    if not have_jax():
        get_health().warn_once(
            f"nojax:{backend}",
            f"stack backend {backend!r} requested but jax is not "
            "importable; falling back to numpy")
        return "numpy"
    if get_health().is_quarantined(backend):
        get_health().warn_once(
            f"resolve-quarantined:{backend}",
            f"stack backend {backend!r} is quarantined after repeated "
            "failures; resolving to numpy (BackendHealth.reset() restores)")
        return "numpy"
    return backend


# -- graceful degradation around device calls --------------------------------

#: Allowed ``REPRO_STACK_VERIFY`` values: ``''`` (off), ``finite`` (reject
#: non-finite device outputs), ``parity`` (compare device outputs against
#: the numpy reference, allclose).
VERIFY_MODES = ("", "finite", "parity")


class BackendVerifyError(RuntimeError):
    """A device output failed the ``REPRO_STACK_VERIFY`` post-kernel check."""


def verify_mode() -> str:
    """The active post-kernel check, from ``REPRO_STACK_VERIFY``.

    ``finite`` rejects NaN/inf in device outputs; ``parity`` recomputes the
    numpy reference and rejects non-allclose outputs.  Either rejection is
    a :class:`BackendVerifyError`, which the degradation policy treats like
    any other backend failure (fallback + health event).  An unknown value
    raises ``ValueError`` naming the allowed modes.
    """
    mode = os.environ.get("REPRO_STACK_VERIFY", "")
    if mode not in VERIFY_MODES:
        raise ValueError(
            f"unknown REPRO_STACK_VERIFY value {mode!r}; allowed values: "
            f"{VERIFY_MODES}")
    return mode


def _leaves(value):
    return value if isinstance(value, tuple) else (value,)


def _check_finite(value) -> None:
    for leaf in _leaves(value):
        arr = np.asarray(leaf)
        if np.issubdtype(arr.dtype, np.floating) and not np.isfinite(arr).all():
            raise BackendVerifyError(
                "device output contains non-finite values "
                "(REPRO_STACK_VERIFY=finite)")


def _check_parity(value, ref) -> None:
    for got, want in zip(_leaves(value), _leaves(ref)):
        g = np.asarray(got)
        w = np.asarray(want)
        if np.issubdtype(g.dtype, np.integer) and \
                np.issubdtype(w.dtype, np.integer):
            # integer device outputs are bit-equal by contract; allclose
            # would let a +1 shift on large values slide under rtol
            ok = g.shape == w.shape and (g == w).all()
        else:
            ok = np.allclose(g.astype(np.float64), w.astype(np.float64),
                             rtol=1e-4, atol=1e-6, equal_nan=False)
        if not ok:
            raise BackendVerifyError(
                "device output does not match the numpy reference "
                "(REPRO_STACK_VERIFY=parity)")


def device_guard(site: str, backend: str, device_fn, numpy_fn):
    """Run one device-backend call under the full degradation contract.

    ``device_fn`` (no arguments) performs the device work; ``numpy_fn`` (no
    arguments) computes the bit-identity numpy reference.  In order:

    1. a quarantined ``backend`` skips the device path entirely and returns
       ``numpy_fn()`` (the quarantine was announced when it was imposed);
    2. the :mod:`repro.comm.faults` injection site ``site`` may raise
       (``raise`` / ``timeout`` modes) or poison the device output
       (``nan`` / ``corrupt`` modes);
    3. the ``REPRO_STACK_VERIFY`` post-kernel check, when enabled, rejects
       non-finite (``finite``) or non-matching (``parity``) device outputs;
    4. *any* failure in 2-3 — or in the device computation itself — is
       recorded in :class:`repro.comm.health.BackendHealth` (warn-once,
       streak accounting, quarantine after repeated failures) and the call
       returns ``numpy_fn()`` instead of raising.

    A successful device call records a success (clearing the backend's
    failure streak) and returns the device output.
    """
    health = get_health()
    if health.is_quarantined(backend):
        return numpy_fn()
    try:
        with _device_call(site):
            faults.fail_point(site)
            out = faults.poison(site, device_fn())
            mode = verify_mode()
            if mode == "finite":
                _check_finite(out)
            elif mode == "parity":
                ref = numpy_fn()
                _check_parity(out, ref)
    except Exception as e:  # noqa: BLE001 - degradation catches everything
        health.record_failure(backend, site, e)
        return numpy_fn()
    health.record_success(backend)
    return out


_NO_SPAN = contextlib.nullcontext()


def _device_call(site: str):
    """The ``repro.device.<site>`` span of one device call, counted as
    ``device.calls.<site>``; while tracing is off, a null context and no
    name built."""
    if not obs.enabled():
        return _NO_SPAN
    obs.count("device.calls." + site)
    return obs.span("repro.device." + site)


# -- jitted segment reductions ----------------------------------------------

@functools.cache
def _jax_segment_ops():
    import jax

    @functools.partial(jax.jit, static_argnames=("n_seg",))
    def seg_sum(vals, ids, n_seg):
        return jax.ops.segment_sum(vals, ids, num_segments=n_seg)

    @functools.partial(jax.jit, static_argnames=("n_seg",))
    def seg_max(vals, ids, n_seg):
        return jax.ops.segment_max(vals, ids, num_segments=n_seg)

    return seg_sum, seg_max


def to_device(a, dtype=None):
    """``a`` as a device array: jax arrays pass through untouched (already
    resident), anything else is shipped once, as ``dtype`` when given.
    Every host->device ship of the planner passes here or through
    :func:`count_shipped`, so the ``device.h2d_bytes`` counter of
    :mod:`repro.comm.obs` is exact."""
    import jax
    import jax.numpy as jnp
    if isinstance(a, jax.Array):
        return a
    out = jnp.asarray(np.asarray(a), dtype=dtype)
    obs.count("device.h2d_bytes", out.nbytes)
    return out


def count_shipped(arrays):
    """``arrays``, host arrays handed as they are to a jitted call, which
    ships them itself; their bytes count toward ``device.h2d_bytes`` while
    tracing is on."""
    if obs.enabled():
        obs.count("device.h2d_bytes", sum(a.nbytes for a in arrays))
    return arrays


def to_host(a, dtype=None) -> np.ndarray:
    """``a`` as a numpy array, as ``dtype`` when given.  A device array is
    copied back under the ``repro.device.sync`` span, the host blocked
    until the device has produced it, and counted (``device.syncs``,
    ``device.d2h_bytes``); a host array is only converted.  Every
    device->host copy of the planner passes here."""
    if not hasattr(a, "block_until_ready"):     # already on the host
        return np.asarray(a, dtype=dtype)
    with obs.span("repro.device.sync"):
        out = np.asarray(a, dtype=dtype)
    obs.count("device.syncs")
    obs.count("device.d2h_bytes", a.nbytes)
    return out


# -- fused Pallas segment reduce ---------------------------------------------
#
# Mosaic has no vector scatter, so the kernel never indexes by segment id.
# The host sorts the message keys once per call; each grid step then takes
# one (segment tile x message chunk) pair and reduces a one-hot membership
# tile on the VPU.  The pairs come in tile order, so a tile's output block
# stays resident in VMEM across its chunks.  A sorted chunk overlaps a
# contiguous run of tiles, so there are at most n_chunks + n_tiles pairs:
# the work stays O(messages * _LANE + segments * _CHUNK) and VMEM holds one
# (_LANE, _CHUNK) tile whatever the arena size.

def _segreduce_layout(seg_ids, n_seg: int):
    """Host-side schedule for :func:`_segreduce_kernel`.

    Returns ``(order, ids, tile_of, chunk_of, n_items)``: the stable sort
    permutation of the messages, the sorted keys padded to whole chunks
    with the sink key ``n_seg`` and shaped ``(n_chunks, 1, _CHUNK)``, and
    the grid's (tile, chunk) pairs.  Every tile gets at least one pair, so
    empty tiles are written too; pairs past ``n_items`` repeat the last
    one and do no work.
    """
    from repro.comm.primitives import segmented_arange

    ids = np.asarray(seg_ids, dtype=np.int64)
    n = ids.size
    n_chunks = max(1, -(-n // _CHUNK))
    n_tiles = -(-(n_seg + 1) // _LANE)            # +1: the sink key's tile
    order = np.argsort(ids, kind="stable").astype(np.int32)
    ids_s = np.full(n_chunks * _CHUNK, n_seg, dtype=np.int32)
    ids_s[:n] = ids[order]
    first_t = ids_s[::_CHUNK] // _LANE
    last_t = ids_s[_CHUNK - 1::_CHUNK] // _LANE
    tiles = np.arange(n_tiles)
    lo = np.searchsorted(last_t, tiles, side="left")
    hi = np.searchsorted(first_t, tiles, side="right")
    cnt = np.maximum(hi - lo, 1)
    lo = np.minimum(lo, n_chunks - 1)
    tile_of = np.repeat(tiles, cnt)
    chunk_of = lo[tile_of] + segmented_arange(cnt)
    n_items = tile_of.size
    width = n_chunks + n_tiles
    tile_of = np.pad(tile_of, (0, width - n_items), mode="edge")
    chunk_of = np.pad(chunk_of, (0, width - n_items), mode="edge")
    return (order, ids_s.reshape(n_chunks, 1, _CHUNK),
            tile_of.astype(np.int32), chunk_of.astype(np.int32),
            np.asarray([n_items], dtype=np.int32))


def _segreduce_kernel(tile_ref, chunk_ref, n_items_ref, ids_ref, vals_ref,
                      sum_ref, max_ref, acc_sum, acc_max):
    """One grid step: fold message chunk ``chunk_of[w]`` into segment tile
    ``tile_of[w]``.  The accumulators are columns (segments on sublanes);
    the output row is their transpose, rewritten every step so that it is
    final whenever the tile's block is written back."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    w = pl.program_id(0)
    tile = tile_ref[w]

    @pl.when(jnp.logical_or(w == 0, tile_ref[jnp.maximum(w - 1, 0)] != tile))
    def _init():
        acc_sum[...] = jnp.zeros_like(acc_sum)
        acc_max[...] = jnp.zeros_like(acc_max)

    @pl.when(w < n_items_ref[0])
    def _fold():
        seg = tile * _LANE + jax.lax.broadcasted_iota(
            jnp.int32, (_LANE, _CHUNK), 0)
        v = jnp.where(ids_ref[...] == seg, vals_ref[...], 0.0)
        acc_sum[...] += jnp.sum(v, axis=1, keepdims=True)
        acc_max[...] = jnp.maximum(acc_max[...],
                                   jnp.max(v, axis=1, keepdims=True))
        sum_ref[...] = jnp.broadcast_to(acc_sum[...], (_LANE, _LANE)).T[:1]
        max_ref[...] = jnp.broadcast_to(acc_max[...], (_LANE, _LANE)).T[:1]


def _segreduce_spec(n_msgs: int, n_seg: int) -> dict:
    """``pl.pallas_call`` arguments (grid spec and output shapes) of the
    segment reduce for ``n_msgs`` messages into ``n_seg`` segments."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    n_chunks = max(1, -(-n_msgs // _CHUNK))
    n_tiles = -(-(n_seg + 1) // _LANE)

    def chunk_block():
        return pl.BlockSpec((None, 1, _CHUNK),
                            lambda w, tile, chunk, n: (chunk[w], 0, 0))

    def tile_block():
        return pl.BlockSpec((1, _LANE), lambda w, tile, chunk, n: (0, tile[w]))

    row = jax.ShapeDtypeStruct((1, n_tiles * _LANE), jnp.float32)
    return dict(
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3, grid=(n_chunks + n_tiles,),
            in_specs=[chunk_block(), chunk_block()],
            out_specs=[tile_block(), tile_block()],
            scratch_shapes=[pltpu.VMEM((_LANE, 1), jnp.float32),
                            pltpu.VMEM((_LANE, 1), jnp.float32)]),
        out_shape=[row, row])


def _segreduce_device(values, order, ids, tile_of, chunk_of, n_items, *,
                      n_seg: int, call):
    """The device side of the segment reduce (traced under ``jax.jit``):
    gather the values into key order, pad them to whole chunks and run the
    ``pallas_call`` ``call``.  Returns ``(sums, maxima)`` of length
    ``n_seg``."""
    import jax.numpy as jnp

    vals = values.astype(jnp.float32)[order]
    vals = jnp.pad(vals, (0, ids.size - vals.size)).reshape(ids.shape)
    sums, maxs = call(tile_of, chunk_of, n_items, ids, vals)
    return sums[0, :n_seg], maxs[0, :n_seg]


@functools.cache
def _pallas_segreduce(n_msgs: int, n_seg: int):
    import jax
    from jax.experimental import pallas as pl

    call = pl.pallas_call(_segreduce_kernel, **_segreduce_spec(n_msgs, n_seg),
                          interpret=jax.default_backend() == "cpu")
    return jax.jit(functools.partial(_segreduce_device, n_seg=n_seg,
                                     call=call))


def fused_segment_reduce(values, seg_ids,
                         n_seg: int) -> tuple[np.ndarray, np.ndarray]:
    """One fused Pallas launch -> ``(segment sums, segment maxima)``.

    The keys are sorted on the host and the kernel reduces one-hot
    membership tiles of (segment tile x message chunk) pairs, so it needs
    no vector scatter (which Mosaic cannot lower) and a bounded VMEM tile
    at any arena size.  ``values`` may be a device array and stays on the
    device; ``seg_ids`` is read on the host.  Empty segments report sum 0
    and every maximum is floored at 0, exactly as the numpy reference
    (the contention reduction's inputs are non-negative byte counts).

    Kernel failures degrade to the numpy reference pair via
    :func:`device_guard` (site ``kernel.segment_reduce``).
    """
    seg_ids = to_host(seg_ids)

    def device_fn():
        import jax.numpy as jnp

        with obs.span("repro.kernel.layout"):
            layout = _segreduce_layout(seg_ids, n_seg)
        s, mx = _pallas_segreduce(int(seg_ids.size), n_seg)(
            to_device(values, jnp.float32), *count_shipped(layout))
        return to_host(s, np.float64), to_host(mx, np.float64)

    def numpy_fn():
        vals = to_host(values)
        return (_segment_sum_numpy(vals, seg_ids, n_seg),
                _segment_max_numpy(vals, seg_ids, n_seg))

    return device_guard("kernel.segment_reduce", "pallas", device_fn,
                        numpy_fn)


# -- public segment reductions -----------------------------------------------

def _segment_sum_numpy(values, seg_ids, n_seg: int) -> np.ndarray:
    """The bit-identity numpy reference for :func:`segment_sum` (also the
    degradation fallback for the device backends)."""
    return np.bincount(to_host(seg_ids, np.int64),
                       weights=to_host(values, np.float64), minlength=n_seg)


def _segment_max_numpy(values, seg_ids, n_seg: int) -> np.ndarray:
    """The bit-identity numpy reference for :func:`segment_max`."""
    out = np.zeros(n_seg)
    np.maximum.at(out, to_host(seg_ids, np.int64),
                  to_host(values, np.float64))
    return out


def segment_sum(values, seg_ids, n_seg: int,
                backend: str | None = None) -> np.ndarray:
    """Sum ``values`` into ``n_seg`` bins by ``seg_ids`` on the chosen
    backend (``None`` = numpy).  Device inputs (jax arrays) stay resident
    on the jax path; the reduced dense result is returned on the host.
    Device-backend failures degrade to the numpy reference via
    :func:`device_guard` (site ``kernel.segment_reduce``)."""
    backend = resolve_backend(backend)
    if backend == "numpy":
        return _segment_sum_numpy(values, seg_ids, n_seg)
    if backend == "pallas":
        return fused_segment_reduce(values, seg_ids, n_seg)[0]

    def device_fn():
        import jax.numpy as jnp
        seg_sum, _ = _jax_segment_ops()
        return to_host(seg_sum(to_device(values, jnp.float32),
                               to_device(seg_ids, jnp.int32), n_seg),
                       np.float64)

    return device_guard("kernel.segment_reduce", backend, device_fn,
                        lambda: _segment_sum_numpy(values, seg_ids, n_seg))


def segment_max(values, seg_ids, n_seg: int,
                backend: str | None = None) -> np.ndarray:
    """Per-segment maximum (0.0 for empty segments, matching the stacked
    contention reduction where all inputs are non-negative byte counts)
    on the chosen backend (``None`` = numpy).  Device-backend failures
    degrade to the numpy reference via :func:`device_guard` (site
    ``kernel.segment_reduce``)."""
    backend = resolve_backend(backend)
    if backend == "numpy":
        return _segment_max_numpy(values, seg_ids, n_seg)
    if backend == "pallas":
        return fused_segment_reduce(values, seg_ids, n_seg)[1]

    def device_fn():
        import jax.numpy as jnp
        _, seg_max = _jax_segment_ops()
        out = to_host(seg_max(to_device(values, jnp.float32),
                              to_device(seg_ids, jnp.int32), n_seg),
                      np.float64)
        out[np.isneginf(out)] = 0.0
        return out

    return device_guard("kernel.segment_reduce", backend, device_fn,
                        lambda: _segment_max_numpy(values, seg_ids, n_seg))


# -- device queue walk: dominance tiles --------------------------------------
#
# A walk length is a dominance count.  Let ``b_j`` be the posted slot of a
# region's j-th arrival.  The slots below ``b_j`` still posted when it
# arrives are exactly those matched by later arrivals, so it matches at
#
#     steps_j = 1 + #{k > j : b_k < b_j}
#
# in the unmatched queue.  Each region is padded to its class ``C`` (the
# next power of two, at least ``_TILE_MIN``) and laid out with the class's
# other regions as one dense int32 tile ``[C, R]``: row ``j`` holds the j-th
# arrival of each of the ``R`` regions, so the regions sit on the lanes, and
# pads hold ``_PAD``, which is never below a posted slot.  Each tile is one
# fused compare-and-sum (a long class's in blocks of rows), with no gather
# or scatter.  Tile work grows as ``C^2`` a region, so a call with a region
# longer than ``TILE_MAX`` takes the host Fenwick walk instead.

#: Longest region (arrivals) the dominance tiles take; a call with a longer
#: region runs the host Fenwick walk of
#: :func:`repro.comm.primitives.batched_queue_traversal_steps`.  Its
#: derivation is in DESIGN.md, "The queue walk on device".
TILE_MAX = 1 << 14
_TILE_MIN = 8                       # smallest class: a sublane tile's rows
_BLOCK = 1 << 24                    # compares of one tile block
_PAD = np.iinfo(np.int32).max       # tile cells past a region's arrivals


def _pow2_ceil(n) -> np.ndarray:
    """The least power of two at or above each of ``n`` (1 where n <= 1)."""
    n = np.asarray(n, dtype=np.int64)
    return np.left_shift(1, np.frexp(np.maximum(n - 1, 0))[1]).astype(
        np.int64)


def _queue_layout(posted, arrival, bounds):
    """Host layout of :func:`queue_walk`: ``(cells, dest, classes)``.

    ``cells`` (int32) holds the class tiles one after another, each
    ``[C, R]`` flattened; ``dest`` is each arrival's index in ``cells``, in
    the layout of ``posted``; ``classes`` is ``((C, R), ...)`` by ascending
    ``C``.  Every index depends on ``bounds`` alone; only the slots come
    from the orders.
    """
    posted = np.asarray(posted, dtype=np.int64)
    arrival = np.asarray(arrival, dtype=np.int64)
    bounds = np.asarray(bounds, dtype=np.int64)
    N = int(posted.size)
    counts = np.diff(bounds)
    region_of = np.repeat(np.arange(counts.size), counts)
    start_of = bounds[:-1][region_of]
    local = np.arange(N) - start_of
    pos = np.empty(N, dtype=np.int64)
    pos[start_of + posted] = local
    b = pos[start_of + arrival]                       # slot of j-th arrival
    # non-empty regions, class by class, each a column of its class's tile
    tiled = np.flatnonzero(counts)
    width = np.maximum(_pow2_ceil(counts[tiled]), _TILE_MIN)
    by_class = np.argsort(width, kind="stable")
    tiled, width = tiled[by_class], width[by_class]
    cw, first, n_per = np.unique(width, return_index=True,
                                 return_counts=True)
    off = np.concatenate([[0], np.cumsum(cw * n_per)])
    cls = np.repeat(np.arange(cw.size), n_per)
    base = np.zeros(counts.size, dtype=np.int64)
    stride = np.ones(counts.size, dtype=np.int64)
    base[tiled] = off[cls] + np.arange(tiled.size) - first[cls]
    stride[tiled] = n_per[cls]
    dest = base[region_of] + local * stride[region_of]
    cells = np.full(int(off[-1]), _PAD, dtype=np.int32)
    cells[dest] = b
    return cells, dest, tuple(zip(cw.tolist(), n_per.tolist()))


def _tile_steps(x):
    """``1 + #{k > j : x[k, r] < x[j, r]}`` for every cell of a class tile
    ``x`` ``[C, R]`` (traced).  Where too few regions share a long class
    to fill the lanes, the tile is turned so that ``k`` takes them.  A
    tile of more than ``_BLOCK`` compares is taken in blocks of rows
    ``j``, each against every row ``k``."""
    import jax
    import jax.numpy as jnp

    C, R = x.shape
    turn = R < _LANE <= C
    y, ax = (x.T, 1) if turn else (x, 0)
    rows = min(C, 1 << (max(1, _BLOCK // (C * R)).bit_length() - 1))
    k = jnp.arange(C, dtype=jnp.int32)

    def block(j0):
        yj = jax.lax.dynamic_slice_in_dim(y, j0, rows, axis=ax)
        j = j0 + jnp.arange(rows, dtype=jnp.int32)
        if turn:                                      # [R, j, k]
            hit = (y[:, None, :] < yj[:, :, None]) & (k > j[:, None])
            return 1 + jnp.sum(hit, axis=2, dtype=jnp.int32)
        hit = (y[None] < yj[:, None]) & (k[:, None] > j[:, None, None])
        return 1 + jnp.sum(hit, axis=1, dtype=jnp.int32)  # [j, k, R]

    if rows == C:
        out = block(0)
    else:
        out = jax.lax.fori_loop(
            0, C // rows,
            lambda i, out: jax.lax.dynamic_update_slice_in_dim(
                out, block(i * rows), i * rows, ax),
            jnp.zeros_like(y))
    return out.T if turn else out


@functools.cache
def _jax_queue_walk():
    """The jitted queue walk of both device backends, ``walk(cells,
    classes=...)``: every class tile of ``cells`` as dominance counts, in
    one program that returns the steps in the layout of ``cells``.  Shapes
    retrace per arena layout."""
    import jax
    import jax.numpy as jnp

    def walk(cells, classes):
        outs, o = [], 0
        for c, r in classes:
            outs.append(_tile_steps(cells[o:o + c * r].reshape(c, r))
                        .reshape(-1))
            o += c * r
        return jnp.concatenate(outs)

    return jax.jit(walk, static_argnames="classes")


def queue_walk(posted, arrival, bounds, backend: str | None = None) -> np.ndarray:
    """Batched receive-queue walk lengths on the chosen backend (``None`` =
    numpy).

    Same contract as
    :func:`repro.comm.primitives.batched_queue_traversal_steps` (region
    ``r`` owns slots ``bounds[r]:bounds[r+1]`` of ``posted``/``arrival``;
    returns per-arrival steps in the same layout).  On ``'jax'`` and
    ``'pallas'`` alike, one jitted program (:func:`_jax_queue_walk`)
    counts, for each arrival, the later arrivals of its region posted
    before it, as dense dominance tiles, one per power-of-two length
    class.  The host lays the tiles out and takes the steps back out of
    them.  A call with a region of more than :data:`TILE_MAX` arrivals, or
    with 2^31 - 1 tile cells or more, runs the numpy reference instead.
    The walk is integer work, so every backend is bit-equal to the numpy
    reference.  With :mod:`repro.comm.obs` on, each call counts
    ``walk.tile_regions``, ``walk.fenwick_regions`` (non-empty regions
    counted in the tiles and by the host Fenwick walk) and
    ``walk.tile_cells`` (tile cells, pads included).  Device-backend
    failures degrade to the numpy reference via :func:`device_guard`
    (site ``kernel.queue_walk``) — bit-identically, since the walk is
    integer work.
    """
    backend = resolve_backend(backend)

    def numpy_fn():
        from repro.comm.primitives import batched_queue_traversal_steps
        return batched_queue_traversal_steps(posted, arrival, bounds)

    if backend == "numpy":
        return numpy_fn()

    counts = np.diff(np.asarray(bounds, dtype=np.int64))
    if not counts.any():
        return np.zeros(int(np.asarray(posted).size), dtype=np.int64)
    if counts.max() > TILE_MAX:
        obs.count("walk.fenwick_regions", int(np.count_nonzero(counts)))
        return numpy_fn()
    with obs.span("repro.kernel.layout"):
        cells, dest, classes = _queue_layout(posted, arrival, bounds)
    if cells.size >= _PAD:                            # pragma: no cover
        return numpy_fn()
    obs.count("walk.tile_regions", sum(r for _, r in classes))
    obs.count("walk.tile_cells", int(cells.size))

    def device_fn():
        steps = _jax_queue_walk()(to_device(cells), classes=classes)
        return to_host(steps)[dest].astype(np.int64)

    return device_guard("kernel.queue_walk", backend, device_fn, numpy_fn)
