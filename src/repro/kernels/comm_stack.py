"""Accelerator backends for the PhaseStack segmented passes.

The stacked sweep engine (:mod:`repro.comm.stack`) reduces per-message
quantities to per-(phase, process) / per-(phase, link) aggregates with
segmented sums/maxima over packed integer keys, and replays receive-queue
walks with a batched lock-step Fenwick sweep.  This module provides the
device implementations of all three:

``backend='jax'``
    ``jax.ops.segment_sum`` / ``segment_max`` under ``jax.jit`` and a jitted
    ``lax.fori_loop`` Fenwick walk (:func:`queue_walk`) — the scalable
    path: O(total messages) scatter work, the whole queue sweep one device
    program with no host round-trip between rounds.
``backend='pallas'``
    A fused Pallas segment reduce.  :func:`fused_segment_reduce` sorts the
    keys on the host and reduces one-hot membership tiles of (segment tile
    x message chunk) pairs — sums and maxima in one launch, with no vector
    scatter (Mosaic has no lowering for one) and a bounded VMEM tile at any
    arena size.  The queue walk is the jitted XLA walk of ``'jax'``: a
    per-lane Fenwick walk needs dynamic gathers and scatters that Mosaic
    cannot lower either.  On hosts without a TPU the kernel runs in
    interpret mode (parity, not speed).
``backend='auto'`` (the resolved form of ``backend=None``)
    The autotuned default: picks numpy below the measured numpy/jax
    crossover size and jax at/above it (:func:`autotune_crossover`).

numpy is the bit-identity reference and the silent fallback when jax is
absent (:func:`resolve_backend` warns once for explicit device requests).
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
silent NaN/mismatch in device outputs and triggers the same fallback.  The
autotune probe is bounded by a cooperative timeout with
retry-and-backoff, and its disk cache tolerates corruption and read-only
directories.

This module imports jax lazily so that importing it — and everything in
:mod:`repro.comm` — stays numpy-only.
"""
from __future__ import annotations

import functools
import json
import os
import time

import numpy as np

from repro.comm import faults
from repro.comm.health import get_health

BACKENDS = ("numpy", "jax", "pallas", "auto")

_CHUNK = 512        # messages per fused-kernel grid step
_LANE = 128         # segments per fused-kernel output tile
_SEG_BLOCK = _LANE  # historical alias (the retired one-hot kernel's block)


def have_jax() -> bool:
    try:
        import jax  # noqa: F401
        return True
    except Exception:  # pragma: no cover - environment-dependent
        return False


def resolve_backend(backend: str | None = None,
                    n_values: int | None = None) -> str:
    """Resolve a backend request to a concrete backend name.

    ``None`` means the *autotuned default* (``'auto'``).  ``'auto'`` picks
    numpy below the measured numpy/jax crossover size and jax at/above it;
    pass ``n_values`` (the reduction's input length) to collapse it to a
    concrete choice here — without ``n_values`` the string ``'auto'`` is
    returned for the caller to resolve per call.  Explicit ``'jax'`` /
    ``'pallas'`` requests fall back to numpy with a warning (once per
    process, via the resettable :class:`repro.comm.health.BackendHealth`
    registry) when jax is not importable or the backend is quarantined
    after repeated failures; ``'auto'`` falls back silently (it is a
    default, not a request).
    """
    if backend is None:
        backend = "auto"
    if backend not in BACKENDS:
        raise ValueError(
            f"unknown stack backend {backend!r}; expected one of {BACKENDS}")
    if backend != "numpy" and not have_jax():
        if backend != "auto":
            get_health().warn_once(
                f"nojax:{backend}",
                f"stack backend {backend!r} requested but jax is not "
                "importable; falling back to numpy")
        return "numpy"
    if backend == "auto" and n_values is not None:
        backend = "numpy" if n_values < autotune_crossover() else "jax"
    if backend in ("jax", "pallas") and get_health().is_quarantined(backend):
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


# -- autotuned numpy/jax crossover -------------------------------------------

#: probe sizes for the crossover search (geometric, covers the realistic
#: arena range on both CPU-only and accelerator hosts)
_PROBE_SIZES = (1 << 13, 1 << 15, 1 << 17, 1 << 19)
_PROBE_SEGMENTS = 256

_crossover: float | None = None


def _probe_tag() -> str:
    """Cache key tying a persisted probe to the software/device stack."""
    parts = [np.__version__]
    try:
        import jax
        parts += [jax.__version__, jax.default_backend()]
    except Exception:  # pragma: no cover - environment-dependent
        parts.append("nojax")
    return "/".join(parts)


def _best_time(fn, reps: int = 3) -> float:
    fn()                                              # warm (jit, caches)
    best = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        fn()
        best = min(best, time.perf_counter() - t0)
    return best


def _probe_pair(n: int) -> tuple[float, float]:
    """(numpy, jax) best-of times for one packed-key segment sum of ``n``."""
    import jax
    import jax.numpy as jnp

    rng = np.random.default_rng(0)
    ids = rng.integers(0, _PROBE_SEGMENTS, size=n)
    vals = rng.random(n)
    t_np = _best_time(
        lambda: np.bincount(ids, weights=vals, minlength=_PROBE_SEGMENTS))
    seg_sum, _ = _jax_segment_ops()
    d_vals = jax.device_put(jnp.asarray(vals, jnp.float32))
    d_ids = jax.device_put(jnp.asarray(ids, jnp.int32))
    t_jax = _best_time(
        lambda: seg_sum(d_vals, d_ids, _PROBE_SEGMENTS).block_until_ready())
    return t_np, t_jax


#: Live-probe hardening: per-size retry attempts, base backoff seconds
#: (doubling per retry), and the cooperative probe deadline (seconds,
#: override with ``REPRO_STACK_PROBE_TIMEOUT``).
_PROBE_RETRIES = 3
_PROBE_BACKOFF = 0.05
_PROBE_TIMEOUT = 60.0


def _read_probe_cache(path: str, tag: str) -> float | None:
    """The cached crossover at ``path``, or None when the cache is absent,
    unreadable, corrupt, or tagged for a different software stack (a
    corrupt cache is recorded as a health event and reprobed, never
    trusted and never fatal)."""
    if not os.path.exists(path):
        return None
    try:
        faults.fail_point("autotune.cache_read")
        with open(path) as fh:
            raw = faults.poison("autotune.cache_read", fh.read())
        rec = json.loads(raw)
        if rec.get("tag") == tag:
            return float(rec["crossover"])
    except (OSError, ValueError, KeyError, TypeError) as e:
        get_health().record_failure("disk-cache", "autotune.cache_read", e)
    return None


def _write_probe_cache(path: str, tag: str, cross: float) -> None:
    """Persist a probe result; a read-only/failing cache directory is a
    recorded health event, not an error (the probe result still serves the
    process from the in-memory memo)."""
    try:
        faults.fail_point("autotune.cache_write")
        with open(path, "w") as fh:
            json.dump({"tag": tag, "crossover": cross,
                       "sizes": list(_PROBE_SIZES)}, fh)
    except OSError as e:
        get_health().record_failure("disk-cache", "autotune.cache_write", e)


def _probe_crossover() -> float:
    """Run the live probe under a cooperative deadline with per-size
    retry-and-backoff; degrades to ``inf`` (numpy always) when the probe
    keeps failing or the deadline passes — a strategy-service query must
    never hang or crash on a misbehaving probe."""
    deadline = time.monotonic() + float(
        os.environ.get("REPRO_STACK_PROBE_TIMEOUT", _PROBE_TIMEOUT))
    for n in _PROBE_SIZES:
        for attempt in range(_PROBE_RETRIES):
            try:
                if time.monotonic() > deadline:
                    raise TimeoutError(
                        f"autotune probe deadline exceeded before size {n}")
                faults.fail_point("autotune.probe")
                t_np, t_jax = _probe_pair(n)
            except TimeoutError as e:
                # the deadline is global: no point retrying or probing on
                get_health().record_failure("autotune", "autotune.probe", e)
                return float("inf")
            except Exception as e:  # noqa: BLE001 - degradation
                get_health().record_failure("autotune", "autotune.probe", e)
                if attempt + 1 == _PROBE_RETRIES:
                    return float("inf")
                time.sleep(_PROBE_BACKOFF * 2 ** attempt)
            else:
                if t_jax < t_np:
                    return float(n)
                break                      # this size settled: next size
    return float("inf")


def autotune_crossover(refresh: bool = False) -> float:
    """The measured input size where the jitted jax segment reduction starts
    beating numpy's ``bincount`` (``float('inf')`` when it never does — e.g.
    CPU-only jax, or jax absent).

    Resolution order: in-process memo -> ``REPRO_STACK_AUTOTUNE`` env
    override (a number, ``inf`` allowed) -> on-disk probe cache (the path in
    ``REPRO_STACK_AUTOTUNE_CACHE``, ignored — with a recorded health event —
    when corrupt or when its software tag no longer matches) -> a live probe
    over ``_PROBE_SIZES`` with device-resident inputs (first size where jax
    wins).  ``refresh=True`` forces a new probe and rewrites the disk cache.
    The probe costs a few jit compiles once per process; pin the env var to
    skip it entirely.

    Hardened for service use: the probe runs under a cooperative deadline
    (``REPRO_STACK_PROBE_TIMEOUT`` seconds) with retry-and-backoff per
    size, and every failure path — probe timeout, corrupt cache, read-only
    cache directory — degrades to a usable crossover (``inf`` = numpy)
    instead of raising.
    """
    global _crossover
    if _crossover is not None and not refresh:
        return _crossover
    env = os.environ.get("REPRO_STACK_AUTOTUNE")
    if env is not None and not refresh:
        _crossover = float(env)
        return _crossover
    path = os.environ.get("REPRO_STACK_AUTOTUNE_CACHE")
    tag = _probe_tag()
    if path and not refresh:
        cached = _read_probe_cache(path, tag)
        if cached is not None:
            _crossover = cached
            return _crossover
    if not have_jax():
        _crossover = float("inf")
        return _crossover
    cross = _probe_crossover()
    _crossover = cross
    if path:
        _write_probe_cache(path, tag, cross)
    return cross


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


def _as_device(a, dtype):
    """``a`` as a device array: jax arrays pass through untouched (already
    resident), anything else is converted once."""
    import jax
    import jax.numpy as jnp
    if isinstance(a, jax.Array):
        return a
    return jnp.asarray(np.asarray(a), dtype=dtype)


def _size_of(a) -> int:
    return int(a.size) if hasattr(a, "size") else len(a)


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
    seg_ids = np.asarray(seg_ids)

    def device_fn():
        import jax.numpy as jnp

        layout = _segreduce_layout(seg_ids, n_seg)
        s, mx = _pallas_segreduce(int(seg_ids.size), n_seg)(
            _as_device(values, jnp.float32), *layout)
        return (np.asarray(s, dtype=np.float64),
                np.asarray(mx, dtype=np.float64))

    def numpy_fn():
        vals = np.asarray(values)
        return (_segment_sum_numpy(vals, seg_ids, n_seg),
                _segment_max_numpy(vals, seg_ids, n_seg))

    return device_guard("kernel.segment_reduce", "pallas", device_fn,
                        numpy_fn)


# -- public segment reductions -----------------------------------------------

def _segment_sum_numpy(values, seg_ids, n_seg: int) -> np.ndarray:
    """The bit-identity numpy reference for :func:`segment_sum` (also the
    degradation fallback for the device backends)."""
    return np.bincount(np.asarray(seg_ids, dtype=np.int64),
                       weights=np.asarray(values, dtype=np.float64),
                       minlength=n_seg)


def _segment_max_numpy(values, seg_ids, n_seg: int) -> np.ndarray:
    """The bit-identity numpy reference for :func:`segment_max`."""
    out = np.zeros(n_seg)
    np.maximum.at(out, np.asarray(seg_ids, dtype=np.int64),
                  np.asarray(values, dtype=np.float64))
    return out


def segment_sum(values, seg_ids, n_seg: int,
                backend: str | None = None) -> np.ndarray:
    """Sum ``values`` into ``n_seg`` bins by ``seg_ids`` on the chosen
    backend (``None``/``'auto'`` = the autotuned default).  Device inputs
    (jax arrays) stay resident on the jax path; the reduced dense result is
    returned on the host.  Device-backend failures degrade to the numpy
    reference via :func:`device_guard` (site ``kernel.segment_reduce``)."""
    if backend in (None, "auto"):
        backend = resolve_backend("auto", n_values=_size_of(seg_ids))
    if backend == "numpy":
        return _segment_sum_numpy(values, seg_ids, n_seg)
    if backend == "pallas":
        return fused_segment_reduce(values, seg_ids, n_seg)[0]

    def device_fn():
        import jax.numpy as jnp
        seg_sum, _ = _jax_segment_ops()
        return np.asarray(seg_sum(_as_device(values, jnp.float32),
                                  _as_device(seg_ids, jnp.int32), n_seg),
                          dtype=np.float64)

    return device_guard("kernel.segment_reduce", backend, device_fn,
                        lambda: _segment_sum_numpy(values, seg_ids, n_seg))


def segment_max(values, seg_ids, n_seg: int,
                backend: str | None = None) -> np.ndarray:
    """Per-segment maximum (0.0 for empty segments, matching the stacked
    contention reduction where all inputs are non-negative byte counts).
    Device-backend failures degrade to the numpy reference via
    :func:`device_guard` (site ``kernel.segment_reduce``)."""
    if backend in (None, "auto"):
        backend = resolve_backend("auto", n_values=_size_of(seg_ids))
    if backend == "numpy":
        return _segment_max_numpy(values, seg_ids, n_seg)
    if backend == "pallas":
        return fused_segment_reduce(values, seg_ids, n_seg)[1]

    def device_fn():
        import jax.numpy as jnp
        _, seg_max = _jax_segment_ops()
        out = np.asarray(seg_max(_as_device(values, jnp.float32),
                                 _as_device(seg_ids, jnp.int32), n_seg),
                         dtype=np.float64)
        out[np.isneginf(out)] = 0.0
        return out

    return device_guard("kernel.segment_reduce", backend, device_fn,
                        lambda: _segment_max_numpy(values, seg_ids, n_seg))


# -- device Fenwick queue walk -----------------------------------------------

def _queue_layout(posted, arrival, bounds):
    """Host-side layout for the lock-step Fenwick sweep (mirrors the numpy
    reference in :func:`repro.comm.primitives.batched_queue_traversal_steps`
    exactly: same private-tree packing, same initial tree contents)."""
    from repro.comm.primitives import segmented_arange

    posted = np.asarray(posted, dtype=np.int64)
    arrival = np.asarray(arrival, dtype=np.int64)
    bounds = np.asarray(bounds, dtype=np.int64)
    N = int(posted.size)
    starts = bounds[:-1]
    counts = np.diff(bounds)
    region_of = np.repeat(np.arange(counts.size), counts)
    start_of = starts[region_of]
    pos = np.empty(N, dtype=np.int64)
    pos[start_of + posted] = np.arange(N) - start_of
    b = pos[start_of + arrival]                       # slot of j-th arrival
    span = np.ones(counts.size, dtype=np.int64)
    while (span < counts).any():
        span = np.where(span < counts, span * 2, span)
    blk = span + 1
    toff = np.concatenate([[0], np.cumsum(blk)])
    tree = np.zeros(toff[-1] + 1, dtype=np.int64)     # +1: shared sink
    li = segmented_arange(blk)
    c_rep = np.repeat(counts, blk)
    lo = li - (li & -li)
    tree[:-1] = np.minimum(li, c_rep) - np.minimum(lo, c_rep)
    depth = int(span.max(initial=1)).bit_length()
    rounds = int(counts.max(initial=0))
    return tree, b, starts, counts, toff[:-1], span, depth, rounds


@functools.cache
def _jax_queue_walk(depth: int):
    """Jitted lock-step Fenwick sweep: all rounds in one ``fori_loop``, no
    host round-trip between rounds.  ``depth`` (the per-round chain length)
    is static and unrolled; shapes retrace per arena layout.  This is the
    queue walk of both device backends: XLA lowers its per-lane gathers and
    scatters on every device, Mosaic does not."""
    import jax
    import jax.numpy as jnp

    def walk(tree, b, starts, counts, toff, span, rounds):
        sink = tree.shape[0] - 1
        steps0 = jnp.zeros(b.shape, dtype=tree.dtype)

        def round_body(j, state):
            tree, steps = state
            mask = counts > j
            s = jnp.where(mask, starts + j, 0)
            p = jnp.where(mask, b[s] + 1, 0)
            # prefix: maskless gathers (a chain that reaches 0 keeps
            # reading its region's always-zero root)
            i = p
            acc = jnp.zeros_like(p)
            for _ in range(depth):
                acc = acc + tree[toff + i]
                i = i - (i & -i)
            steps = steps.at[s].add(jnp.where(mask, acc, 0))
            # removal: chains past the region span (and inactive regions)
            # park at the shared sink slot, which is never read
            i = p
            bound = jnp.where(mask, span, -1)
            idx = jnp.where(mask, toff + i, sink)
            delta = jnp.where(mask, -1, 0).astype(tree.dtype)
            for _ in range(depth):
                tree = tree.at[idx].add(delta)
                i = i + (i & -i)
                idx = jnp.where(i > bound, sink, toff + i)
            return tree, steps

        _, steps = jax.lax.fori_loop(0, rounds, round_body, (tree, steps0))
        return steps

    return jax.jit(walk)


def queue_walk(posted, arrival, bounds, backend: str | None = None) -> np.ndarray:
    """Batched receive-queue walk lengths on the chosen backend.

    Same contract as
    :func:`repro.comm.primitives.batched_queue_traversal_steps` (region
    ``r`` owns slots ``bounds[r]:bounds[r+1]`` of ``posted``/``arrival``;
    returns per-arrival steps in the same layout).  The walk is integer
    work, so every backend is bit-equal to the numpy reference — the device
    path runs all rounds in one program instead of one host-synced array
    pass per round.  ``'jax'`` and ``'pallas'`` share that program
    (:func:`_jax_queue_walk`).  Index arithmetic runs in int32 on device
    (arenas beyond 2^31 - 1 queue slots must use numpy).  Device-backend
    failures degrade to the numpy reference via :func:`device_guard`
    (site ``kernel.queue_walk``) — bit-identically, since the walk is
    integer work.
    """
    if backend in (None, "auto"):
        backend = resolve_backend("auto", n_values=_size_of(posted))
    else:
        backend = resolve_backend(backend)

    def numpy_fn():
        from repro.comm.primitives import batched_queue_traversal_steps
        return batched_queue_traversal_steps(posted, arrival, bounds)

    if backend == "numpy":
        return numpy_fn()

    tree, b, starts, counts, toff, span, depth, rounds = _queue_layout(
        posted, arrival, bounds)
    N = int(b.size)
    if N == 0 or rounds == 0:
        return np.zeros(N, dtype=np.int64)
    if tree.size - 1 >= np.iinfo(np.int32).max:       # pragma: no cover
        return numpy_fn()

    def device_fn():
        import jax.numpy as jnp
        walk = _jax_queue_walk(depth)
        steps = walk(jnp.asarray(tree, jnp.int32), jnp.asarray(b, jnp.int32),
                     jnp.asarray(starts, jnp.int32),
                     jnp.asarray(counts, jnp.int32),
                     jnp.asarray(toff, jnp.int32),
                     jnp.asarray(span, jnp.int32), rounds)
        return np.asarray(steps, dtype=np.int64)

    return device_guard("kernel.queue_walk", backend, device_fn, numpy_fn)


# -- deprecated one-hot era shims --------------------------------------------

#: Deprecated: the retired one-hot kernel's work ceiling.  The fused tiled
#: kernel works per (tile, chunk) pair, so no limit applies; the
#: constant is kept (with :func:`pallas_within_limit`) so external callers
#: written against the old reroute logic keep working.
PALLAS_ONE_HOT_LIMIT = 1 << 24


def pallas_within_limit(n_values: int, n_seg: int) -> bool:
    """Deprecated: always True.

    The one-hot Pallas kernel this guarded was replaced by the fused tiled
    kernel (:func:`fused_segment_reduce`), which reduces only the (segment
    tile x message chunk) pairs that overlap — there is no work ceiling and
    no jax reroute.  Warns once
    per process (via the resettable
    :class:`repro.comm.health.BackendHealth` registry), then delegates to
    the new behaviour (every size is within limit).
    """
    get_health().warn_once(
        "kernels.one_hot_deprecated",
        "pallas_within_limit/PALLAS_ONE_HOT_LIMIT are deprecated: the "
        "one-hot kernel was replaced by a fused tiled kernel with no "
        "size limit; the pallas backend now handles "
        "every request directly", category=DeprecationWarning, stacklevel=3)
    return True
