"""PhaseStack: one ragged arena for a whole sweep of CommPhases.

PR 1 removed the per-message Python loops *inside* a phase; this module
removes the per-phase loop *around* them — the third and last layer of the
vectorization ladder (messages -> phases -> sweeps).  A
:class:`PhaseStack` concatenates N bound :class:`~repro.comm.CommPhase`
objects (all bound to the *same* machine) into flat per-message arrays plus
``phase_id`` / ``offsets``, and evaluates every sweep quantity in one
segmented pass:

* per-(phase, process) transport sums and receive counts via a packed-key
  ``bincount`` (``phase_id * proc_span + proc``), reshaped dense and reduced
  per row;
* per-(phase, receiver) receive-queue traversal steps via one global
  :func:`~repro.comm.primitives.grouped_queue_steps` Fenwick sweep — all
  receivers of all phases advance in lock-step;
* link contention via a single phase-tagged routing expansion: one
  ``route_link_ids`` call for every network message of every phase, grouped
  by packed ``(phase, link, source)`` keys.

Bit-identity contract: with the default numpy backend every aggregate equals
the per-phase loop result *bit for bit*.  Packed-key ``bincount`` accumulates
weights in array order, which restricted to one phase is exactly the order
the per-phase ``bincount`` used; maxima are order-independent.  The one
reduction where numpy's algorithm depends on layout — ``ndarray.sum()``'s
pairwise summation over a phase's masked sizes — is computed per phase on
the identical contiguous slice of the stacked mask (:meth:`masked_phase_sums`,
O(n_phases) trivial slice-sums; all per-message work stays in the single
pass).

Device backends (``backend='jax' | 'pallas'``) route the packed-key
transport/contention reductions and the queue walk through
:mod:`repro.kernels.comm_stack`, with the hot per-message columns cached
device-resident on first use (one transfer per arena, not per call) and the
message pricing itself run under the backend's array namespace
(:mod:`repro.comm.xp`).  numpy (``backend=None``) is the default and the
fallback; float backend results are allclose (not bit-equal, the device
path runs float32) while queue steps are integer work and bit-equal
everywhere.

Arenas can also be built *streaming* (:meth:`PhaseStack.build_streaming`):
phases from any iterable are appended through fixed-size buffers and the
stacked phase tuple is rebuilt as zero-copy views into the arena —
bit-identical to monolithic :meth:`PhaseStack.build` without ever holding
all source phases in RAM.

Layering: numpy-only, below both consumers.  Pricing formulas stay where
they live today — :mod:`repro.core.models` turns these aggregates into
``CostBreakdown`` rows, :mod:`repro.net.simulator` into ``PhaseResult`` rows.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any

import numpy as np

from . import faults, obs
from .guard import ArenaOverflowError
from .health import get_health
from .phase import CommPhase
from .primitives import (flat_orders, group_by_receiver,
                         grouped_queue_steps, transport_times)
from .primitives import active_senders_per_node

__all__ = ["PhaseStack", "StackSimArrays", "as_stack", "STACK_BACKENDS"]

#: Allowed values for the ``backend`` kwarg (``None`` means numpy).  Mirrors
#: ``repro.kernels.comm_stack.BACKENDS`` — duplicated here so eager
#: validation never has to import the (jax-adjacent) kernels module.
STACK_BACKENDS = ("numpy", "jax", "pallas")


def as_stack(phases) -> "PhaseStack | None":
    """A PhaseStack for the sweep, or None when the per-phase loop is the
    right path (fewer than two phases, unbound arrays, mixed machines).

    The one stack-or-fallback policy shared by every batched entry point
    (:func:`repro.core.models.phase_cost_many`,
    :func:`repro.net.simulator.simulate_many`): an already-built stack
    passes through, a same-machine sweep of two or more bound phases is
    stacked, anything else signals the caller to loop phase by phase.
    """
    if isinstance(phases, PhaseStack):
        return phases
    if len(phases) < 2:
        return None
    m = getattr(phases[0], "machine", None)
    if m is None or any(getattr(ph, "machine", None) is not m
                        for ph in phases):
        return None
    return PhaseStack.build(phases)


#: Per-message arrays concatenated into the arena, in CommPhase field order.
_ARENA_FIELDS = ("src", "dst", "size", "loc", "proto", "is_net", "send_node",
                 "torus_src", "torus_dst", "active_ppn")


@dataclasses.dataclass(frozen=True)
class StackSimArrays:
    """Raw per-phase simulator aggregates (priced by ``repro.net.simulator``)."""

    transport: np.ndarray            # [N] max over procs of send-side sums
    per_proc: list[np.ndarray]       # per-phase send-side transport sums
    qsteps: list[np.ndarray]         # per-phase queue traversal steps
    max_link: np.ndarray             # [N] hottest contended-link bytes
    net_bytes: np.ndarray            # [N] total network bytes


@dataclasses.dataclass(frozen=True, eq=False)
class PhaseStack:
    """N CommPhases concatenated into one ragged arena (same machine)."""

    machine: Any                     # shared MachineSpec (duck-typed)
    phases: tuple[CommPhase, ...]
    offsets: np.ndarray              # [N+1] message offsets into the arena
    n_procs: np.ndarray              # [N] per-phase process counts
    src: np.ndarray                  # [total] — concatenated CommPhase arrays
    dst: np.ndarray
    size: np.ndarray
    loc: np.ndarray
    proto: np.ndarray
    is_net: np.ndarray
    send_node: np.ndarray
    torus_src: np.ndarray
    torus_dst: np.ndarray
    active_ppn: np.ndarray
    phase_id: np.ndarray             # [total] owning phase of each message

    @classmethod
    def build(cls, phases) -> "PhaseStack":
        """Concatenate bound phases into one arena.

        Every phase must be bound to the *same* machine object: the arena
        caches machine-derived arrays, and mixing machines would silently
        price messages with the wrong parameter tables.
        """
        phases = tuple(phases)
        for ph in phases:
            if not isinstance(ph, CommPhase):
                raise TypeError(
                    f"PhaseStack stacks bound CommPhases, got {type(ph).__name__}")
        machine = phases[0].machine if phases else None
        for ph in phases:
            if ph.machine is not machine:
                raise ValueError(
                    "mixed machines: every phase in a PhaseStack must be "
                    "bound to the same machine object (rebind with "
                    "CommPhase.build / CommPattern.bind first)")
        with obs.span("repro.plan.arena"):
            counts = np.asarray([ph.n_msgs for ph in phases], dtype=np.int64)
            offsets = np.concatenate([[0], np.cumsum(counts)]).astype(
                np.int64)
            cat = {f: (np.concatenate([getattr(ph, f) for ph in phases])
                       if phases else np.zeros(0))
                   for f in _ARENA_FIELDS}
            return cls(
                machine=machine, phases=phases, offsets=offsets,
                n_procs=np.asarray([ph.n_procs for ph in phases],
                                   dtype=np.int64),
                phase_id=np.repeat(np.arange(len(phases), dtype=np.int64),
                                   counts),
                **cat)

    @classmethod
    def build_streaming(cls, phases, chunk_msgs: int = 1 << 16) -> "PhaseStack":
        """Stream bound phases into an arena through fixed-size buffers.

        ``phases`` is any *iterable* of bound CommPhases — a generator is
        the point: each phase can be produced, copied into the staging
        buffer and dropped before the next one exists, so arena setup never
        needs all source phases in RAM at once.  Per-message columns are
        appended into ``chunk_msgs``-sized staging buffers; a full buffer is
        sealed into a chunk block, and each column is concatenated exactly
        once at the end.  Peak extra memory is one chunk plus the sealed
        blocks (which together are the arena), instead of every source
        phase's arrays *plus* the arena.

        The stacked ``phases`` tuple is rebuilt as zero-copy views: each
        entry is a CommPhase whose arrays are slices of the arena columns.
        The result is **bit-identical** to monolithic :meth:`build` for
        every chunk size — a concatenation of chunk blocks is the same
        array as a concatenation of per-phase columns, and every derived
        aggregate reduces the same arena.
        """
        chunk_msgs = int(chunk_msgs)
        if chunk_msgs < 1:
            raise ValueError(f"chunk_msgs must be >= 1, got {chunk_msgs}")
        machine = None
        counts: list[int] = []
        n_procs: list[int] = []
        overridden: list[bool] = []
        dtypes: dict[str, Any] = {}
        blocks: dict[str, list] = {f: [] for f in _ARENA_FIELDS}
        buf: dict[str, np.ndarray] = {}
        fill = 0

        def seal():
            nonlocal fill
            if fill:
                for f in _ARENA_FIELDS:
                    blocks[f].append(buf[f][:fill].copy())
            fill = 0

        for ph in phases:
            if not isinstance(ph, CommPhase):
                raise TypeError(
                    f"PhaseStack stacks bound CommPhases, got {type(ph).__name__}")
            if not counts:
                machine = ph.machine
                dtypes = {f: getattr(ph, f).dtype for f in _ARENA_FIELDS}
            elif ph.machine is not machine:
                raise ValueError(
                    "mixed machines: every phase in a PhaseStack must be "
                    "bound to the same machine object (rebind with "
                    "CommPhase.build / CommPattern.bind first)")
            counts.append(ph.n_msgs)
            n_procs.append(ph.n_procs)
            overridden.append(ph.loc_overridden)
            if not buf and ph.n_msgs:
                buf = {f: np.empty(chunk_msgs, dtype=dtypes[f])
                       for f in _ARENA_FIELDS}
            taken = 0
            while taken < ph.n_msgs:
                step = min(chunk_msgs - fill, ph.n_msgs - taken)
                for f in _ARENA_FIELDS:
                    buf[f][fill:fill + step] = \
                        getattr(ph, f)[taken:taken + step]
                fill += step
                taken += step
                if fill == chunk_msgs:
                    seal()
        seal()
        counts_a = np.asarray(counts, dtype=np.int64)
        offsets = np.concatenate([[0], np.cumsum(counts_a)]).astype(np.int64)
        cat = {f: (np.concatenate(blocks[f]) if blocks[f]
                   else np.zeros(0, dtype=dtypes[f]) if dtypes
                   else np.zeros(0))
               for f in _ARENA_FIELDS}
        views = tuple(
            CommPhase(machine=machine, n_procs=int(n_procs[i]),
                      loc_overridden=bool(overridden[i]),
                      **{f: cat[f][offsets[i]:offsets[i + 1]]
                         for f in _ARENA_FIELDS})
            for i in range(len(counts)))
        return cls(
            machine=machine, phases=views, offsets=offsets,
            n_procs=np.asarray(n_procs, dtype=np.int64),
            phase_id=np.repeat(np.arange(len(counts), dtype=np.int64),
                               counts_a),
            **cat)

    # -- basic stats --------------------------------------------------------
    @property
    def n_phases(self) -> int:
        return len(self.phases)

    @property
    def total_msgs(self) -> int:
        return int(self.offsets[-1]) if self.offsets.size else 0

    def __len__(self) -> int:
        return self.n_phases

    def __iter__(self):
        return iter(self.phases)

    # cached_property writes straight to __dict__, bypassing the frozen
    # dataclass __setattr__ — all of these are derived state, computed once
    # per stack and reused by every sweep over it (ladder levels, strategy
    # candidates, repeated simulations).
    @functools.cached_property
    def proc_span(self) -> int:
        """Column span of the dense per-(phase, process) layouts."""
        return int(max(self.n_procs.max(initial=0),
                       self.src.max(initial=-1) + 1,
                       self.dst.max(initial=-1) + 1, 1))

    @functools.cached_property
    def _src_key(self) -> np.ndarray:
        """Packed (phase, sender) key of every message."""
        return self.phase_id * self.proc_span + self.src

    @functools.cached_property
    def _dst_key(self) -> np.ndarray:
        """Packed (phase, receiver) key of every message."""
        return self.phase_id * self.proc_span + self.dst

    @functools.cached_property
    def _recv_counts(self) -> np.ndarray:
        """Dense [n_phases, proc_span] receive counts (level-independent)."""
        return np.bincount(self._dst_key,
                           minlength=self.n_phases * self.proc_span).reshape(
            self.n_phases, self.proc_span)

    @functools.cached_property
    def _receiver_groups(self) -> tuple[np.ndarray, np.ndarray]:
        """Stable grouping of messages by packed (phase, receiver) slot."""
        return group_by_receiver(self._dst_key,
                                 self.n_phases * self.proc_span)

    @functools.cached_property
    def _net_bytes(self) -> np.ndarray:
        """Per-phase network bytes under the machine's own locality tables."""
        return self.masked_phase_sums(self.size, self.is_net)

    @functools.cached_property
    def _class_bytes(self) -> np.ndarray:
        """Dense [n_phases, n_locality] byte sums by locality class — the
        packed-key bincount with the *class* axis in place of the process
        axis.  Restricted to one phase the accumulation order is the
        per-phase ``CommPhase.class_bytes`` order, so rows are bit-identical
        to the loop."""
        L = self.machine.params.n_locality
        return np.bincount(self.phase_id * L + self.loc, weights=self.size,
                           minlength=self.n_phases * L).reshape(
            self.n_phases, L)

    def class_bytes(self) -> np.ndarray:
        """Per-phase payload bytes per locality class ([n_phases,
        n_locality]) — one packed-key pass over the arena, row ``i``
        bit-identical to ``phases[i].class_bytes()``.  The class-axis view
        the hetero benches and examples report (how much traffic rides each
        rate-table row)."""
        return self._class_bytes

    @functools.cached_property
    def _machine_tables(self) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
        """(alpha, Rb, RN) indexed per message with the machine's own
        parameter tables — shared by the simulator and every node-aware
        ladder level priced against the ground truth."""
        p = self.machine.params
        return (p.alpha[self.loc, self.proto], p.Rb[self.loc, self.proto],
                p.RN[self.loc, self.proto])

    @functools.cached_property
    def _machine_t_msg(self) -> np.ndarray:
        """Max-rate transport time of every message under the machine's own
        tables — the quantity the simulator and the node-aware ladder levels
        both price (identical inputs, so one cached pass serves both)."""
        alpha, Rb, RN = self._machine_tables
        return transport_times(self.size, alpha, Rb, RN, self.active_ppn,
                               self.is_net,
                               rails=self.machine.params.n_rails)

    @functools.cached_property
    def _machine_transport(self) -> np.ndarray:
        """Dense per-(phase, process) sums of :attr:`_machine_t_msg`.

        Pinned to the numpy backend: the cache stays bit-exact whatever
        backend a caller prices with.
        """
        return self._phase_proc_sums(self._machine_t_msg, self._src_key,
                                     backend="numpy")

    @functools.cached_property
    def _ladder_cache(self) -> dict:
        """Dense transport matrices per (node_aware, use_maxrate) flag pair,
        for pricing against the machine's own tables (numpy backend).  Like
        every cached property here these are pure functions of the arena:
        binding once and sweeping many times — fitting loops, strategy scans,
        repeated ladders — amortizes the message-pricing passes away."""
        return {}

    # -- backend resolution --------------------------------------------------
    @staticmethod
    def _backend(backend):
        """Resolve a backend name to ('numpy', None) or (name, kernels mod).

        ``None`` means numpy.  Validation is eager and happens *here*,
        before any reduction runs: an unknown name raises a ``ValueError``
        naming the allowed values, instead of failing deep inside a
        segmented pass.  A device backend that cannot run (no jax, or
        quarantined) resolves to numpy
        (:func:`repro.kernels.comm_stack.resolve_backend`).
        """
        if backend is None or backend == "numpy":
            return "numpy", None
        if backend not in STACK_BACKENDS:
            raise ValueError(
                f"unknown stack backend {backend!r} (from the backend "
                f"argument); allowed values: {STACK_BACKENDS}")
        from repro.kernels import comm_stack   # lazy: keeps comm numpy-only
        backend = comm_stack.resolve_backend(backend)
        return backend, (None if backend == "numpy" else comm_stack)

    # -- device-resident columns --------------------------------------------
    @functools.cached_property
    def _device_store(self) -> dict:
        """Device (jax) copies of arena columns, by attribute name — filled
        lazily by :meth:`_dev`, so a device-backed sweep transfers each hot
        column once per arena instead of once per call."""
        return {}

    def _dev(self, name):
        """The named per-message column as a cached device array (float64
        columns go over as float32, int64 keys as int32 — the device
        contract is allclose/float32 for floats and exact for keys).

        An arena whose keys exceed int32 raises the typed
        :class:`repro.comm.guard.ArenaOverflowError` — callers inside the
        degradation contract (:meth:`cost_arrays` / :meth:`sim_arrays`)
        catch it and price the arena on the numpy path with a warn-once
        instead of crashing the sweep.
        """
        store = self._device_store
        if name not in store:
            from repro.kernels.comm_stack import to_device
            faults.fail_point("stack.device_store")
            a = np.asarray(getattr(self, name))
            if a.dtype == np.float64:
                a = a.astype(np.float32)
            elif a.dtype == np.int64:
                if a.size and (a.max() >= 2 ** 31 or a.min() < -2 ** 31):
                    raise ArenaOverflowError(
                        f"arena column {name!r} exceeds int32 range; such "
                        "arenas price on the numpy backend")
                a = a.astype(np.int32)
            store[name] = to_device(a)
        return store[name]

    # -- segmented reductions -----------------------------------------------
    def _phase_proc_sums(self, values, key, backend=None) -> np.ndarray:
        """Dense [n_phases, proc_span] sums of ``values`` by a packed
        (phase, process) key (``_src_key`` / ``_dst_key``)."""
        n = self.n_phases * self.proc_span
        backend, mod = self._backend(backend)
        if mod is None:
            dense = np.bincount(key, weights=values, minlength=n)
        else:
            dense = mod.segment_sum(values, key, n, backend=backend)
        return dense.reshape(self.n_phases, self.proc_span)

    def masked_phase_sums(self, values, mask) -> np.ndarray:
        """Per-phase ``values[mask].sum()`` with the loop path's exact
        floating-point result: each phase's masked elements form a contiguous
        slice of the stacked mask selection, and ``ndarray.sum()`` on that
        slice replays the identical pairwise-summation tree.  O(n_phases)
        trivial slice-sums; the selection itself is one vectorized pass."""
        picked = np.asarray(values)[mask]
        pid = self.phase_id[mask]
        bounds = np.searchsorted(pid, np.arange(self.n_phases + 1))
        return np.asarray([picked[bounds[i]:bounds[i + 1]].sum()
                           for i in range(self.n_phases)])

    # -- model-side aggregates ----------------------------------------------
    def cost_arrays(self, params=None, *, node_aware: bool = True,
                    use_maxrate: bool = True, with_queue: bool = True,
                    with_net_bytes: bool = True, backend=None):
        """Aggregates behind the model ladder, one segmented pass each.

        Returns ``(transport[N], max_recv[N], net_bytes[N])``: the worst
        per-process send-side transport sum, the worst per-process receive
        count (0s when ``with_queue=False``) and the total network-class
        bytes (0s when ``with_net_bytes=False``) of every phase.  ``params``
        substitutes a fitted table for the machine's own; ``node_aware`` /
        ``use_maxrate`` select the ladder rung's transport formula;
        ``backend`` routes the pricing and segmented reductions through
        :mod:`repro.kernels.comm_stack` (``'jax'``/``'pallas'`` run
        device-resident off the cached column store; ``None`` is numpy).
        :func:`repro.core.models.phase_cost_many` prices them into
        ``CostBreakdown`` rows bit-identical to the per-phase loop.
        """
        N = self.n_phases
        zeros = np.zeros(N)
        if N == 0 or self.total_msgs == 0:
            return zeros, zeros.copy(), zeros.copy()
        m = self.machine
        p = params if params is not None else m.params
        same_net = p.network_locality == m.params.network_locality
        backend_name, mod = self._backend(backend)
        flags = (node_aware, use_maxrate)
        cacheable = p is m.params and backend_name == "numpy"
        if cacheable and flags in self._ladder_cache:
            dense = self._ladder_cache[flags]
        else:
            if node_aware and use_maxrate and cacheable:
                # ground-truth node-aware pricing: the pass shared with the
                # simulator (identical inputs, identical result)
                dense = self._machine_transport
            else:
                # device path: columns cached resident, tables indexed and
                # the formula priced on device, one transfer of the reduced
                # dense matrix back.  A device failure (None) degrades to
                # the numpy pricing path — the sweep never crashes on a
                # backend fault (DESIGN.md §12).
                dense = (self._device_dense_guarded(
                             p, node_aware, use_maxrate, backend_name, mod,
                             same_net)
                         if mod is not None else None)
                if dense is None:
                    dense = self._numpy_dense_for(p, node_aware, use_maxrate,
                                                  same_net)
            if cacheable:
                self._ladder_cache[flags] = dense
        transport = dense.max(axis=1)
        max_recv = (self._recv_counts.max(axis=1).astype(np.float64)
                    if with_queue else zeros.copy())
        if not with_net_bytes:
            net_bytes = zeros.copy()
        elif node_aware and same_net:
            net_bytes = self._net_bytes        # cached machine classification
        elif node_aware:
            net_bytes = self.masked_phase_sums(self.size,
                                               self.loc >= p.network_locality)
        else:                                  # every message is network-class
            net_bytes = self.masked_phase_sums(
                self.size, np.ones(self.total_msgs, dtype=bool))
        return np.asarray(transport, dtype=np.float64), max_recv, net_bytes

    def _active_ppn_for(self, params) -> np.ndarray:
        """Cached active-sender counts, or a stacked recompute when an
        override params table reclassifies localities (the per-(phase, node)
        grouping rides on phase-offset node ids)."""
        if params.network_locality == self.machine.params.network_locality:
            return self.active_ppn
        node_span = int(self.send_node.max(initial=-1)) + 1
        return active_senders_per_node(
            self.src, self.phase_id * node_span + self.send_node,
            self.loc >= params.network_locality)

    def _numpy_cost_dense(self, p, node_aware, use_maxrate,
                          same_net) -> np.ndarray:
        """The ladder transport matrix priced on the host — the bit-identity
        numpy reference the device path degrades to."""
        m = self.machine
        # protocol classes depend on size thresholds only: the
        # machine-table classification is already cached
        proto = self.proto if p is m.params else p.protocol_of(self.size)
        if node_aware:
            if p is m.params:
                alpha, Rb, RN = self._machine_tables
            else:
                alpha = p.alpha[self.loc, proto]
                Rb = p.Rb[self.loc, proto]
                RN = p.RN[self.loc, proto] if use_maxrate else None
            is_net = (self.is_net if same_net
                      else self.loc >= p.network_locality)
        else:
            # loc collapses to the network class: index the table
            # rows by protocol only (== full_like(loc, nl) indexing)
            nl = p.network_locality
            alpha = p.alpha[nl][proto]
            Rb = p.Rb[nl][proto]
            RN = p.RN[nl][proto] if use_maxrate else None
            is_net = np.ones(self.total_msgs, dtype=bool)
        if use_maxrate:
            t_msg = transport_times(self.size, alpha, Rb, RN,
                                    self._active_ppn_for(p), is_net,
                                    rails=p.n_rails)
        else:
            t_msg = transport_times(self.size, alpha, Rb, None, 1.0,
                                    False, use_maxrate=False)
        return self._phase_proc_sums(t_msg, self._src_key, backend="numpy")

    def _numpy_dense_for(self, p, node_aware, use_maxrate,
                         same_net) -> np.ndarray:
        """The numpy reference dense matrix for a ladder configuration —
        the cached machine pass when it applies, the host pricing path
        otherwise.  Both the degradation fallback and the
        ``REPRO_STACK_VERIFY=parity`` reference for the device pricing."""
        if node_aware and use_maxrate and p is self.machine.params:
            return self._machine_transport
        return self._numpy_cost_dense(p, node_aware, use_maxrate, same_net)

    def _device_dense_guarded(self, p, node_aware, use_maxrate, backend_name,
                              mod, same_net) -> np.ndarray | None:
        """:meth:`_device_cost_dense` under the degradation contract.

        The ``stack.device_store`` injection site covers the whole device
        pricing pass (column shipping via :meth:`_dev` has its own
        fail-point inside).  Any failure — an injected fault, an
        :class:`repro.comm.guard.ArenaOverflowError` from an oversized
        arena, a compile error, a ``REPRO_STACK_VERIFY`` rejection — is
        recorded in :class:`repro.comm.health.BackendHealth` (warn-once,
        quarantine accounting) and returns None; the caller prices on the
        numpy path instead.
        """
        from repro.kernels import comm_stack as cs
        health = get_health()
        if health.is_quarantined(backend_name):
            return None
        obs.count("device.calls.stack.device_store")
        try:
            with obs.span("repro.device.stack.device_store"):
                dense = faults.poison(
                    "stack.device_store",
                    self._device_cost_dense(p, node_aware, use_maxrate,
                                            backend_name, mod, same_net))
                mode = cs.verify_mode()
                if mode == "finite":
                    cs._check_finite(dense)
                elif mode == "parity":
                    cs._check_parity(dense, self._numpy_dense_for(
                        p, node_aware, use_maxrate, same_net))
        except Exception as e:  # noqa: BLE001 - degradation catches all
            health.record_failure(backend_name, "stack.device_store", e)
            return None
        health.record_success(backend_name)
        return dense

    def _device_cost_dense(self, p, node_aware, use_maxrate, backend_name,
                           mod, same_net) -> np.ndarray:
        """Ladder transport matrix priced end-to-end on device.

        The cached device columns (:meth:`_dev`) supply the per-message
        inputs, the (tiny) locality x protocol parameter tables are shipped
        once and indexed on device, :func:`transport_times` runs under the
        backend's array namespace and the packed-key reduction consumes the
        device values directly — the only host transfer per call is the
        reduced dense ``[n_phases, proc_span]`` matrix.
        """
        import jax.numpy as jnp

        from repro.kernels.comm_stack import to_device

        from .xp import get_xp
        xp = get_xp(backend_name)
        m = self.machine
        proto = (self._dev("proto") if p is m.params
                 else to_device(p.protocol_of(self.size).astype(np.int32)))
        at = to_device(np.asarray(p.alpha, dtype=np.float32))
        rb = to_device(np.asarray(p.Rb, dtype=np.float32))
        rn = to_device(np.asarray(p.RN, dtype=np.float32))
        if node_aware:
            loc = self._dev("loc")
            alpha, Rb, RN = at[loc, proto], rb[loc, proto], rn[loc, proto]
            is_net = (self._dev("is_net") if same_net
                      else loc >= p.network_locality)
        else:
            nl = p.network_locality
            alpha, Rb, RN = at[nl, proto], rb[nl, proto], rn[nl, proto]
            is_net = jnp.ones(self.total_msgs, dtype=bool)
        if use_maxrate:
            if p.network_locality == m.params.network_locality:
                ppn = self._dev("active_ppn")
            else:
                ppn = to_device(self._active_ppn_for(p).astype(np.float32))
            t_msg = transport_times(self._dev("size"), alpha, Rb, RN, ppn,
                                    is_net, rails=p.n_rails, xp=xp)
        else:
            t_msg = transport_times(self._dev("size"), alpha, Rb, None, 1.0,
                                    False, use_maxrate=False, xp=xp)
        n = self.n_phases * self.proc_span
        dense = mod.segment_sum(t_msg, self._dev("_src_key"), n,
                                backend=backend_name)
        return dense.reshape(self.n_phases, self.proc_span)

    # -- per-rail byte counters ---------------------------------------------
    def rail_bytes(self, n_rails: int | None = None) -> np.ndarray:
        """Dense ``[n_phases, n_rails]`` injected network bytes per NIC rail.

        The measurement-side counter behind multi-rail fitting
        (:func:`repro.core.fitting.fit_rails`): each network-class message —
        the same selection the routing expansion routes — is charged to its
        sender's rail ``src % n_rails``, the static round-robin NIC binding
        the max-rate rail model assumes.  One packed-key bincount
        (``phase * n_rails + rail``).  ``n_rails`` defaults to the machine
        table's own ``CommParams.n_rails``.
        """
        r = int(n_rails) if n_rails is not None else \
            int(self.machine.params.n_rails)
        if r < 1:
            raise ValueError(f"n_rails must be >= 1, got {r}")
        key = self.phase_id * r + self.src % r
        w = np.where(self.is_net, self.size, 0.0)
        return np.bincount(key, weights=w,
                           minlength=self.n_phases * r).reshape(
            self.n_phases, r)

    # -- receive-queue accounting -------------------------------------------
    def queue_steps_many(self, recv_post_orders=None,
                         arrival_orders=None, backend=None) -> np.ndarray:
        """Dense [n_phases, proc_span] exact queue traversal-step totals.

        ``recv_post_orders[i]`` / ``arrival_orders[i]`` are phase ``i``'s
        per-receiver order dicts (phase-local message indices, exactly what
        :meth:`CommPhase.queue_steps` takes).  All phases' custom receivers
        run in ONE batched walk: on numpy a lock-step Fenwick sweep whose
        rounds are the *max* messages-per-receiver over the whole stack,
        not the per-phase sum.  ``backend`` selects where the walk runs —
        the device walk (:func:`repro.kernels.comm_stack.queue_walk`)
        counts every receiver's steps as dominance counts in one program
        and, being integer work, is *bit-equal* to numpy.
        """
        P = self.proc_span
        backend_name, _ = self._backend(backend)
        qsteps = grouped_queue_steps(
            self._dst_key, self.n_phases * P,
            recv_post_order=self._flatten_orders(recv_post_orders),
            arrival_order=self._flatten_orders(arrival_orders),
            groups=self._receiver_groups,
            describe=lambda s: f"receiver {s % P} of phase {s // P}",
            backend=backend_name)
        return qsteps.reshape(self.n_phases, P)

    def _flatten_orders(self, per_phase):
        """Merge per-phase order specs (dicts or flat ``(slots, lens, ids)``
        tuples of phase-local values) into one stack-wide flat spec: slots
        become packed ``(phase, receiver)`` keys, ids become arena indices.
        Pure array concatenation — no per-receiver work for flat inputs."""
        if per_phase is None:
            return None
        P = self.proc_span
        slot_parts, len_parts, id_parts = [], [], []
        for i, d in enumerate(per_phase):
            flat = flat_orders(d)
            if flat is None:
                continue
            slots, lens, ids = flat
            if slots.size and (slots[0] < 0 or slots[-1] >= P):
                keep = (slots >= 0) & (slots < P)   # mirror per-phase filter
                sel = np.repeat(keep, lens)
                slots, lens, ids = slots[keep], lens[keep], ids[sel]
            slot_parts.append(i * P + slots)
            len_parts.append(lens)
            id_parts.append(ids + self.offsets[i])
        if not slot_parts:
            return None
        return (np.concatenate(slot_parts), np.concatenate(len_parts),
                np.concatenate(id_parts))

    # -- link contention ----------------------------------------------------
    @functools.cached_property
    def _link_contention(self) -> tuple[np.ndarray, np.ndarray]:
        """Cached numpy-backend :meth:`link_contention_many` — the routing
        expansion depends only on the arena, never on receive orders, so
        repeated simulations of a bound stack reuse it.  Pinned to numpy so
        a device backend cannot poison the bit-exact cache."""
        return self._compute_link_contention("numpy")

    def link_contention_many(self, backend=None):
        """(hottest contended-link bytes, total network bytes) per phase;
        ``backend`` selects the reduction backend (numpy default, cached).

        One phase-tagged routing expansion: every inter-torus-unit network
        message of every phase is routed dimension-ordered in a single
        ``route_link_ids`` call, grouped by packed ``(phase, link, source)``
        keys.  Per ``(phase, link)``, bytes beyond the largest single-source
        contribution count as contention, exactly like
        :meth:`CommPhase.link_contention` — and bit-identically so: within a
        phase the packed keys sort and accumulate in the per-phase order.
        """
        backend_name, _ = self._backend(backend)
        if backend_name == "numpy":
            return self._link_contention
        return self._compute_link_contention(backend_name)

    def _compute_link_contention(self, backend):
        net_bytes = self._net_bytes
        out = np.zeros(self.n_phases)
        with obs.span("repro.sim.routing"):
            sel = self.is_net & (self.torus_src != self.torus_dst)
            if not sel.any():
                return out, net_bytes
            torus = self.machine.torus
            tsrc = self.torus_src[sel]
            pid = self.phase_id[sel]
            midx, link = torus.route_link_ids(tsrc, self.torus_dst[sel])
            if link.size == 0:
                return out, net_bytes
            w = self.size[sel][midx]
            src_span = np.int64(max(torus.size, int(tsrc.max()) + 1))
            link_span = np.int64(torus.link_slots)
            if self.n_phases * int(link_span) * int(src_span) >= 2 ** 62:
                raise ValueError(
                    "packed (phase, link, source) key would overflow int64; "
                    "split the sweep into smaller stacks")
            key = (pid[midx] * link_span + link) * src_span + tsrc[midx]
            uk, inv = np.unique(key, return_inverse=True)
            per_src = np.bincount(inv, weights=w)  # bytes/(phase, link, src)
            pair = uk // src_span                  # (phase, link) runs
            starts = np.nonzero(np.r_[True, pair[1:] != pair[:-1]])[0]
        backend, mod = self._backend(backend)
        if mod is None:
            totals = np.add.reduceat(per_src, starts)
            largest = np.maximum.reduceat(per_src, starts)
        else:
            lens = np.diff(np.r_[starts, per_src.size])
            seg = np.repeat(np.arange(starts.size), lens)
            if backend == "pallas":
                # the contention reduction needs both aggregates: one fused
                # launch returns (sums, maxima) together
                totals, largest = mod.fused_segment_reduce(per_src, seg,
                                                           starts.size)
            else:
                totals = mod.segment_sum(per_src, seg, starts.size,
                                         backend=backend)
                largest = mod.segment_max(per_src, seg, starts.size,
                                          backend=backend)
        run_phase = (pair[starts] // link_span).astype(np.int64)
        np.maximum.at(out, run_phase, totals - largest)
        return out, net_bytes

    # -- simulator-side aggregates ------------------------------------------
    def sim_arrays(self, recv_post_orders=None, arrival_orders=None,
                   backend=None) -> StackSimArrays:
        """Raw simulator aggregates for the whole stack, one pass each.

        ``recv_post_orders[i]`` / ``arrival_orders[i]`` are phase ``i``'s
        receive-order specs (as in :meth:`queue_steps_many`); ``backend``
        selects the reduction backend.
        :func:`repro.net.simulator.simulate_many` prices them into
        ``PhaseResult`` rows bit-identical to per-phase :func:`simulate`
        (numpy backend); phases with zero messages get the empty per-proc
        arrays the per-phase early return produces.
        """
        if self.n_phases == 0:
            z = np.zeros(0)
            return StackSimArrays(z, [], [], z.copy(), z.copy())
        backend_name, mod = self._backend(backend)
        if backend_name == "numpy":
            dense = self._machine_transport    # cached, shared with the model
        else:
            # device failures degrade to the cached numpy machine pass
            # (bit-identical) instead of crashing the simulation
            dense = self._device_dense_guarded(self.machine.params, True,
                                               True, backend_name, mod, True)
            if dense is None:
                dense = self._machine_transport
        qdense = self.queue_steps_many(recv_post_orders, arrival_orders,
                                       backend=backend_name)
        max_link, net_bytes = self.link_contention_many(backend=backend_name)
        counts = np.diff(self.offsets)
        empty_f = np.zeros(0)
        empty_i = np.zeros(0, dtype=qdense.dtype)
        per_proc = [dense[i, :self.n_procs[i]].copy() if counts[i] else empty_f
                    for i in range(self.n_phases)]
        qsteps = [qdense[i, :self.n_procs[i]].copy() if counts[i] else empty_i
                  for i in range(self.n_phases)]
        return StackSimArrays(
            transport=np.asarray(dense.max(axis=1), dtype=np.float64),
            per_proc=per_proc, qsteps=qsteps,
            max_link=max_link, net_bytes=net_bytes)
