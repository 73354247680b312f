"""Event-level communication simulator (the "measured" side of the paper).

For one communication *phase* (a set of point-to-point messages that are all
posted, then all completed — e.g. one SpMV halo exchange or one direction of a
HighVolumePingPong):

* every message is priced with the machine's ground-truth node-aware
  parameters, with node-injection saturation computed from the *actual* number
  of actively-sending processes per node (the max-rate mechanism, mechanistic);
* the MPI receive queue is simulated: each process posts receives in a given
  order, envelopes arrive in network order, and every arrival walks the posted
  queue until it matches — traversal steps are counted exactly (Fenwick tree,
  batched across all receiving processes) and priced at gamma per step;
* network messages are routed dimension-ordered over the torus in one
  vectorized segment expansion; per-link byte counters feed a contention
  penalty of delta * (hottest-link contended bytes).

All hot paths are thin layers over the shared engine in :mod:`repro.comm`:
:class:`repro.comm.CommPhase` caches locality / protocol / routing endpoints /
active-sender counts once, and the same primitives also feed the closed-form
model of :mod:`repro.core.models`, which must predict these outputs across the
same inferential gap the paper has between model and machine (cube-partition
estimate vs real routing, n^2 upper bound vs actual traversal).
"""
from __future__ import annotations

import dataclasses

import numpy as np

from repro.comm import ARENA_TYPES as _ARENAS
from repro.comm import obs
from repro.comm import CommPhase, PhaseStack
from repro.comm.stack import as_stack
from repro.comm.primitives import (per_proc_sums, queue_traversal_steps,
                                   transport_times)

from .machine import MachineSpec

__all__ = ["PhaseResult", "SequenceResult", "simulate", "simulate_phase",
           "simulate_many", "simulate_sequence", "queue_traversal_steps"]


@dataclasses.dataclass
class PhaseResult:
    time: float                      # modeled wall time of the phase (seconds)
    transport: float                 # max over procs of send-side transport
    queue: float                     # gamma * steps, worst process
    contention: float                # delta * hottest-link bytes
    per_proc_transport: np.ndarray
    per_proc_queue_steps: np.ndarray
    max_link_bytes: float
    total_net_bytes: float


def simulate(phase: CommPhase,
             recv_post_order: dict[int, np.ndarray] | None = None,
             arrival_order: dict[int, np.ndarray] | None = None,
             rng: np.random.Generator | None = None,
             noise: float = 0.0) -> PhaseResult:
    """Simulate one prebuilt :class:`CommPhase`.

    ``recv_post_order[p]`` / ``arrival_order[p]``: permutations of the indices
    (into src/dst/size) of messages destined to process ``p``, giving the
    order receives are posted and envelopes arrive.  Default: array order for
    both (best case, O(n) queue cost).

    ``noise`` multiplies the total by a lognormal factor drawn from ``rng``.
    The generator is owned by the *sweep*: create it once (e.g.
    ``np.random.default_rng(seed)``) and thread it through every call, as
    :func:`simulate_many` and the ping-pong harnesses do — a per-call default
    would re-seed on every call and make repeated noisy calls draw identical
    noise.
    """
    if noise > 0.0 and rng is None:
        raise ValueError(
            "noise > 0 needs an explicit rng, created once at the sweep "
            "level (a per-call default would redraw the same noise); "
            "simulate_many seeds np.random.default_rng(0) for you")
    if phase.n_msgs == 0:
        z = np.zeros(0)
        return PhaseResult(0.0, 0.0, 0.0, 0.0, z, z, 0.0, 0.0)
    params = phase.machine.params

    # --- max-rate transport: actual active senders per node ----------------
    alpha = params.alpha[phase.loc, phase.proto]
    Rb = params.Rb[phase.loc, phase.proto]
    RN = params.RN[phase.loc, phase.proto]
    t_msg = transport_times(phase.size, alpha, Rb, RN, phase.active_ppn,
                            phase.is_net, rails=params.n_rails)
    per_proc = per_proc_sums(phase.src, t_msg, phase.n_procs)
    transport = float(per_proc.max())

    # --- queue search (exact traversal counts, batched Fenwick) ------------
    qsteps = phase.queue_steps(recv_post_order, arrival_order)
    queue = params.gamma * float(qsteps.max(initial=0))

    # --- link contention (actual dimension-ordered routing) ----------------
    max_link, net_bytes = phase.link_contention()
    contention = params.delta * max_link

    total = transport + queue + contention
    if noise > 0.0:
        total *= float(np.exp(rng.normal(0.0, noise)))
    return PhaseResult(total, transport, queue, contention,
                       per_proc, qsteps, max_link, net_bytes)


@dataclasses.dataclass
class SequenceResult:
    """Summed result of a multi-phase sequence (a strategy rewrite): the
    phases execute back-to-back, so times add; per-phase results are kept
    for breakdown tables."""
    time: float
    transport: float
    queue: float
    contention: float
    phases: list[PhaseResult]


def simulate_sequence(phases,
                      recv_post_orders=None,
                      arrival_orders=None,
                      rng: np.random.Generator | None = None,
                      noise: float = 0.0) -> SequenceResult:
    """Simulate a phase *sequence* end-to-end (e.g. the gather -> inter ->
    scatter steps of a strategy rewrite) and sum the step times."""
    results = simulate_many(phases, recv_post_orders=recv_post_orders,
                            arrival_orders=arrival_orders, rng=rng,
                            noise=noise)
    return SequenceResult(
        time=sum(r.time for r in results),
        transport=sum(r.transport for r in results),
        queue=sum(r.queue for r in results),
        contention=sum(r.contention for r in results),
        phases=results)


def simulate_phase(machine: MachineSpec, src, dst, size,
                   recv_post_order: dict[int, np.ndarray] | None = None,
                   arrival_order: dict[int, np.ndarray] | None = None,
                   rng: np.random.Generator | None = None,
                   noise: float = 0.0, validate: bool = False) -> PhaseResult:
    """Simulate one phase of point-to-point messages (array-level entry).

    ``validate=True`` runs the typed validation layer over the message
    arrays first (:func:`repro.comm.guard.validate_messages` via
    :meth:`repro.comm.CommPhase.build`): NaN/negative sizes and
    out-of-range ranks raise a precise
    :class:`repro.comm.guard.PatternError` subclass instead of simulating
    garbage.
    """
    return simulate(CommPhase.build(machine, src, dst, size,
                                    validate=validate),
                    recv_post_order=recv_post_order,
                    arrival_order=arrival_order, rng=rng, noise=noise)


def _simulate_stack(stack: PhaseStack, recv_post_orders,
                    arrival_orders, backend=None) -> list[PhaseResult]:
    """Price a stacked sweep's raw aggregates into PhaseResult rows.

    One segmented pass per quantity (transport sums, queue steps, link
    contention) over the whole arena — bit-identical to per-phase
    :func:`simulate` (DESIGN.md §8) on the numpy backend; device backends
    are allclose for the float aggregates and bit-equal for queue steps."""
    if stack.n_phases == 0:
        return []
    params = stack.machine.params
    raw = stack.sim_arrays(recv_post_orders=recv_post_orders,
                           arrival_orders=arrival_orders, backend=backend)
    out = []
    for i in range(stack.n_phases):
        if stack.phases[i].n_msgs == 0:
            z = np.zeros(0)
            out.append(PhaseResult(0.0, 0.0, 0.0, 0.0, z, z, 0.0, 0.0))
            continue
        transport = float(raw.transport[i])
        queue = params.gamma * float(raw.qsteps[i].max(initial=0))
        contention = params.delta * float(raw.max_link[i])
        out.append(PhaseResult(
            transport + queue + contention, transport, queue, contention,
            raw.per_proc[i], raw.qsteps[i],
            float(raw.max_link[i]), float(raw.net_bytes[i])))
    return out


def simulate_many(phases,
                  recv_post_orders=None,
                  arrival_orders=None,
                  rng: np.random.Generator | None = None,
                  noise: float = 0.0,
                  backend=None) -> list[PhaseResult]:
    """Simulate a sweep of :class:`CommPhase` objects (an AMG hierarchy, a
    partition or machine scan) in one call.

    ``recv_post_orders[i]`` / ``arrival_orders[i]`` apply to ``phases[i]``;
    a single shared ``rng`` drives the noise stream across the whole sweep
    (default: ``np.random.default_rng(0)``, created once per call so the
    sweep is reproducible — pass your own generator to chain sweeps).

    Fast path: phases bound to one machine (or an already-built
    :class:`repro.comm.PhaseStack` / :class:`repro.comm.DeltaStack`) are
    simulated in one segmented pass over the arena, bit-identical to the
    per-phase loop; single phases and mixed-machine sweeps fall back to
    :func:`simulate`.  A ``DeltaStack`` serves transport and contention from
    its incrementally-maintained caches.  ``backend`` selects the arena's
    reduction backend (as in :meth:`repro.comm.PhaseStack.sim_arrays`;
    ``None`` is numpy) and is ignored on the per-phase fallback path.
    """
    if noise > 0.0 and rng is None:
        rng = np.random.default_rng(0)
    with obs.span("repro.plan.simulate"):
        if isinstance(phases, _ARENAS):
            stack = phases
        else:
            phases = list(phases)
            stack = as_stack(phases)
        if stack is not None:
            out = _simulate_stack(stack, recv_post_orders, arrival_orders,
                                  backend=backend)
            if noise > 0.0:
                # same draw order as the per-phase loop, which returns early
                # for empty phases without touching the rng
                for r, ph in zip(out, stack.phases):
                    if ph.n_msgs:
                        r.time *= float(np.exp(rng.normal(0.0, noise)))
            return out
        return [simulate(
            ph,
            recv_post_order=recv_post_orders[i] if recv_post_orders else None,
            arrival_order=arrival_orders[i] if arrival_orders else None,
            rng=rng, noise=noise) for i, ph in enumerate(phases)]
