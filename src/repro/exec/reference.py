"""Serial numpy reference executor: the bit-identity oracle for lowered
schedules.

Two independent answers for "what payload does each rank end up holding":

* :func:`reference_delivered` — the *semantic* oracle.  It ignores the
  schedule's routing entirely and places every unit's payload directly at
  its destination: the answer any correct exchange must produce.
* :func:`run_reference` — the *operational* oracle.  It walks the
  schedule's phases and rounds serially with plain Python loops, consuming
  the same ``pack`` / ``stage`` / ``final`` index tables the JAX executor
  (:mod:`repro.exec.lower`) feeds to ``ppermute`` — so a schedule bug
  (mis-colored round, wrong table entry) makes *both* executors disagree
  with :func:`reference_delivered`, while a lowering/transport bug makes
  the JAX path disagree with this one.

Payloads are int32 and accumulation is addition of disjoint contributions,
so equality is exact (``==``), never approximate.
"""
from __future__ import annotations

import numpy as np

from .plan import ExecSchedule


def reference_delivered(schedule: ExecSchedule) -> np.ndarray:
    """The semantic delivery oracle for ``schedule``: an ``(n_procs,
    n_units)`` int32 matrix with every unit's payload placed directly at its
    destination rank, no routing involved."""
    out = np.zeros((schedule.n_procs, schedule.n_units), dtype=np.int32)
    out[schedule.unit_dst, np.arange(schedule.n_units)] = schedule.payload
    return out


def run_reference(schedule: ExecSchedule) -> np.ndarray:
    """Execute ``schedule`` serially in numpy and return the delivered
    ``(n_procs, n_units)`` matrix.

    Walks every phase's rounds in order; for each ``(sender, receiver)``
    pair of a round's permutation the sender's ``pack`` row is read from its
    holding buffer and scattered through the receiver's ``stage`` /
    ``final`` rows — exactly the dataflow the JAX executor runs as one
    ``ppermute`` per round.  The padded sink column is carried and trimmed
    like the device path carries it.
    """
    P, U = schedule.n_procs, schedule.n_units
    hold = np.zeros((P, U + 1), dtype=np.int32)
    deliv = np.zeros((P, U + 1), dtype=np.int32)
    units = np.arange(U)
    hold[schedule.unit_src, units] = schedule.payload
    at_home = schedule.unit_src == schedule.unit_dst
    deliv[schedule.unit_dst[at_home], units[at_home]] = \
        schedule.payload[at_home]

    for phase in schedule.phases:
        for rnd in phase.rounds:
            arrivals = []                       # snapshot: sends are posted
            for s, d in rnd.perm:               # before any receive lands
                arrivals.append((d, hold[s, rnd.pack[s]]))
            for d, recv in arrivals:
                np.add.at(hold[d], rnd.stage[d], recv)
                np.add.at(deliv[d], rnd.final[d], recv)
            hold[:, U] = 0                      # discard sink junk
            deliv[:, U] = 0
    return deliv[:, :U]


def delivered_digest(delivered: np.ndarray, schedule: ExecSchedule,
                     backend: str | None = None) -> np.ndarray:
    """Per-rank delivered-payload totals of a ``delivered`` matrix, reduced
    through the fused segment kernels
    (:func:`repro.kernels.comm_stack.segment_sum`) — the on-device
    aggregation path when ``backend`` is ``'jax'``/``'pallas'``, the numpy
    reference when it is ``None`` or ``'numpy'``.  For a correct execution of ``schedule`` this
    equals ``segment_sum(payload, unit_dst, n_procs)``."""
    from repro.kernels.comm_stack import segment_sum
    units = np.arange(schedule.n_units)
    values = np.asarray(delivered)[schedule.unit_dst, units]
    return segment_sum(values.astype(np.float64), schedule.unit_dst,
                       schedule.n_procs, backend=backend)
