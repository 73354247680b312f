"""Lower an :class:`~repro.exec.plan.ExecSchedule` to a jitted JAX program.

The program is one ``jax.jit`` around one ``jax.shard_map`` over a 1-D
``("rank",)`` mesh
(:func:`repro.launch.mesh.make_rank_mesh`): every simulated MPI rank owns
one mesh device, its row of the holding/delivered buffers, and its rows of
each round's index tables.  Per round the body gathers the rank's ``pack``
slots from its holding buffer, moves them with a single static
``jax.lax.ppermute`` (the round's permutation is
baked in at trace time — rounds unroll, no dynamic control flow), and
scatter-adds the received slots into the holding (``stage``) and delivered
(``final``) buffers.  Padding flows through the sink column, which both
sides index for unused slots, so junk never aliases a real unit; the sink
is trimmed before returning.

Payloads are int32 and scatter-adds touch disjoint real columns, so the
result is bit-identical to the serial numpy walk of the same tables
(:func:`repro.exec.reference.run_reference`) — the oracle
:mod:`tests.test_exec` pins on the forced 8-device host mesh.

jax is imported lazily inside the functions: importing this module (for
docs and docstring coverage) needs numpy only.
"""
from __future__ import annotations

import numpy as np

from .plan import ExecSchedule


def initial_buffers(schedule: ExecSchedule) -> tuple[np.ndarray, np.ndarray]:
    """The executor's starting ``(hold, deliv)`` int32 buffers for
    ``schedule``, each ``(n_procs, n_units + 1)`` with the sink column last:
    every unit's payload sits in its origin rank's holding row, and units
    already at home (origin == destination) are pre-delivered."""
    P, U = schedule.n_procs, schedule.n_units
    units = np.arange(U)
    hold = np.zeros((P, U + 1), dtype=np.int32)
    deliv = np.zeros((P, U + 1), dtype=np.int32)
    hold[schedule.unit_src, units] = schedule.payload
    at_home = schedule.unit_src == schedule.unit_dst
    deliv[schedule.unit_dst[at_home], units[at_home]] = \
        schedule.payload[at_home]
    return hold, deliv


def executor_program(schedule: ExecSchedule, mesh):
    """The jitted ``shard_map`` program of ``schedule`` on ``mesh`` and its
    host arguments: ``fn(*args)`` returns the delivered ``(n_procs,
    n_units + 1)`` int32 matrix, sink column included, sharded by rank.
    Lowering ``fn`` with shapes instead of ``args`` compiles it for a mesh
    of described devices."""
    import jax

    hold0, deliv0 = initial_buffers(schedule)
    perms = []
    tables = []
    for phase in schedule.phases:
        for rnd in phase.rounds:
            perms.append(tuple((int(s), int(d)) for s, d in rnd.perm))
            tables.append((np.asarray(rnd.pack, dtype=np.int32),
                           np.asarray(rnd.stage, dtype=np.int32),
                           np.asarray(rnd.final, dtype=np.int32)))
    tables = tuple(tables)

    def step(hold, deliv, round_tables):
        h, dv = hold[0], deliv[0]
        for perm, (pack, stage, final) in zip(perms, round_tables):
            send = h[pack[0]]
            recv = jax.lax.ppermute(send, "rank", perm)
            h = h.at[stage[0]].add(recv)
            dv = dv.at[final[0]].add(recv)
        return dv[None]

    spec = jax.sharding.PartitionSpec("rank")
    args = (hold0, deliv0, tables)
    in_specs = jax.tree_util.tree_map(lambda _: spec, args)
    fn = jax.jit(jax.shard_map(step, mesh=mesh, in_specs=in_specs,
                               out_specs=spec))
    return fn, args


def build_executor(schedule: ExecSchedule, mesh=None):
    """Compile ``schedule`` into a zero-argument callable returning the
    delivered ``(n_procs, n_units)`` int32 matrix (host numpy, sink
    trimmed).

    ``mesh`` is the 1-D ``("rank",)`` mesh to run on, defaulting to
    :func:`repro.launch.mesh.make_rank_mesh` over the schedule's rank
    count.  The callable re-runs the jitted program of
    :func:`executor_program` on each invocation (compilation is cached by
    jax), which is what :func:`repro.exec.measure.time_schedule` times.
    """
    import jax

    from repro.launch.mesh import make_rank_mesh

    if mesh is None:
        mesh = make_rank_mesh(schedule.n_procs)
    fn, args = executor_program(schedule, mesh)

    def run() -> np.ndarray:
        out = jax.block_until_ready(fn(*args))
        return np.asarray(out)[:, :schedule.n_units]

    return run


def execute(schedule: ExecSchedule, mesh=None,
            digest_backend: str | None = None):
    """Run ``schedule`` once on the JAX path and return ``(delivered,
    digest)``: the delivered int32 matrix and its per-rank payload totals
    reduced through the fused segment kernels
    (:func:`repro.exec.reference.delivered_digest`, device-backed when
    ``digest_backend`` is ``'jax'``/``'pallas'``).  ``mesh`` as in
    :func:`build_executor`."""
    from .reference import delivered_digest
    delivered = build_executor(schedule, mesh=mesh)()
    return delivered, delivered_digest(delivered, schedule,
                                       backend=digest_backend)
