"""Lower an :class:`~repro.exec.plan.ExecSchedule` to a jitted JAX program.

The program is one ``jax.jit`` around one ``jax.shard_map`` over a 1-D
``("rank",)`` mesh
(:func:`repro.launch.mesh.make_rank_mesh`): every simulated MPI rank owns
one mesh device, its row of the holding/delivered buffers, and its rows of
each round's tables.  Per round the body loads the rank's ``pack`` slots
from its holding buffer, moves them with a single static
``jax.lax.ppermute`` (the round's permutation is baked in at trace time —
rounds unroll, no dynamic control flow), and adds the received slots into
the holding (``stage``) and delivered (``final``) buffers.

Each of a round's three tables is lowered on its own, by its run
structure (:func:`_lower_table`): a *run* is a span of slots that holds
consecutive unit ids.  Where every rank's row has at most
:data:`MAX_RUNS` runs, each run is a block copy: a window of the round's
width read with ``dynamic_slice`` at a per-rank offset, or added back
under a slot mask with ``dynamic_update_slice``.  The per-rank offsets
and masks travel as small int32 tables sharded by rank, like the index
tables they replace.  A window never relies on the slice clamping at the
row's end: the host moves its start inside the row and rotates the data
by the same amount.  A table with no run on any rank (``stage`` when every
unit arrives at its destination) is dropped.  Any other table (striped
injector shares, many aggregated messages) keeps the per-word gather or
scatter-add, whose unused slots index the sink column, so junk never
aliases a real unit; the sink is trimmed by every caller.

Payloads are int32 and every real column receives at most once a round,
so the result is bit-identical to the serial numpy walk of the same
tables (:func:`repro.exec.reference.run_reference`) — the oracle
:mod:`tests.test_exec` pins on the forced 8-device host mesh.

jax is imported lazily inside the functions: importing this module (for
docs and docstring coverage) needs numpy only.
"""
from __future__ import annotations

import numpy as np

from repro.comm import obs

from .plan import ExecSchedule

#: Most runs a table row may hold and still lower as window copies.  On
#: four v5e chips, a ring of 4 rounds 11,264 words wide costs about 3.6 us
#: more for each further run of a table, and 134 us more for a table
#: lowered per word (131,072 words wide: 5.3 us and 1.5 ms), so windows
#: win up to about 37 runs at the narrowest rounds the benchmark runs.
#: Each run is also unrolled into the program, and compile time grows with
#: it (9 s at 32 runs a row over 4 rounds), hence half that.
MAX_RUNS = 16

#: Columns of a window table: the window's start in the row, the rotation
#: that lines the window up with the slots, and the masked range
#: ``[lo, hi)`` (slots on the gather side, window positions on the scatter
#: side).
_START, _ROT, _LO, _HI = range(4)


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


def _lower_table(table: np.ndarray, n_cols: int, gather: bool):
    """Lower one ``(n_procs, width)`` index table of a round by its runs.

    Returns ``(rots, arg)``.  ``rots is None`` keeps the per-word lowering,
    with ``arg`` the table itself.  Otherwise ``rots`` holds one flag a
    run (whether any rank's window needs rotating; empty when no rank has
    a run, and ``arg`` is then None) and ``arg`` the ``(n_procs, runs, 4)``
    int32 window table, a rank with fewer runs padded with empty ones.
    ``n_cols`` is the buffer row's length, sink included."""
    P, W = table.shape
    sink = n_cols - 1
    real = table != sink
    cont = np.zeros_like(real)
    cont[:, 1:] = real[:, 1:] & real[:, :-1] & (table[:, 1:]
                                               == table[:, :-1] + 1)
    start = real & ~cont
    end = real.copy()
    end[:, :-1] &= ~cont[:, 1:]
    rank, slot = np.nonzero(start)
    last = np.nonzero(end)[1]
    n_runs = int(np.bincount(rank, minlength=P).max()) if rank.size else 0
    if n_runs > MAX_RUNS:
        return None, table
    if n_runs == 0:
        return (), None
    unit = table[rank, slot].astype(np.int64)
    length = last - slot + 1
    base = unit - slot                  # slot k of the run holds unit base + k
    win = np.clip(base, 0, n_cols - W)  # kept inside the row: no clamping
    shift = base - win
    arg = np.zeros((P, n_runs, 4), dtype=np.int32)
    j = np.arange(rank.size) - np.searchsorted(rank, rank)
    arg[rank, j, _START] = win
    if gather:      # send[k] = window[k + shift] on the run's slots
        arg[rank, j, _ROT] = shift % W
        arg[rank, j, _LO] = slot
        arg[rank, j, _HI] = slot + length
    else:           # window[i] += recv[i - shift] on the run's positions
        arg[rank, j, _ROT] = -shift % W
        arg[rank, j, _LO] = unit - win
        arg[rank, j, _HI] = unit - win + length
    return tuple(bool(r) for r in (arg[:, :, _ROT] != 0).any(axis=0)), arg


def executor_program(schedule: ExecSchedule, mesh):
    """The jitted ``shard_map`` program of ``schedule`` on ``mesh`` and its
    host arguments ``(hold0, deliv0, tables)``: ``fn(*args)`` returns the
    delivered ``(n_procs, n_units + 1)`` int32 matrix, sink column last,
    sharded by rank.  ``tables`` holds, per round, the ``pack``, ``stage``
    and ``final`` tables as lowered (:func:`_lower_table`), every array
    with one row a rank.  Lowering ``fn`` with shapes instead of ``args``
    compiles it for a mesh of described devices.  With tracing on
    (:mod:`repro.comm.obs`) the counters ``exec.block_tables``,
    ``exec.gather_tables`` and ``exec.dropped_tables`` count the tables
    lowered as windows, per word, and dropped."""
    import jax
    import jax.numpy as jnp

    hold0, deliv0 = initial_buffers(schedule)
    n_cols = hold0.shape[1]
    rounds, tables = [], []
    kinds = {"block": 0, "gather": 0, "dropped": 0}
    for phase in schedule.phases:
        for rnd in phase.rounds:
            lowered = [_lower_table(np.asarray(t, dtype=np.int32), n_cols,
                                    gather)
                       for t, gather in ((rnd.pack, True), (rnd.stage, False),
                                         (rnd.final, False))]
            for rots, _ in lowered:
                kinds["gather" if rots is None else
                      "block" if rots else "dropped"] += 1
            rounds.append((tuple((int(s), int(d)) for s, d in rnd.perm),
                           rnd.width, tuple(r for r, _ in lowered)))
            tables.append(tuple(a for _, a in lowered))
    for kind, n in kinds.items():
        obs.count(f"exec.{kind}_tables", n)

    # a rank's rows stay (1, n_cols): on a v5e a 1-D view costs two
    # relayouts of the whole row a program, more than all its windows
    def window(row, at, width):
        return jax.lax.dynamic_slice_in_dim(row, at, width, axis=1)

    def rotate(x, by):
        # x[:, (i + by) % W]; ``by`` lies in [0, W)
        return window(jnp.concatenate([x, x], axis=1), by, x.shape[1])

    def load(row, rots, arg, width):
        if rots is None:
            return row[:, arg]
        send = jnp.zeros((1, width), row.dtype)    # no rank sends
        slot = jax.lax.broadcasted_iota(jnp.int32, (1, width), 1)
        for j, rot in enumerate(rots):
            win = window(row, arg[j, _START], width)
            if rot:
                win = rotate(win, arg[j, _ROT])
            # the first run's window fills every slot; later runs overwrite
            # their own, and slots in no run carry junk the receiver masks
            send = win if j == 0 else jnp.where(
                (slot >= arg[j, _LO]) & (slot < arg[j, _HI]), win, send)
        return send

    def add(row, rots, arg, recv):
        if rots is None:
            return row.at[:, arg].add(recv)
        width = recv.shape[1]
        pos = jax.lax.broadcasted_iota(jnp.int32, (1, width), 1)
        for j, rot in enumerate(rots):
            at = arg[j, _START]
            got = rotate(recv, arg[j, _ROT]) if rot else recv
            win = window(row, at, width)
            win = jnp.where((pos >= arg[j, _LO]) & (pos < arg[j, _HI]),
                            win + got, win)
            row = jax.lax.dynamic_update_slice_in_dim(row, win, at, axis=1)
        return row

    def step(h, dv, round_tables):
        for (perm, width, (rp, rs, rf)), args in zip(rounds, round_tables):
            pack, stage, final = (None if a is None else a[0] for a in args)
            recv = jax.lax.ppermute(load(h, rp, pack, width), "rank", perm)
            h = add(h, rs, stage, recv)
            dv = add(dv, rf, final, recv)
        return dv

    spec = jax.sharding.PartitionSpec("rank")
    args = (hold0, deliv0, tuple(tables))
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
    ``digest_backend`` is ``'jax'``/``'pallas'``, numpy when it is
    ``None``).  ``mesh`` as in
    :func:`build_executor`."""
    from .reference import delivered_digest
    delivered = build_executor(schedule, mesh=mesh)()
    return delivered, delivered_digest(delivered, schedule,
                                       backend=digest_backend)
