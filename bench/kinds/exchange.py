"""Traffic kind ``exchange``: the planner's chosen plan for one MoE layer,
lowered to ``ppermute`` rounds and run back to back on the chips.

Set-up draws one balanced routing of the configuration's layer from the
traffic's ``routing_seed`` (``tokens_per_rank`` tokens per rank), the same
for every run seed, so that every run does the same work and finds its
programs in the compile cache; lets the planner pick its model winner for dispatch and for combine among
``strategies``, lowers each with ``unit_bytes`` bytes per int32 payload
word, and puts the arguments on the mesh once, with one payload word per
unit drawn from the run seed.  The window runs dispatch, then combine, each
to ``block_until_ready``.

Traffic file keys: ``kind``, ``tokens_per_rank``, ``routing_seed``,
``strategies``, ``unit_bytes``, ``kept`` and ``limits``.
"""
from __future__ import annotations

import time

import numpy as np

from bench import counts, deploy, moe

PHASES = ("dispatch", "combine")


class Cell:
    def __init__(self, cfg: dict, mix: dict, seed: int, devices, spans):
        self.cfg, self.mix, self.seed = cfg, mix, int(seed)
        self.devices = list(devices)
        self.spans = spans
        self.kept: dict = {}
        self.n = 0

    def setup(self) -> None:
        import jax
        from repro.comm import CommPhase
        from repro.comm.strategies import best_strategy_many
        from repro.exec import build_schedule
        from repro.exec.lower import executor_program

        mix = self.mix
        machine = deploy.machine(self.cfg["machine"])
        n = int(self.cfg["expert_parallel"])
        if machine.n_procs != n or len(self.devices) != n:
            raise SystemExit(f"{n} ranks need {n} devices and a machine of "
                             f"{n}; have {len(self.devices)} and "
                             f"{machine.n_procs}")
        self.messages = moe.draw(self.cfg, mix["tokens_per_rank"],
                                 mix["routing_seed"])
        rng = np.random.default_rng(self.seed)
        phases = [CommPhase.build(machine, s, d, z, n_procs=n)
                  for s, d, z in self.messages]
        verdicts = best_strategy_many(phases,
                                      strategies=tuple(mix["strategies"]),
                                      backend="numpy")
        self.winners = [v.model_winner for v in verdicts]
        mesh = jax.sharding.Mesh(np.asarray(self.devices), ("rank",))
        shard = jax.sharding.NamedSharding(mesh,
                                           jax.sharding.PartitionSpec("rank"))
        self.programs, self.units = [], []
        for phase, winner in zip(phases, self.winners):
            sched = build_schedule(phase, winner,
                                   unit_bytes=float(mix["unit_bytes"]))
            fn, (_, _, tables) = executor_program(sched, mesh)
            u = sched.n_units
            payload = rng.integers(1, 2 ** 31 - 1, size=u, dtype=np.int32)
            hold = np.zeros((n, u + 1), dtype=np.int32)
            deliv = np.zeros((n, u + 1), dtype=np.int32)
            cols = np.arange(u)
            hold[sched.unit_src, cols] = payload
            home = sched.unit_src == sched.unit_dst
            deliv[sched.unit_dst[home], cols[home]] = payload[home]
            args = jax.device_put((hold, deliv, tables), shard)
            compiled = fn.lower(*args).compile()
            jax.block_until_ready(compiled(*args))
            self.programs.append((compiled, args))
            self.units.append((payload, sched.unit_src.copy()))
        keep = np.random.default_rng(self.seed + 1).choice(
            mix["kept"]["among"], size=mix["kept"]["count"], replace=False)
        self.keep = {int(k) for k in keep}

    def window(self, seconds: float) -> dict:
        import jax
        t0 = time.perf_counter()
        last = None
        while True:
            outs = []
            for name, (compiled, args) in zip(PHASES, self.programs):
                with self.spans("exchange." + name):
                    outs.append(jax.block_until_ready(compiled(*args)))
            if self.n in self.keep:
                self.kept[self.n] = outs
            last = outs
            self.n += 1
            if time.perf_counter() - t0 >= seconds:
                break
        t1 = time.perf_counter()
        self.kept[self.n - 1] = last
        return {"attempted": self.n, "failed": 0, "units": self.n,
                "e2e": {"exchange_ms": (t1 - t0) / self.n * 1e3}}

    def release(self) -> None:
        """Bring the kept outputs to the host and free the device."""
        self.kept = {i: [np.array(o)[:, :-1] for o in outs]
                     for i, outs in self.kept.items()}
        self.programs = []

    def work(self) -> dict:
        """The messages' bytes per chip (:func:`bench.counts.exchange_work`),
        whatever plan carries them."""
        return counts.exchange_work(self.messages,
                                    int(self.cfg["expert_parallel"]),
                                    float(self.mix["unit_bytes"]))

    def check(self, control: bool = False) -> list[tuple[str, float, float]]:
        """Every kept exchange's delivered payload against the messages:
        each unit delivered once, with its word, and the units from each
        rank to each rank as many as the messages carry.  ``control``
        carries every word through float32 instead."""
        wrong = 0
        for outs in self.kept.values():
            for (s, d, z), (payload, src), got in zip(self.messages,
                                                      self.units, outs):
                if control:
                    got = np.where(got != 0, got.astype(np.float32)
                                   .astype(np.int64), 0)
                wrong += units_wrong(got, payload, src, s, d, z,
                                     float(self.mix["unit_bytes"]))
        return [("units_wrong", float(wrong), 0.0)]


def units_wrong(delivered, payload, unit_src, src, dst, size,
                unit_bytes: float) -> int:
    """Units not delivered exactly once with their own word, plus the
    difference, pair by pair, between the units that went from each rank
    to each rank and the ``ceil(size / unit_bytes)`` (at least one) of the
    messages between them."""
    d = np.asarray(delivered)
    u = np.arange(d.shape[1])
    nz = d != 0
    row = np.argmax(nz, axis=0)
    ok = (nz.sum(axis=0) == 1) & (d[row, u] == payload)
    n = d.shape[0]
    got = np.bincount(unit_src[ok] * n + row[ok], minlength=n * n)
    units = np.maximum(1, np.ceil(np.asarray(size) / unit_bytes)).astype(
        np.int64)
    want = np.bincount(np.asarray(src) * n + np.asarray(dst),
                       weights=units, minlength=n * n)
    return int((~ok).sum() + np.abs(got - want).sum())
