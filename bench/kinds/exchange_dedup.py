"""Traffic kind ``exchange_dedup``: DeepSeek-V3's deduplicated, dropless
MoE exchange, planned, lowered to ``ppermute`` rounds and run back to back
on the chips.

The kind ``exchange`` (:mod:`bench.kinds.exchange`) with other messages:
set-up draws iid uniform scores for every (token, expert) from the
traffic's ``routing_seed``, the same for every run seed.  The plain
reference (:mod:`bench.moe_dedup`) routes and lowers them into the
messages that the check holds the delivery to and that the roofline
counts.  The program routes and lowers the same scores
(``repro.workloads.moe``: node-limited top-K, one copy per token and
chip, its own FP8 and BF16 widths) into the messages that the planner
prices and ``exec/`` lowers and runs.  The window, the release and the
work are the parent kind's.

The check adds ``messages_wrong``: the (src, dst) pairs of either phase
whose bytes differ between the program's messages and the reference's,
plus the pairs that only one of them has.  Its control routes the
program without the group limit.

Traffic file keys: ``kind``, ``tokens_per_rank``, ``scores``,
``routing_seed``, ``dispatch_bytes``, ``combine_bytes``, ``strategies``,
``unit_bytes``, ``kept`` and ``limits``.
"""
from __future__ import annotations

import numpy as np

from bench import deploy, moe_dedup
from bench.kinds import exchange


class Cell(exchange.Cell):
    def setup(self) -> None:
        import jax
        from repro.comm import CommPhase
        from repro.comm.strategies import best_strategy_many
        from repro.exec import build_schedule
        from repro.exec.lower import executor_program

        cfg, mix = self.cfg, self.mix
        machine = deploy.machine(cfg["machine"])
        n = int(cfg["expert_parallel"])
        if machine.n_procs != n or len(self.devices) != n:
            raise SystemExit(f"{n} ranks need {n} devices and a machine of "
                             f"{n}; have {len(self.devices)} and "
                             f"{machine.n_procs}")
        self.scores, self.messages = moe_dedup.draw(cfg, mix)
        self.planned = self.program_messages(int(cfg["topk_group"]))
        rng = np.random.default_rng(self.seed)
        phases = [CommPhase.build(machine, s, d, z, n_procs=n)
                  for s, d, z in self.planned]
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

    def program_messages(self, topk_group: int):
        """``(dispatch, combine)`` of the drawn scores as the program routes
        (over ``topk_group`` groups) and lowers them, at the program's own
        wire widths for the configuration's hidden size."""
        from repro.workloads import moe as program

        cfg = self.cfg
        n, hidden = int(cfg["expert_parallel"]), int(cfg["hidden_size"])
        choices = program.node_limited_topk(
            self.scores, int(cfg["num_experts_per_tok"]),
            int(cfg["n_group"]), topk_group)
        pat = program.pattern_from_choices(
            choices, n, int(cfg["n_routed_experts"]),
            program.fp8_token_bytes(hidden), hidden * program.ACT_BYTES)
        return [(p.src, p.dst, p.size) for p in (pat.dispatch, pat.combine)]

    def check(self, control: bool = False) -> list[tuple[str, float, float]]:
        """The parent's check, and ``messages_wrong``; ``control`` routes
        the program over every group."""
        planned = self.planned
        if control:
            planned = self.program_messages(int(self.cfg["n_group"]))
        wrong = sum(messages_wrong(got, want)
                    for got, want in zip(planned, self.messages))
        return super().check(control) + [
            ("messages_wrong", float(wrong),
             float(self.mix["limits"]["messages_wrong"]))]


def messages_wrong(got, want) -> int:
    """The (src, dst) pairs whose bytes differ between two ``(src, dst,
    size)`` message sets, counting a pair that only one set has; a pair a
    set repeats counts once more for each repeat."""
    def by_pair(msgs):
        src, dst, size = msgs
        pairs = {}
        for s, d, z in zip(np.asarray(src).tolist(), np.asarray(dst).tolist(),
                           np.asarray(size, dtype=np.float64).tolist()):
            pairs[s, d] = pairs.get((s, d), 0.0) + z
        return pairs, len(src) - len(pairs)

    g, g_repeats = by_pair(got)
    w, w_repeats = by_pair(want)
    return (sum(g.get(k) != w.get(k) for k in g.keys() | w.keys())
            + g_repeats + w_repeats)
