"""Traffic kind ``sweep``: one planning user sweeping back to back.

Each sweep binds the deployment's unbound patterns to its machine and
prices every strategy of every pattern in one ``best_strategy_many`` call,
with the arrival seed ``run seed + sweep index``: no object of an earlier
sweep is reused, so no cache inside the program can serve one.  A sweep
priced under a backend fallback, or with a verdict marked degraded,
counts as failed.

Traffic file keys: ``kind``, ``backend`` and ``limits``.
"""
from __future__ import annotations

import time

import numpy as np

from bench import deploy, reference


class Cell:
    def __init__(self, cfg: dict, mix: dict, seed: int, devices, spans):
        self.cfg, self.mix, self.seed = cfg, mix, int(seed)
        self.spans = spans
        self.backend = mix["backend"]
        self.verdicts: list = []

    def setup(self) -> None:
        from repro.comm.strategies import best_strategy_many
        self.machine = deploy.machine(self.cfg["machine"])
        self.patterns = deploy.amg_patterns(self.cfg["problem"],
                                            self.machine.n_procs)
        self.check_traffic()
        # every shape a sweep uses: the arena, the kernels, the walk
        best_strategy_many([p.bind(self.machine) for p in self.patterns],
                           seed=self.seed, backend=self.backend)

    def check_traffic(self) -> None:
        """The levels as the configuration file records them: a change to
        the program's sparse code must not move the traffic."""
        got = [{"messages": int(p.n_msgs), "ranks": int(p.n_procs),
                "digest": deploy.digest(p.src, p.dst, p.size)}
               for p in self.patterns]
        want = self.cfg["problem"].get("levels")
        if want is not None and got != want:
            raise SystemExit(f"AMG traffic differs from the configuration: "
                             f"{got} != {want}")

    def window(self, seconds: float) -> dict:
        from repro.comm.health import get_health
        from repro.comm.strategies import best_strategy_many
        health = get_health()
        failed = 0
        t0 = time.perf_counter()
        while True:
            i = len(self.verdicts)
            before = health.n_events
            with self.spans("sweep"):
                with self.spans("bind"):
                    phases = [p.bind(self.machine) for p in self.patterns]
                out = best_strategy_many(phases, seed=self.seed + i,
                                         backend=self.backend)
            self.verdicts.append([deploy.verdict_body(v) for v in out])
            failed += int(health.n_events > before
                          or any(v.degraded for v in out))
            if time.perf_counter() - t0 >= seconds:
                break
        t1 = time.perf_counter()
        n = len(self.verdicts)
        return {"attempted": n, "failed": failed, "units": n,
                "e2e": {"sweep_s": (t1 - t0) / n}}

    def reseed(self, seed: int) -> None:
        """Start over with another arrival seed; the patterns stay."""
        self.seed, self.verdicts = int(seed), []

    def release(self) -> None:
        pass

    def work(self) -> dict:
        """Sizes of one sweep's arena, from the reference's own rewrite."""
        m = reference.Machine(self.cfg["reference_machine"])
        return reference.arena_sizes(m, [(p.src, p.dst, p.size, p.n_procs)
                                         for p in self.patterns])

    def check(self, control: bool = False) -> list[tuple[str, float, float]]:
        """Every verdict of a sweep drawn from the seed against the plain
        reference, and every sweep's presence.  ``control`` puts the
        reference priced in bfloat16 in the program's place."""
        m = reference.Machine(self.cfg["reference_machine"])
        i = int(np.random.default_rng(self.seed).integers(len(self.verdicts)))
        gap = 0.0
        for p, got in zip(self.patterns, self.verdicts[i]):
            want = reference.verdict(m, p.src, p.dst, p.size, p.n_procs,
                                     self.seed + i)
            if control:
                got = reference.verdict(m, p.src, p.dst, p.size, p.n_procs,
                                        self.seed + i, lowp=True)
            gap = max(gap, reference.verdict_gap(got, want))
        missing = sum(len(self.patterns) - len(v) for v in self.verdicts)
        lim = self.mix["limits"]
        return [("verdict_gap", gap, lim["verdict_gap"]),
                ("verdicts_missing", float(missing), 0.0)]
