"""Traffic kind ``query``: one client sending cold planning queries back to
back through a long-lived ``StrategyService``.

Set-up draws a pool of balanced routing draws from the traffic's
``pool_seed`` (``pool`` draws at ``tokens_per_rank`` tokens per rank), the
same pool for every run seed, and prices each once on a throwaway
service, so that every arena shape is compiled before
the window (and found in the compile cache by every later run).  The run
seed orders the pool and draws the sizes: query ``i`` sends the ``i``-th
draw of the run's order, cycling, with every message size scaled by ``t /
tokens_per_rank``, ``t`` log-uniform in ``tokens_range``: a new
fingerprint every time, so the verdict cache never hits, and a shape of
the pool, so nothing compiles.  A query with a result that is missing,
degraded or in error, or priced under a backend fallback, counts as
failed.

Traffic file keys: ``kind``, ``backend``, ``pool``, ``tokens_per_rank``,
``tokens_range``, ``pool_seed`` and ``limits``.
"""
from __future__ import annotations

import time

import numpy as np

from bench import deploy, moe, reference


class Cell:
    def __init__(self, cfg: dict, mix: dict, seed: int, devices, spans):
        self.cfg, self.mix, self.seed = cfg, mix, int(seed)
        self.spans = spans
        self.results: list = []
        self.scales: list[float] = []
        self.cache_stats = None

    def _patterns(self, draw, scale: float):
        from repro.sparse.partition import CommPattern
        n = int(self.cfg["expert_parallel"])
        return [CommPattern(src=s, dst=d, size=z * scale, n_procs=n)
                for s, d, z in draw]

    def setup(self) -> None:
        from repro.serve import StrategyService
        mix = self.mix
        self.machine = deploy.machine(self.cfg["machine"])
        pool_rng = np.random.default_rng(mix["pool_seed"])
        pool = [moe.draw(self.cfg, mix["tokens_per_rank"],
                         int(pool_rng.integers(2 ** 62)))
                for _ in range(mix["pool"])]
        rng = np.random.default_rng(self.seed)
        self.pool = [pool[k] for k in rng.permutation(len(pool))]
        lo, hi = mix["tokens_range"]
        self.rng = rng
        self.log_t = (np.log(lo), np.log(hi))
        warm = StrategyService(self.machine, backend=mix["backend"])
        for draw in self.pool:
            warm.query_many(self._patterns(draw, 1.0))

    def window(self, seconds: float) -> dict:
        from repro.comm.health import get_health
        from repro.serve import StrategyService
        health = get_health()
        svc = StrategyService(self.machine, backend=self.mix["backend"])
        lat, failed = [], 0
        t0 = time.perf_counter()
        while True:
            i = len(self.results)
            t = float(np.exp(self.rng.uniform(*self.log_t)))
            scale = t / self.mix["tokens_per_rank"]
            pats = self._patterns(self.pool[i % len(self.pool)], scale)
            before = health.n_events
            q0 = time.perf_counter()
            with self.spans("query"):
                res = svc.query_many(pats)
            lat.append(time.perf_counter() - q0)
            self.scales.append(scale)
            self.results.append([deploy.verdict_body(r.verdict)
                                 if r is not None and r.ok else None
                                 for r in res])
            failed += int(health.n_events > before or any(
                r is None or not r.ok or r.degraded or r.error is not None
                for r in res))
            if time.perf_counter() - t0 >= seconds:
                break
        self.cache_stats = svc.cache.stats()
        ms = np.asarray(lat) * 1e3
        return {"attempted": len(lat), "failed": failed, "units": len(lat),
                "e2e": {"query_p95_ms": float(np.percentile(ms, 95)),
                        "query_p50_ms": float(np.percentile(ms, 50))},
                "cache": self.cache_stats}

    def release(self) -> None:
        pass

    def work(self) -> dict:
        return {}

    def check(self, control: bool = False) -> list[tuple[str, float, float]]:
        """Every verdict of every query of the window against the plain
        reference (the service prices with arrival seed 0).  ``control``
        puts the reference priced in bfloat16 in the program's place."""
        m = reference.Machine(self.cfg["reference_machine"])
        n = int(self.cfg["expert_parallel"])
        gap, missing = 0.0, 0
        for i, (got, scale) in enumerate(zip(self.results, self.scales)):
            draw = self.pool[i % len(self.pool)]
            missing += len(draw) - sum(g is not None for g in got)
            for (s, d, z), g in zip(draw, got):
                if g is None:
                    continue
                want = reference.verdict(m, s, d, z * scale, n, 0)
                if control:
                    g = reference.verdict(m, s, d, z * scale, n, 0, lowp=True)
                gap = max(gap, reference.verdict_gap(g, want))
        lim = self.mix["limits"]
        return [("verdict_gap", gap, lim["verdict_gap"]),
                ("verdicts_missing", float(missing), 0.0)]
