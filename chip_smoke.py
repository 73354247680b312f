"""Chip smoke test: the planner's main path on a TPU, checked against numpy.

    python chip_smoke.py               # one chip
    python chip_smoke.py --four-chip   # four chips: the lowered exchange only

With no option it runs two phases in this one process, each on every
device backend of the stacked passes (``jax`` and ``pallas``):

* ``registry``: the workload registry's scenarios on its machine presets,
  priced through ``repro.workloads.sweep`` and through one
  ``StrategyService`` per machine;
* ``amg``: the paper's AMG/SpMV strategy sweep on Blue Waters at 8192
  ranks, one ``best_strategy_many`` arena of about 2^21 messages.

Each device run must resolve to the requested backend, record no
``BackendHealth`` event, mark no verdict degraded, and price every
candidate within ``rtol=1e-4`` of a numpy run of the same call; one more
pass runs under ``REPRO_STACK_VERIFY=parity``.  It must pick numpy's
winners, except where numpy's costs tie to within that tolerance: such a
tie is reported, since float32 costs need not break it the way float64
ones do.

With ``--four-chip`` it runs only the lowered exchange: the ``standard``,
``two_step`` and ``three_step`` schedules of a seeded irregular exchange
on a 4-rank mesh, each bit-identical to the serial numpy executor and
spanning all four devices.

Progress and timings go to the earlier lines of standard output; the last
line is one JSON object naming the device.  The script exits non-zero,
without that line, when jax finds no TPU or any check fails.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

DEVICE_BACKENDS = ("jax", "pallas")
RTOL = 1e-4                 # the REPRO_STACK_VERIFY=parity contract
AMG_GRID = 40               # elasticity-like operator on a 40^3 grid
AMG_TORUS = (8, 8, 4)       # 128 Geminis x 2 nodes x 16 ranks = 8192 ranks
EXCHANGE_MSGS = 96


def log(msg: str) -> None:
    print(msg, flush=True)


class Check:
    """Collects failed checks; the run fails if any was recorded."""

    def __init__(self):
        self.failures: list[str] = []

    def __call__(self, ok, what: str) -> None:
        if not ok:
            self.failures.append(what)
            log(f"FAIL {what}")


class CompileClock:
    """Seconds jax spent tracing, lowering and compiling since ``reset``."""

    def __init__(self):
        from jax import monitoring
        self.seconds = 0.0
        self.count = 0
        monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, name, duration, **_):
        if name.startswith("/jax/core/compile/"):
            self.seconds += duration
            self.count += name.endswith("backend_compile_duration")

    def reset(self) -> None:
        self.seconds = 0.0
        self.count = 0


def tpu_devices(n_chips: int):
    """jax's devices, or exit when they are not ``n_chips`` or more TPUs."""
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < n_chips:
        raise SystemExit(
            f"chip_smoke: needs {n_chips} TPU chip(s); jax found "
            f"{len(devices)} {devices[0].platform} device(s)")
    return devices


def timed(fn):
    t0 = time.perf_counter()
    out = fn()
    return out, time.perf_counter() - t0


def same_winner(check, got: str, want: str, want_costs: dict,
                tag: str) -> int:
    """Check that the device picked numpy's winner; returns 1 where it
    picked another candidate that ties numpy's to within ``RTOL`` (a tie
    that float32 costs cannot break the way float64 ones do), else 0."""
    if got == want:
        return 0
    tied = want_costs.get(got, np.inf) <= want_costs[want] * (1 + RTOL)
    check(tied, f"{tag}: winner {got} != numpy's {want} "
          f"({want_costs.get(got)} vs {want_costs[want]})")
    return int(tied)


def compare_verdicts(check, got, want, where: str) -> int:
    """Same winners, every candidate's cost within ``RTOL``, nothing
    degraded; returns how many winners were tie-broken differently."""
    check(len(got) == len(want), f"{where}: {len(got)} verdicts, "
          f"numpy gave {len(want)}")
    ties = 0
    for i, (g, w) in enumerate(zip(got, want)):
        tag = f"{where}[{i}]"
        check(not g.degraded, f"{tag}: degraded verdict")
        ties += same_winner(check, g.model_winner, w.model_winner, w.model,
                            f"{tag}/model")
        ties += same_winner(check, g.sim_winner, w.sim_winner, w.sim,
                            f"{tag}/sim")
        for side in ("model", "sim"):
            a, b = getattr(g, side), getattr(w, side)
            check(a.keys() == b.keys(), f"{tag}: {side} strategy sets differ")
            for k in b:
                check(np.isclose(a.get(k, np.nan), b[k], rtol=RTOL, atol=0),
                      f"{tag}: {side}[{k}] {a.get(k)} vs numpy {b[k]}")
    return ties


def compare_rows(check, got, want, want_verdicts, where: str) -> int:
    """:func:`repro.workloads.sweep` rows against the numpy rows, with
    ``want_verdicts`` (numpy's verdicts of the same phases, in row order)
    supplying every candidate's cost for the tie rule of
    :func:`same_winner`; returns the number of tie-broken winners."""
    check(len(got) == len(want) == len(want_verdicts),
          f"{where}: {len(got)} rows, numpy gave {len(want)}")
    ties = 0
    for g, w, v in zip(got, want, want_verdicts):
        tag = f"{where}[{g.machine}/{g.scenario}/{g.phase}]"
        check(not g.degraded, f"{tag}: degraded row")
        ties += same_winner(check, g.model_winner, w.model_winner, v.model,
                            f"{tag}/model")
        ties += same_winner(check, g.sim_winner, w.sim_winner, v.sim,
                            f"{tag}/sim")
        check(np.isclose(g.model, v.model[g.model_winner], rtol=RTOL, atol=0)
              and np.isclose(g.sim, v.sim[g.sim_winner], rtol=RTOL, atol=0),
              f"{tag}: costs {g.model}/{g.sim} vs numpy "
              f"{v.model[g.model_winner]}/{v.sim[g.sim_winner]}")
    return ties


def service_verdicts(check, machine, patterns, backend, where):
    """One fresh ``StrategyService`` pass; every result must be a clean,
    uncached verdict."""
    from repro.serve import StrategyService
    results = StrategyService(machine, backend=backend).query_many(patterns)
    for i, r in enumerate(results):
        check(r.ok and r.error is None and not r.cached,
              f"{where}[{i}]: service result {r.error!r} cached={r.cached}")
    return [r.verdict for r in results if r.ok]


def arena_msgs(phases) -> int:
    """Messages in the strategy-sweep arena of bound ``phases``: every
    strategy rewrite of every phase."""
    from repro.comm.strategies import rewrite, strategies_for
    return sum(ph.n_msgs for p in phases for s in strategies_for(p.machine)
               for ph in rewrite(p, s).phases)


def run_backend(check, clock, phase: str, backend: str, run, compare):
    """Cold run (compiles), warm run, then a parity-verified run of
    ``run(backend)``; each result goes through ``compare``."""
    from repro.comm.health import get_health, reset_health
    from repro.kernels.comm_stack import resolve_backend

    check(resolve_backend(backend) == backend,
          f"{phase}/{backend}: resolves to {resolve_backend(backend)}")
    stats = {}
    for label in ("cold", "warm", "parity"):
        reset_health()
        clock.reset()
        if label == "parity":
            os.environ["REPRO_STACK_VERIFY"] = "parity"
        try:
            out, wall = timed(lambda: run(backend))
        finally:
            os.environ.pop("REPRO_STACK_VERIFY", None)
        health = get_health()
        check(health.n_events == 0, f"{phase}/{backend}/{label}: "
              f"{health.n_events} health events, first "
              f"{[str(e) for e in health.events[:3]]}")
        ties = compare(out, f"{phase}/{backend}/{label}")
        stats[label] = (wall, clock.seconds, clock.count, ties)
    (cold, c_s, c_n, _), (warm, w_s, w_n, _), (par, *_) = (
        stats["cold"], stats["warm"], stats["parity"])
    ties = [stats[label][3] for label in ("cold", "warm", "parity")]
    log(f"{phase:>8} {backend:>6}: cold {cold:.3f} s (compile {c_s:.3f} s, "
        f"{c_n} programs)  warm {warm:.3f} s (compile {w_s:.3f} s, "
        f"{w_n} programs)  parity-verified {par:.3f} s  winners tie-broken "
        f"differently (cold/warm/parity): {'/'.join(map(str, ties))}")


def registry_phase(check, clock, backends=DEVICE_BACKENDS,
                   scenarios=None, machines=None) -> None:
    """The registry's scenarios x machines through ``sweep`` and through a
    ``StrategyService`` per machine."""
    from repro.workloads import DEFAULT_SCENARIOS, default_machines, sweep
    from repro.workloads.registry import scenario_patterns

    scenarios = DEFAULT_SCENARIOS if scenarios is None else scenarios
    machines = default_machines() if machines is None else machines
    patterns = [pat for sc in scenarios for _, pat in scenario_patterns(sc)]
    for name, m in machines.items():
        log(f"registry arena on {name}: {len(patterns)} phases, "
            f"{sum(p.n_msgs for p in patterns)} messages, "
            f"{arena_msgs([p.bind(m) for p in patterns])} in the strategy "
            f"arena, {m.n_procs} ranks")

    def run(backend):
        rows = sweep(scenarios, machines, backend=backend)
        served = {name: service_verdicts(check, m, patterns, backend,
                                         f"registry/{backend}/{name}")
                  for name, m in machines.items()}
        return rows, served

    (ref_rows, ref_served), wall = timed(lambda: run("numpy"))
    log(f"registry  numpy: {wall:.3f} s")
    # sweep rows run machine by machine over the same patterns the
    # services price, so numpy's service verdicts line up with its rows
    ref_verdicts = [v for name in machines for v in ref_served[name]]
    check([(r.model_winner, r.sim_winner) for r in ref_rows]
          == [(v.model_winner, v.sim_winner) for v in ref_verdicts],
          "registry/numpy: sweep rows and service verdicts disagree")

    def compare(out, where):
        rows, served = out
        return compare_rows(check, rows, ref_rows, ref_verdicts,
                            f"{where}/sweep") + sum(
            compare_verdicts(check, served[name], ref_served[name],
                             f"{where}/service/{name}") for name in machines)

    for backend in backends:
        run_backend(check, clock, "registry", backend, run, compare)


def amg_phases(grid: int = AMG_GRID, torus=AMG_TORUS):
    """Bound SpMV halo-exchange phases of every AMG level, as in
    ``examples/comm_model_amg.py`` but at ``torus``'s rank count."""
    from repro.net import blue_waters_machine
    from repro.sparse import (RowPartition, build_hierarchy,
                              elasticity_like_3d, spmv_comm_pattern)

    machine = blue_waters_machine(torus)
    phases = []
    for lvl in build_hierarchy(elasticity_like_3d(grid)):
        n_procs = min(machine.n_procs, max(lvl.A.n_rows // 2, 2))
        cp = spmv_comm_pattern(lvl.A, RowPartition.balanced(lvl.A.n_rows,
                                                            n_procs))
        if cp.n_msgs:
            phases.append(cp.bind(machine))
    return machine, phases


def amg_phase(check, clock, backends=DEVICE_BACKENDS, grid: int = AMG_GRID,
              torus=AMG_TORUS) -> None:
    """The paper's AMG/SpMV strategy sweep in one arena."""
    from repro.comm.strategies import best_strategy_many

    (machine, phases), setup = timed(lambda: amg_phases(grid, torus))
    n_arena = arena_msgs(phases)
    log(f"amg arena on blue_waters: {len(phases)} levels, "
        f"{sum(p.n_msgs for p in phases)} messages, {n_arena} in the "
        f"strategy arena, {machine.n_procs} ranks (set-up {setup:.3f} s)")

    def run(backend):
        return best_strategy_many(phases, seed=0, backend=backend)

    ref, wall = timed(lambda: run("numpy"))
    log(f"     amg  numpy: {wall:.3f} s")
    for backend in backends:
        run_backend(check, clock, "amg", backend, run,
                    lambda out, where: compare_verdicts(check, out, ref,
                                                        where))


def tpu_v5e_4():
    """Four TPU v5e chips as two 2-chip "nodes", shaped like
    :func:`repro.exec.presets.tpu_v5e_8`."""
    from repro.core.params import tpu_v5e
    from repro.core.topology import TorusTopology
    from repro.net.machine import MachineSpec
    return MachineSpec(name="tpu_v5e_4", params=tpu_v5e(),
                       torus=TorusTopology((2, 2), wrap=True),
                       nodes_per_torus_node=1, procs_per_node=2,
                       sockets_per_node=1, link_bw=50e9,
                       torus_over_procs=True, cross_node_locality=1)


def exchange_phase(check, seed: int = 7) -> None:
    """The lowered exchange of every node-aware strategy on a 4-rank mesh,
    against the serial numpy executor."""
    import jax

    from repro.comm import CommPhase
    from repro.exec import build_schedule, executor_program, run_reference
    from repro.launch.mesh import make_rank_mesh

    machine = tpu_v5e_4()
    P = machine.n_procs
    rng = np.random.default_rng(seed)
    src = rng.integers(0, P, EXCHANGE_MSGS)
    dst = (src + rng.integers(1, P, EXCHANGE_MSGS)) % P
    size = rng.integers(256, 8192, EXCHANGE_MSGS).astype(float)
    phase = CommPhase.build(machine, src, dst, size, n_procs=P)
    mesh = make_rank_mesh(P)
    want_devices = set(mesh.devices.flat)
    log(f"exchange on {machine.name}: {EXCHANGE_MSGS} messages, "
        f"{phase.size.sum():.0f} bytes, mesh of {len(want_devices)} devices")
    for strat in ("standard", "two_step", "three_step"):
        sched = build_schedule(phase, strat)
        fn, args = executor_program(sched, mesh)
        compiled, t_compile = timed(lambda: fn.lower(*args).compile())
        check("collective-permute" in compiled.as_text(),
              f"exchange/{strat}: no collective-permute in the program")
        in_devices = {d for s in jax.tree.leaves(compiled.input_shardings)
                      for d in s.device_set}
        out, t_cold = timed(lambda: jax.block_until_ready(compiled(*args)))
        _, t_warm = timed(lambda: jax.block_until_ready(compiled(*args)))
        check(in_devices == want_devices,
              f"exchange/{strat}: inputs on {len(in_devices)} devices")
        check(out.sharding.device_set == want_devices,
              f"exchange/{strat}: output on {len(out.sharding.device_set)} "
              "devices")
        got = np.asarray(out)[:, :sched.n_units]
        mismatches = int((got != run_reference(sched)).sum())
        check(mismatches == 0,
              f"exchange/{strat}: {mismatches} payload units differ")
        log(f"exchange {strat:>10}: {sched.n_units} units, "
            f"{sched.n_rounds} rounds, compile {t_compile:.3f} s, first run "
            f"{t_cold:.6f} s, warm run {t_warm:.6f} s, bit-identical "
            f"{mismatches == 0}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--four-chip", action="store_true",
                    help="run only the lowered exchange on four chips")
    args = ap.parse_args(argv)
    n_chips = 4 if args.four_chip else 1
    devices = tpu_devices(n_chips)

    from repro.launch.compile_cache import use_compile_cache
    log(f"device: {devices[0].device_kind} x{len(devices)}, "
        f"compile cache {use_compile_cache()}")
    check = Check()
    if args.four_chip:
        exchange_phase(check)
    else:
        clock = CompileClock()
        registry_phase(check, clock)
        amg_phase(check, clock)
    if check.failures:
        log(f"{len(check.failures)} check(s) failed")
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
