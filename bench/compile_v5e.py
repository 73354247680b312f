"""Compile the four-chip exchange's programs for a described v5e 2x2.

    JAX_PLATFORMS=cpu python bench/compile_v5e.py \\
        [--config deepseek_moe16b_ep4_v5e] [--traffic exchange]

Builds the exchange's dispatch and combine schedules at their real size
from the traffic's ``routing_seed``, as set-up does, and compiles each
program for four described (not attached) TPU v5e chips: what the chip's
compiler would refuse, and the bytes each program needs per chip, show
here at no chip time.  Nothing
runs, so nothing here is a measurement.
"""
from __future__ import annotations

import argparse
import json
import os
import pathlib
import sys
import time

os.environ.setdefault("TPU_LOG_DIR", "disabled")
ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--config", default="deepseek_moe16b_ep4_v5e")
    ap.add_argument("--traffic", default="exchange")
    a = ap.parse_args()

    import jax
    import numpy as np
    from jax.experimental import topologies

    from bench import deploy, moe
    from bench.run import BENCH, load_json
    from repro.comm import CommPhase
    from repro.comm.strategies import best_strategy_many
    from repro.exec import build_schedule
    from repro.exec.lower import executor_program

    cfg = load_json(BENCH / "configs" / f"{a.config}.json")
    mix = load_json(BENCH / "traffic" / f"{a.traffic}.json")
    n = int(cfg["expert_parallel"])
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    mesh = jax.sharding.Mesh(np.asarray(topo.devices[:n]), ("rank",))
    shard = jax.sharding.NamedSharding(mesh, jax.sharding.PartitionSpec("rank"))
    machine = deploy.machine(cfg["machine"])
    msgs = moe.draw(cfg, mix["tokens_per_rank"], mix["routing_seed"])
    phases = [CommPhase.build(machine, s, d, z, n_procs=n) for s, d, z in msgs]
    verdicts = best_strategy_many(phases, strategies=tuple(mix["strategies"]),
                                  backend="numpy")
    for name, phase, v in zip(("dispatch", "combine"), phases, verdicts):
        t0 = time.perf_counter()
        sched = build_schedule(phase, v.model_winner,
                               unit_bytes=float(mix["unit_bytes"]))
        t_build = time.perf_counter() - t0
        fn, args = executor_program(sched, mesh)
        shapes = jax.tree.map(lambda x: jax.ShapeDtypeStruct(
            np.shape(x), np.asarray(x).dtype, sharding=shard), args)
        t0 = time.perf_counter()
        compiled = fn.lower(*shapes).compile()
        mem = compiled.memory_analysis()
        print(json.dumps({
            "phase": name, "strategy": v.model_winner,
            "messages": int(phase.n_msgs), "units": int(sched.n_units),
            "rounds": int(sched.n_rounds), "build_s": round(t_build, 3),
            "compile_s": round(time.perf_counter() - t0, 3),
            "collective_permute": "collective-permute" in compiled.as_text(),
            "argument_bytes": int(mem.argument_size_in_bytes),
            "output_bytes": int(mem.output_size_in_bytes),
            "temp_bytes": int(mem.temp_size_in_bytes)}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
