"""The readings that each limit of ``correct`` is set from, on the chip.

    python bench/control.py --workload <name> --seeds <n,n,...> \\
        [--control <k>] [--seconds <s>]

For every seed, the cell as a run sets it up, a short window at the
cell's own load, and the run's comparison with the plain reference: the
program's reading.  For the first ``k`` seeds also the control's reading:
the same comparison with the reference priced in bfloat16 put in the
program's place (for the exchange, every payload word carried through
float32).  One JSON line per seed.  The benchmark's own runs never run the
control.
"""
from __future__ import annotations

import argparse
import json
import sys

import run


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control", type=int, default=3)
    ap.add_argument("--seconds", type=float, default=1.0)
    a = ap.parse_args(argv)
    c = run.cell(a.workload)
    run.use_compile_cache()
    devices = run.tpu_devices(int(c["workload"]["chips"]))
    import jax
    kind = run.load_module(run.BENCH / "kinds"
                             / f"{c['mix']['kind']}.py")
    cell = None
    for k, seed in enumerate(int(s) for s in a.seeds.split(",")):
        if cell is not None and hasattr(cell, "reseed"):
            cell.reseed(seed)
        else:
            cell = kind.Cell(c["config"], c["mix"], seed, devices,
                             jax.profiler.TraceAnnotation)
            cell.setup()
        out = cell.window(a.seconds)
        cell.release()
        line = {"seed": seed, "units": out["units"], "failed": out["failed"],
                "program": {n: v for n, v, _ in cell.check()}}
        if k < a.control:
            line["control"] = {n: v for n, v, _ in cell.check(control=True)}
        print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
