"""Benchmark harness: one benchmark per paper table/figure + kernels +
roofline.  Prints ``name,us_per_call,derived`` CSV."""
from __future__ import annotations

import sys
import traceback


def main() -> None:
    from repro.launch.compile_cache import use_compile_cache

    use_compile_cache()
    from . import (bench_paper, bench_kernels, bench_roofline, bench_delta,
                   bench_llm_workloads, bench_faults, bench_exec)
    print("name,us_per_call,derived")
    failures = 0
    for mod in (bench_paper, bench_kernels, bench_roofline, bench_delta,
                bench_llm_workloads, bench_faults, bench_exec):
        for bench in mod.ALL_BENCHES:
            try:
                for (name, us, derived) in bench():
                    print(f"{name},{us:.1f},{derived:.6g}", flush=True)
            except Exception:  # noqa: BLE001
                failures += 1
                print(f"{bench.__name__},nan,nan  # FAILED", flush=True)
                traceback.print_exc(file=sys.stderr)
    if failures:
        sys.exit(1)


if __name__ == "__main__":
    main()
