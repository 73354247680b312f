"""Record the small CPU trace that ``python -m bench.trace --selftest``
reduces, and the figures it must give.

    JAX_PLATFORMS=cpu python bench/testdata/record_cpu_trace.py

Rewrite the expected figures only after checking the new trace by hand.
"""
import json
import pathlib
import shutil
import sys
import tempfile
import time

import jax
import jax.numpy as jnp

HERE = pathlib.Path(__file__).resolve().parent
sys.path.insert(0, str(HERE.parents[1]))
from bench import trace  # noqa: E402


def main():
    f = jax.jit(lambda x: (x @ x).sum())
    x = jnp.ones((384, 384))
    f(x).block_until_ready()
    tmp = tempfile.mkdtemp()
    try:
        jax.profiler.start_trace(tmp)
        with jax.profiler.TraceAnnotation("window"):
            for name in ("sweep", "query"):
                with jax.profiler.TraceAnnotation(name):
                    for _ in range(3):
                        f(x).block_until_ready()
                    time.sleep(0.002)
            time.sleep(0.002)
        jax.profiler.stop_trace()
        shutil.copy(trace.find_trace(tmp), HERE / "cpu_trace.xplane.pb")
    finally:
        shutil.rmtree(tmp)
    line = next(ln.name for p in jax.profiler.ProfileData.from_file(
        str(HERE / "cpu_trace.xplane.pb")).planes if p.name == "/host:CPU"
        for ln in p.lines if ln.name.startswith("tf_XLAPjRtCpuClient"))
    (HERE / "cpu_trace.expected.json").write_text(json.dumps(
        {"op_line": line, "values": {}}))
    t = trace.load(str(HERE / "cpu_trace.xplane.pb"), 1,
                   device_prefix="/host:CPU", op_lines=(line,))
    ops, w = t["devices"][0], t["window"]
    values = {"window_ns": w[1] - w[0],
              "busy_ns": trace._brute_busy_ns(ops, w),
              "dot_ns": trace._brute_busy_ns(
                  [o for o in ops if "dot" in o[3]], w)}
    (HERE / "cpu_trace.expected.json").write_text(json.dumps(
        {"op_line": line, "values": values}, indent=1))
    print(values)


if __name__ == "__main__":
    main()
