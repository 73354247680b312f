"""Record the small CPU trace with nested program spans that
``python3 bench/spans.py --selftest`` reduces, and the figures it must give.

    JAX_PLATFORMS=cpu python bench/testdata/record_span_trace.py

A benchmark ``sweep`` holds a bind and a program sweep; inside the sweep's
model span ten closed ``repro.kernel.layout`` siblings come before an idle
gap, which must be labelled ``repro.plan.model``.  Rewrite the expected
figures only after checking the new trace by hand.
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
sys.path[:0] = [str(HERE.parents[1] / "src"), str(HERE.parents[1])]
from bench import spans  # noqa: E402
from repro.comm import obs  # noqa: E402

COUNTERS = {"device.syncs": 1, "device.h2d_bytes": 2_500_000}


def main():
    f = jax.jit(lambda x: (x @ x).sum())
    x = jnp.ones((384, 384))
    f(x).block_until_ready()
    tmp = tempfile.mkdtemp()
    A = jax.profiler.TraceAnnotation
    try:
        jax.profiler.start_trace(tmp)
        obs.enable()
        with A("window"):
            with A("sweep"):
                with obs.span("repro.plan.bind"):
                    time.sleep(0.001)
                with obs.span("repro.plan.sweep", patterns=2, candidates=6,
                              messages=100):
                    with obs.span("repro.plan.model"):
                        for _ in range(10):
                            with obs.span("repro.kernel.layout"):
                                time.sleep(0.0002)
                        time.sleep(0.002)
                        with obs.span("repro.device.kernel.segment_reduce"):
                            y = f(x)
                            with obs.span("repro.device.sync"):
                                y.block_until_ready()
                    with obs.span("repro.plan.verdict"):
                        time.sleep(0.001)
                time.sleep(0.001)
            time.sleep(0.002)
        obs.disable()
        jax.profiler.stop_trace()
        shutil.copy(spans.tr.find_trace(tmp),
                    HERE / "cpu_span_trace.xplane.pb")
    finally:
        shutil.rmtree(tmp)
    line = next(ln.name for p in jax.profiler.ProfileData.from_file(
        str(HERE / "cpu_span_trace.xplane.pb")).planes
        if p.name == "/host:CPU" for ln in p.lines
        if ln.name.startswith("tf_XLAPjRtCpuClient"))
    got, brute = spans.figures(str(HERE / "cpu_span_trace.xplane.pb"), line,
                               COUNTERS)
    print(json.dumps(got), "\nbrute force:", brute)
    (HERE / "cpu_span_trace.expected.json").write_text(json.dumps(
        {"op_line": line, "counters": COUNTERS, "values": got}, indent=1))

if __name__ == "__main__":
    main()
