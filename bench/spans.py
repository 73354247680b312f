"""Split a traced cell's window by the program's own spans.

    python3 bench/spans.py --workload <name> --seed <n> --seconds <s>
    python3 bench/spans.py --selftest      # check against a recorded trace

Runs one cell as ``bench/run.py --trace 1`` does: set-up, then the window
under the profiler, with the program's spans and counters
(:mod:`repro.comm.obs`) switched on for the window alone; the same run
with them off is ``bench/run.py --trace 1`` at the same seed, which never
switches them on.  Prints one JSON object: the
window's end-to-end numbers, the counters per unit of work, each program
span's time per unit (``ms``) and that time less the ``repro.device.sync``
spans nested in it (``self_ms``), how much of each parent span its
children cover, and the device's idle gaps labelled by the innermost span
of either kind, the benchmark's or the program's, open on the window's
thread.  Without a TPU it exits non-zero, as the harness does.

:func:`span_ms` and :func:`counter_per_unit` read a record shaped as the
harness's readers get it (``units``, ``counters``, ``trace``), with the
program's spans under ``trace["_trace"]["program_spans"]``.
"""
from __future__ import annotations

import argparse
import bisect
import json
import pathlib
import shutil
import sys
import tempfile
import time

BENCH = pathlib.Path(__file__).resolve().parent
for _p in (str(BENCH.parent / "src"), str(BENCH.parent)):
    if _p not in sys.path:
        sys.path.insert(0, _p)
sys.path[:] = [p for p in sys.path
               if pathlib.Path(p or ".").resolve() != BENCH]

from bench import trace as tr  # noqa: E402

PREFIX = "repro."
SYNC = "repro.device.sync"

#: Parent spans and the children that should cover them.
CHILDREN = {
    "repro.plan.sweep": ("repro.plan.bind", "repro.plan.rewrite",
                         "repro.plan.arrivals", "repro.plan.arena",
                         "repro.plan.model", "repro.plan.simulate",
                         "repro.plan.verdict"),
    "repro.service.query": ("repro.service.validate", "repro.service.admit",
                            "repro.service.key", "repro.service.cache",
                            "repro.service.sweep"),
    "sweep": ("repro.plan.bind", "repro.plan.sweep"),
}

TESTDATA = BENCH / "testdata"


# -- reading a trace ---------------------------------------------------------

def program_spans(path: str, window) -> tuple[list, int]:
    """The host events of the trace whose names start with ``repro.``, as
    ``(name, start, end, line)`` in ns, and the line (thread) that holds
    the ``window`` span; a line is numbered by its order in the trace."""
    from jax.profiler import ProfileData
    spans, window_line, k = [], None, -1
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            k += 1
            for ev in line.events:
                if ev.name.startswith(PREFIX):
                    spans.append((ev.name, ev.start_ns, ev.end_ns, k))
                elif (ev.name == "window"
                      and (ev.start_ns, ev.end_ns) == tuple(window)):
                    window_line = k
    if window_line is None:
        raise RuntimeError("the window span is on no host line")
    return spans, window_line


def innermost(points, spans) -> list:
    """For each of the sorted ``points``, the innermost of ``spans``
    (``(name, start, end)`` tuples of one thread, which nest or follow each
    other) open at it, or None: however many closed spans come before."""
    order = sorted(spans, key=lambda s: (s[1], -s[2]))
    out, stack, k = [], [], 0
    for t in points:
        while k < len(order) and order[k][1] <= t:
            while stack and stack[-1][2] <= order[k][1]:
                stack.pop()
            stack.append(order[k])
            k += 1
        while stack and stack[-1][2] <= t:
            stack.pop()
        out.append(stack[-1] if stack else None)
    return out


def idle_by_span(ops, window, spans) -> dict[str, float]:
    """Idle seconds of one device inside ``window``, each idle instant
    labelled by the innermost of ``spans`` (``(name, start, end)``, the
    window's thread) open at it, ``harness`` where only the window is: a
    gap that outlasts a span is split at the span's edges."""
    lo, hi = window
    edges = [lo]
    for s, e in tr.busy_intervals(ops, window):
        edges += [s, e]
    edges.append(hi)
    inner = [s for s in spans if s[0] != "window"]
    cuts = sorted({t for _, s, e in inner for t in (s, e) if lo < t < hi})
    pieces = []
    for a, b in zip(edges[::2], edges[1::2]):
        if b > a:
            pts = [a, *cuts[bisect.bisect_right(cuts, a):
                            bisect.bisect_left(cuts, b)], b]
            pieces += zip(pts, pts[1:])
    out: dict[str, float] = {}
    for (a, b), sp in zip(pieces, innermost([(a + b) / 2 for a, b in pieces],
                                            inner)):
        label = sp[0] if sp is not None else "harness"
        out[label] = out.get(label, 0.0) + (b - a) * 1e-9
    return out


def _union(intervals) -> list[list[float]]:
    out: list[list[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _overlap_ns(a, b) -> float:
    """Length of the intersection of two unions of intervals."""
    a, b = _union(a), _union(b)
    i = j = 0
    total = 0.0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if hi > lo:
            total += hi - lo
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return total


def _by_line(spans, name, window):
    lo, hi = window
    out: dict[int, list] = {}
    for n, s, e, line in spans:
        if n == name and e > lo and s < hi:
            out.setdefault(line, []).append((max(s, lo), min(e, hi)))
    return out


def span_ns(spans, name: str, window, less_syncs: bool = True
            ) -> float | None:
    """Time inside ``window`` covered by the spans named ``name``, less the
    ``repro.device.sync`` spans nested in them on the same thread when
    ``less_syncs``; None when no such span ran."""
    own = _by_line(spans, name, window)
    if not own:
        return None
    syncs = _by_line(spans, SYNC, window) if less_syncs and name != SYNC \
        else {}
    return sum(sum(e - s for s, e in _union(iv))
               - _overlap_ns(iv, syncs.get(line, []))
               for line, iv in own.items())


def coverage(spans, parent: str, children, window) -> tuple[float, float]:
    """(over all, worst single) share of the ``parent`` spans' time that
    the ``children`` spans on the same thread cover; spans are
    ``(name, start, end, line)``."""
    kids: dict[int, list] = {}
    for c in children:
        for line, iv in _by_line(spans, c, window).items():
            kids.setdefault(line, []).extend(iv)
    covered = total = 0.0
    worst = 1.0
    for line, iv in _by_line(spans, parent, window).items():
        for s, e in iv:
            got = _overlap_ns([(s, e)], kids.get(line, []))
            covered += got
            total += e - s
            if e > s:
                worst = min(worst, got / (e - s))
    return (covered / total if total else 0.0), worst


# -- readers -----------------------------------------------------------------

def span_ms(rec, name: str) -> float | None:
    """Milliseconds per unit of work of the program spans named ``name``
    inside the window, less the ``repro.device.sync`` time nested in them;
    None where the program opened no such span."""
    t = rec.get("trace")
    if t is None or not rec["units"]:
        return None
    tt = t["_trace"]
    ns = span_ns(tt.get("program_spans") or [], name, tt["window"])
    return None if ns is None else ns * 1e-6 / rec["units"]


def counter_per_unit(rec, name: str, scale: float = 1.0) -> float | None:
    """The program counter ``name`` over the window, times ``scale``, per
    unit of work; None where the program kept no such counter."""
    c = rec.get("counters")
    if not c or name not in c or not rec["units"]:
        return None
    return c[name] * scale / rec["units"]


# -- one traced run ----------------------------------------------------------

def measure(c: dict, seed: int, seconds: float, devices,
            trace_dir: str) -> dict:
    """Set up the cell ``c`` (as :func:`bench.run.cell` gives it) and run
    its window under the profiler, the program's spans and counters on
    for the window alone; the trace is written under ``trace_dir``."""
    import jax

    from bench import run as harness
    from repro.comm import obs

    t0 = time.perf_counter()
    kind = harness.load_module(BENCH / "kinds" / f"{c['mix']['kind']}.py")
    cell = kind.Cell(c["config"], c["mix"], seed, devices,
                     jax.profiler.TraceAnnotation)
    cell.setup()
    setup_s = time.perf_counter() - t0
    jax.profiler.start_trace(trace_dir)
    obs.reset()
    obs.enable()
    try:
        with jax.profiler.TraceAnnotation("window"):
            out = cell.window(seconds)
    finally:
        obs.disable()
        jax.profiler.stop_trace()
    cell.release()
    return {"units": out["units"], "failed": out["failed"],
            "e2e": out["e2e"], "setup_s": setup_s,
            "counters": obs.counters()}


def breakdown(path: str, n_devices: int, run: dict, **trace_kw) -> dict:
    """The split of one traced window (``run`` as :func:`measure` returns
    it) by program span, with the counters per unit and the idle gaps."""
    reduced = tr.reduce(path, n_devices, **trace_kw)
    t = reduced["_trace"]
    prog, line = program_spans(path, t["window"])
    t["program_spans"] = prog
    rec = {"units": run["units"], "counters": run["counters"],
           "trace": reduced}
    names = sorted({n for n, *_ in prog})
    own = [(n, s, e) for n, s, e, ln in prog if ln == line]
    bench_spans = [(n, s, e, line) for n, s, e in t["spans"]]
    idle = idle_by_span(t["devices"][reduced["busiest"]], t["window"],
                        sorted(own + list(t["spans"]),
                               key=lambda sp: sp[1]))
    idle_s = sum(idle.values())
    cover = {}
    for parent, kids in CHILDREN.items():
        total, worst = coverage(prog + bench_spans, parent, kids,
                                t["window"])
        if total:
            cover[parent] = {"share": total, "worst": worst}
    return {
        "units": run["units"], "failed": run["failed"], "e2e": run["e2e"],
        "setup_s": run["setup_s"], "window_s": reduced["window_s"],
        "busy_s": max(reduced["busy_s"]),
        "counters_per_unit": {k: counter_per_unit(rec, k)
                              for k in sorted(run["counters"])},
        "span_ms": {n: {"ms": span_ns(prog, n, t["window"], False)
                        * 1e-6 / run["units"],
                        "self_ms": span_ms(rec, n)}
                    for n in names},
        "coverage": cover,
        "idle_s": idle_s,
        "idle_program_share": (sum(v for k, v in idle.items()
                                   if k.startswith(PREFIX)) / idle_s
                               if idle_s else 0.0),
        "idle_gaps": tr.top(idle, 25),
        "device_ops": reduced["breakdown"]["device_ops"],
    }


# -- self-check --------------------------------------------------------------

def _brute_idle(ops, window, spans) -> dict[str, int]:
    """Idle ns by the smallest span holding each nanosecond (slow,
    independent of :func:`innermost`)."""
    import numpy as np
    lo, hi = int(window[0]), int(window[1])
    names = ["harness"]
    label = np.zeros(hi - lo, dtype=np.int32)
    for n, s, e in sorted((sp for sp in spans if sp[0] != "window"),
                          key=lambda sp: sp[1] - sp[2]):  # widest first
        names.append(n)
        label[max(int(s), lo) - lo:min(int(e), hi) - lo] = len(names) - 1
    idle = np.ones(hi - lo, dtype=bool)
    for _, s, e, _ in ops:
        idle[max(int(s), lo) - lo:max(min(int(e), hi) - lo, 0)] = False
    out: dict[str, int] = {}
    for k, n in enumerate(np.bincount(label[idle], minlength=len(names))):
        if n:
            out[names[k]] = out.get(names[k], 0) + int(n)
    return out


def figures(path: str, op_line: str, counters: dict) -> tuple[dict, dict]:
    """The figures the self-check compares, from a trace recorded on the
    CPU (its host thread pool's XLA line ``op_line`` stands in for a
    device), and the idle labels found by brute force."""
    kw = dict(device_prefix="/host:CPU", op_lines=(op_line,))
    run = {"units": 1, "failed": 0, "e2e": {}, "setup_s": 0.0,
           "counters": counters}
    b = breakdown(path, 1, run, **kw)
    t = tr.load(path, 1, **kw)
    prog, line = program_spans(path, t["window"])
    own = [(n, s, e) for n, s, e, ln in prog if ln == line] + t["spans"]
    got = {"idle_ns": {k: round(v * 1e9) for k, v in b["idle_gaps"]},
           "span_self_ms": {k: v["self_ms"]
                            for k, v in b["span_ms"].items()},
           "coverage": b["coverage"],
           "h2d_mb": b["counters_per_unit"]["device.h2d_bytes"] * 1e-6}
    return got, _brute_idle(t["devices"][0], t["window"], own)


def selftest() -> int:
    """Reduce the recorded CPU trace with nested program spans; check the
    idle labels against a brute-force search (among them the gap behind
    ten closed sibling spans) and every figure against the values written
    when it was recorded."""
    want = json.loads((TESTDATA / "cpu_span_trace.expected.json")
                      .read_text())
    got, brute = figures(str(TESTDATA / "cpu_span_trace.xplane.pb"),
                         want["op_line"], want["counters"])
    fails = []
    if set(got["idle_ns"]) != set(brute):
        fails.append(f"idle labels {sorted(got['idle_ns'])} != brute "
                     f"{sorted(brute)}")
    for k, v in brute.items():
        if abs(got["idle_ns"].get(k, 0) - v) > 4:
            fails.append(f"idle {k}: {got['idle_ns'].get(k)} != brute {v}")
    for k, v in want["values"].items():
        if got[k] != v:
            fails.append(f"{k}: {got[k]!r} != recorded {v!r}")
    print(json.dumps(got))
    for f in fails:
        print("FAIL", f, file=sys.stderr)
    return 1 if fails else 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--selftest", action="store_true")
    ap.add_argument("--workload")
    ap.add_argument("--seed", type=int)
    ap.add_argument("--seconds", type=float)
    a = ap.parse_args(argv)
    if a.selftest:
        return selftest()
    if a.workload is None or a.seed is None or a.seconds is None:
        ap.error("--workload, --seed and --seconds are required")
    from bench import run as harness
    c = harness.cell(a.workload)
    harness.use_compile_cache()
    devices = harness.tpu_devices(int(c["workload"]["chips"]))
    tmp = tempfile.mkdtemp(prefix="bench-spans-")
    try:
        run = measure(c, a.seed, a.seconds, devices, tmp)
        out = breakdown(tr.find_trace(tmp), len(devices), run)
    finally:
        shutil.rmtree(tmp, ignore_errors=True)
    out = {"workload": a.workload, "seed": a.seed,
           "device": devices[0].device_kind, **out}
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
