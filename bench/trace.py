"""Reduce a profiler trace (``.xplane.pb``) to the benchmark's numbers.

    python -m bench.trace --selftest     # check against the recorded trace

The benchmark's own spans (``jax.profiler.TraceAnnotation`` on the host)
and the device planes' operations share the trace's clock.  Of the traced
window (the ``window`` span) this module gives, per device: busy time (the
union of the intervals in which an operation ran), time by operation name,
the time of the operations whose name or metadata matches a pattern (a
kernel, ``collective-permute``), and the idle gaps labelled by the
innermost benchmark span open at each gap's midpoint.
"""
from __future__ import annotations

import argparse
import bisect
import glob
import json
import os
import pathlib
import re
import sys

#: Host spans the benchmark opens around its calls into the program.
SPANS = ("window", "sweep", "bind", "query", "exchange.dispatch",
         "exchange.combine")

TESTDATA = pathlib.Path(__file__).resolve().parent / "testdata"


def find_trace(root: str) -> str:
    """The one ``.xplane.pb`` file under ``root``."""
    paths = glob.glob(os.path.join(root, "**", "*.xplane.pb"),
                      recursive=True)
    if len(paths) != 1:
        raise RuntimeError(f"expected one trace under {root}, found "
                           f"{len(paths)}")
    return paths[0]


def _label(name: str, module: str) -> str:
    """A short, stable name for an operation: its program and its HLO
    instruction without the instruction's number (``jit_walk/%while``)."""
    op = re.sub(r"\.\d+$", "", name.split(" = ", 1)[0])
    return f"{module.split('(', 1)[0]}/{op}" if module else op


def _text(event) -> str:
    parts = [event.name]
    for _, value in event.stats:
        if isinstance(value, str):
            parts.append(value)
    return " ".join(parts)


def load(path: str, n_devices: int, device_prefix: str = "/device:TPU:",
         op_lines=("XLA Ops",), module_line: str = "XLA Modules") -> dict:
    """Host spans and the first ``n_devices`` devices' operations.

    A device is a plane whose name starts with ``device_prefix``; its
    operations are the events of its first line named in ``op_lines``,
    each labelled with the program (``module_line`` event) it ran in.
    Returns ``{"spans": [(name, start, end)], "window": (start, end),
    "devices": [[(label, start, end, text), ...], ...]}`` in ns, where
    ``text`` is the operation's full name and string metadata.
    """
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    spans, devices = [], []
    for plane in pd.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name in SPANS:
                        spans.append((ev.name, ev.start_ns, ev.end_ns))
        if plane.name.startswith(device_prefix):
            lines = {ln.name: ln for ln in plane.lines}
            line = next((lines[n] for n in op_lines if n in lines), None)
            if line is None:
                continue
            mods = sorted((ev.start_ns, ev.end_ns, ev.name) for ev in
                          (lines[module_line].events
                           if module_line in lines else ()))
            starts = [m[0] for m in mods]
            ops = []
            for ev in line.events:
                k = bisect.bisect_right(starts, ev.start_ns) - 1
                mod = mods[k][2] if k >= 0 and ev.start_ns < mods[k][1] else ""
                ops.append((_label(ev.name, mod), ev.start_ns, ev.end_ns,
                            _text(ev)))
            devices.append((plane.name[len(device_prefix):], ops))
    windows = [(s, e) for n, s, e in spans if n == "window"]
    if len(windows) != 1:
        raise RuntimeError(f"expected one window span, found {len(windows)}")
    devices.sort(key=lambda d: (len(d[0]), d[0]))
    if len(devices) < n_devices:
        raise RuntimeError(f"trace holds {len(devices)} device(s) with "
                           f"operations, expected {n_devices}")
    return {"spans": spans, "window": windows[0],
            "devices": [ops for _, ops in devices[:n_devices]]}


def _clip(ops, window):
    lo, hi = window
    return [(n, max(s, lo), min(e, hi), t) for n, s, e, t in ops
            if e > lo and s < hi]


def busy_intervals(ops, window) -> list[tuple[float, float]]:
    """The union of the operations' intervals inside ``window``."""
    out: list[list[float]] = []
    for _, s, e, _ in sorted(_clip(ops, window), key=lambda o: o[1]):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def busy_ns(ops, window) -> float:
    return sum(e - s for s, e in busy_intervals(ops, window))


def matching_ns(ops, window, pattern: str, field: str = "text") -> float:
    """Time in which an operation ran whose label (``field="label"``) or
    whose name and metadata (``"text"``) contain ``pattern``: the union of
    their intervals, so that operations nested in a loop count once."""
    k = {"label": 0, "text": 3}[field]
    return busy_ns([o for o in ops if pattern in o[k]], window)


def op_seconds(devices, window) -> dict[str, float]:
    """Seconds per operation name, averaged over ``devices``."""
    out: dict[str, float] = {}
    for ops in devices:
        for n, s, e, _ in _clip(ops, window):
            out[n] = out.get(n, 0.0) + (e - s) * 1e-9 / len(devices)
    return out


def idle_by_span(ops, window, spans) -> dict[str, float]:
    """Idle seconds of one device inside ``window``, by the innermost
    benchmark span open at each gap's midpoint (``harness`` where only the
    window is)."""
    lo, hi = window
    edges = [lo]
    for s, e in busy_intervals(ops, window):
        edges += [s, e]
    edges.append(hi)
    # the benchmark's spans nest a few deep and otherwise follow each
    # other, so the spans open at a point start among the last few before it
    inner = sorted((s, e, n) for n, s, e in spans if n != "window")
    starts = [s for s, _, _ in inner]
    out: dict[str, float] = {}
    for a, b in zip(edges[::2], edges[1::2]):
        if b <= a:
            continue
        mid = (a + b) / 2
        k = bisect.bisect_right(starts, mid)
        open_ = [(e - s, n) for s, e, n in inner[max(0, k - 8):k] if mid < e]
        label = min(open_)[1] if open_ else "harness"
        out[label] = out.get(label, 0.0) + (b - a) * 1e-9
    return out


def top(d: dict[str, float], n: int = 10) -> list[list]:
    return [[k, v] for k, v in sorted(d.items(), key=lambda kv: -kv[1])[:n]]


def reduce(path: str, n_devices: int, **kw) -> dict:
    """The numbers the harness and the per-layer readers take from one
    trace: window and busy seconds per device, the busiest device, the
    breakdown, and the raw operations for pattern queries."""
    t = load(path, n_devices, **kw)
    w = t["window"]
    busy = [busy_ns(ops, w) * 1e-9 for ops in t["devices"]]
    hot = max(range(len(busy)), key=busy.__getitem__)
    return {
        "window_s": (w[1] - w[0]) * 1e-9,
        "busy_s": busy,
        "busiest": hot,
        "breakdown": {
            "device_ops": top(op_seconds(t["devices"], w)),
            "idle_gaps": top(idle_by_span(t["devices"][hot], w,
                                          t["spans"]))},
        "_trace": t,
    }


def kernel_seconds(reduced: dict, pattern: str, device: int | None = None,
                   field: str = "text") -> float:
    """Seconds of the operations matching ``pattern`` (see
    :func:`matching_ns`) on ``device`` (the busiest by default) inside the
    window."""
    t = reduced["_trace"]
    dev = reduced["busiest"] if device is None else device
    return matching_ns(t["devices"][dev], t["window"], pattern,
                       field) * 1e-9


# -- self-check --------------------------------------------------------------

def _brute_busy_ns(ops, window) -> int:
    """Busy time by marking every covered nanosecond (slow, independent)."""
    import numpy as np
    lo, hi = (int(window[0]), int(window[1]))
    mark = np.zeros(hi - lo, dtype=bool)
    for _, s, e, _ in ops:
        a, b = max(int(s), lo), min(int(e), hi)
        if b > a:
            mark[a - lo:b - lo] = True
    return int(mark.sum())


def selftest() -> int:
    """Reduce the trace recorded on the CPU in ``testdata`` (its host
    thread pool's XLA line stands in for a device) and check busy time
    against a nanosecond-by-nanosecond count, idle time against the window,
    and every figure against the values written when it was recorded."""
    path = str(TESTDATA / "cpu_trace.xplane.pb")
    want = json.loads((TESTDATA / "cpu_trace.expected.json").read_text())
    kw = dict(device_prefix="/host:CPU", op_lines=(want["op_line"],))
    t = load(path, 1, **kw)
    r = reduce(path, 1, **kw)
    ops, w = t["devices"][0], t["window"]
    got = {
        "window_ns": w[1] - w[0],
        "busy_ns": busy_ns(ops, w),
        "brute_busy_ns": _brute_busy_ns(ops, w),
        "idle_ns": round(sum(idle_by_span(ops, w, t["spans"]).values())
                         * 1e9),
        "dot_ns": matching_ns(ops, w, "dot"),
        "top_op": r["breakdown"]["device_ops"][0][0],
        "idle_labels": sorted(idle_by_span(ops, w, t["spans"])),
    }
    fails = []
    if got["busy_ns"] != got["brute_busy_ns"]:
        fails.append(f"union {got['busy_ns']} != brute {got['brute_busy_ns']}")
    if abs(got["busy_ns"] + got["idle_ns"] - got["window_ns"]) > 2:
        fails.append("busy + idle != window")
    for k, v in want["values"].items():
        if got[k] != v:
            fails.append(f"{k}: {got[k]!r} != recorded {v!r}")
    print(json.dumps(got))
    for f in fails:
        print("FAIL", f, file=sys.stderr)
    return 1 if fails else 0


def names(path: str, n: int = 25) -> dict:
    """Every plane and line of a trace with its commonest event names, and
    the metadata of one event per name: what to look at before writing a
    pattern against a new trace."""
    from jax.profiler import ProfileData
    out = {}
    for plane in ProfileData.from_file(path).planes:
        lines = {}
        for line in plane.lines:
            count: dict[str, int] = {}
            meta: dict[str, str] = {}
            for ev in line.events:
                count[ev.name] = count.get(ev.name, 0) + 1
                if ev.name not in meta:
                    meta[ev.name] = _text(ev)[:300]
            lines[line.name] = [[k, v, meta[k]] for k, v in sorted(
                count.items(), key=lambda kv: -kv[1])[:n]]
        out[plane.name] = lines
    return out


if __name__ == "__main__":
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--selftest", action="store_true")
    ap.add_argument("--names", action="store_true",
                    help="list planes, lines and event names instead")
    ap.add_argument("path", nargs="?", help="a directory holding one trace")
    ap.add_argument("--devices", type=int, default=1)
    a = ap.parse_args()
    if a.selftest:
        sys.exit(selftest())
    if a.names:
        print(json.dumps(names(find_trace(a.path)), indent=1))
        sys.exit(0)
    r = reduce(find_trace(a.path), a.devices)
    r.pop("_trace")
    print(json.dumps(r, indent=1))
