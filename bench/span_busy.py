"""Device time inside the benchmark's host spans, for per-layer readers.

The benchmark's host spans (``bench.trace.SPANS``) and the devices'
operations share the trace's clock, so the device time of one phase of
the work is the busy time that falls inside that phase's spans.
"""
from __future__ import annotations

from bench import trace as tr


def overlap_ns(a, b) -> float:
    """Length of the intersection of two sorted lists of disjoint
    ``(start, end)`` intervals."""
    total, i, j = 0.0, 0, 0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        total += max(0.0, hi - lo)
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return total


def busy_ms(rec, span: str) -> float | None:
    """The busiest chip's device busy time inside the host spans named
    ``span``, per unit of work, in ms; None where the trace has no such
    span or no busy time in it."""
    t = rec.get("trace")
    if t is None or not rec["units"]:
        return None
    raw = t["_trace"]
    lo, hi = raw["window"]
    spans = sorted((max(s, lo), min(e, hi)) for n, s, e in raw["spans"]
                   if n == span and e > lo and s < hi)
    ns = max((overlap_ns(tr.busy_intervals(ops, raw["window"]), spans)
              for ops in raw["devices"]), default=0.0)
    return ns * 1e-6 / rec["units"] if ns > 0 else None
