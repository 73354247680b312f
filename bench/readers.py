"""What the per-layer metric readers share.

Each reader in ``bench/metrics`` takes the run's record: ``units`` (whole
units of work in the window), ``compiles`` (backend compilations in it),
``cache`` (the service's cache counters, where the traffic has a
service), ``trace`` (the reduction of :mod:`bench.trace`), ``work`` (the
arena's sizes, or the exchange's bytes per chip) and ``device_kind``.  A
reader that finds nothing to read returns None, and the metric is left out
of the line.
"""
from __future__ import annotations

from bench import counts
from bench import trace as tr

#: Trace name fragments, each with the field it is looked for in.  The
#: Pallas segment reduce is the program's one Pallas kernel; its op is a
#: ``tpu_custom_call`` named after the unnamed ``functools.partial`` jitted
#: around it (``_unknown_``), not after the kernel, so it is found by its
#: metadata.  The exchange's rounds are ``collective-permute`` ops, found
#: by their own name (other ops' metadata can name them).  The queue walk
#: is the program ``jit_walk``: its ``while`` loop and the ops nested in it.
SEGREDUCE = ("tpu_custom_call", "text")
PERMUTE = ("/%collective-permute", "label")
WALK = ("jit_walk/", "label")


def idle_share(rec) -> float | None:
    """Idle share of the busiest chip over the traced window, in %."""
    t = rec.get("trace")
    if t is None or t["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - max(t["busy_s"]) / t["window_s"])


def kernel_ms(rec, pattern: tuple[str, str]) -> float | None:
    """Device milliseconds per unit of work of the operations matching
    ``pattern`` (fragment, field) on the busiest chip."""
    t = rec.get("trace")
    if t is None or not rec["units"]:
        return None
    s = tr.kernel_seconds(t, pattern[0], field=pattern[1])
    return s / rec["units"] * 1e3 if s > 0 else None


def segreduce_roofline(rec) -> float | None:
    """Least time of one sweep's segment-reduce work over its measured
    time, in %."""
    ms = kernel_ms(rec, SEGREDUCE)
    if ms is None or not rec.get("work"):
        return None
    least = counts.roofline_seconds(counts.segreduce_work(rec["work"]),
                                    counts.peaks(rec["device_kind"]))
    return 100.0 * least / (ms * 1e-3)


def exchange_roofline(rec) -> float | None:
    """Least time of one exchange (dispatch and combine) over the busiest
    chip's busy time per exchange, in %: the window runs only the
    exchange's two programs."""
    t = rec.get("trace")
    if t is None or not rec["units"] or not rec.get("work"):
        return None
    busy = max(t["busy_s"]) / rec["units"]
    if busy <= 0:
        return None
    least = counts.exchange_seconds(rec["work"],
                                    counts.peaks(rec["device_kind"]))
    return 100.0 * least / busy


def compiles(rec) -> float:
    return float(rec["compiles"])


def cache_hit_share(rec) -> float | None:
    c = rec.get("cache")
    if not c or not c["hits"] + c["misses"]:
        return None
    return 100.0 * c["hits"] / (c["hits"] + c["misses"])
