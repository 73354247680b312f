"""Bytes that a kernel's work needs, from the arena's sizes.

Counted from what the reduction must read and write, not from how the
kernel tiles it, so a kernel that does more than the work needs reads
below its roofline.
"""
from __future__ import annotations

import json
import pathlib

PEAKS = pathlib.Path(__file__).resolve().parent / "peaks.json"


def peaks(device_kind: str) -> dict:
    """The peak table's row for ``device_kind``; a device missing from the
    table is an error."""
    table = json.loads(PEAKS.read_text())["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r}; "
                       f"bench/peaks.json has {sorted(table)}")
    return table[device_kind]


def segreduce_work(arena: dict) -> dict:
    """One strategy sweep's segment-reduce work.

    The sweep reduces three quantities on the device: each candidate
    phase's per-sender transport sums for the model and again for the
    simulator (every arena message into its (phase, sender) segment), and
    the per-link contention (every (link, source unit) pair into its
    link's segment, sum and maximum).  Each element is read once as an
    int32 key and a float32 value, and costs one add and one maximum; each
    segment that receives an element is written once as a float32 sum and
    a float32 maximum.  Segments that receive nothing, such as a kernel's
    padding of every phase to the widest rank count, are no part of the
    work.
    """
    passes = [(arena["messages"], arena["senders"])] * 2
    passes.append((arena["link_sources"], arena["links"]))
    elements = sum(n for n, _ in passes)
    segments = sum(s for _, s in passes)
    return {"bytes": 8.0 * elements + 8.0 * segments}


def roofline_seconds(work: dict, peak: dict) -> float:
    """The least time ``work`` needs on a chip with ``peak``: its bytes at
    the HBM bandwidth.

    The compute side cannot bind: the work does at most 0.25 operations
    per byte (two per element of 8 bytes), so at 819 GB/s it needs 0.2 T
    float32 operations a second, which a vector unit finishing one
    8x128-lane operation a cycle sustains at a 200 MHz clock.  The
    reduction runs on the vector unit, so the matrix unit's published
    bfloat16 peak does not apply to it.
    """
    return work["bytes"] / peak["hbm_bytes_per_s"]
