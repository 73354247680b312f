"""Bytes that a kernel's work needs, from the arena's sizes or the
exchange's messages.

Counted from what the reduction must read and write, not from how the
kernel tiles it, so a kernel that does more than the work needs reads
below its roofline.
"""
from __future__ import annotations

import json
import math
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


def exchange_work(messages, n_ranks: int, unit_bytes: float) -> dict:
    """The exchange's work per chip, from its messages alone.

    ``messages`` holds one ``(src, dst, size)`` set per phase (dispatch,
    combine).  Each message carries ``max(1, ceil(size / unit_bytes))``
    int32 words: its sender reads each word once from HBM and sends it
    once over ICI, and its receiver writes each word once to HBM.  Counted
    from the messages and not from a schedule's rounds, so a plan that
    relays words through a middle chip does more than this work, and reads
    lower on its roofline.
    """
    sent = [0.0] * n_ranks
    received = [0.0] * n_ranks
    for src, dst, size in messages:
        words = [max(1, math.ceil(float(z) / unit_bytes)) for z in size]
        for s, d, w in zip(src, dst, words):
            sent[int(s)] += w
            received[int(d)] += w
    return {"hbm_bytes": [4.0 * (s + r) for s, r in zip(sent, received)],
            "ici_bytes": [4.0 * s for s in sent]}


def exchange_seconds(work: dict, peak: dict) -> float:
    """The least time of the exchange's ``work``: on the chip where it is
    largest, the larger of its HBM bytes at the HBM bandwidth and its ICI
    egress at the chip's whole published interconnect rate."""
    ici = peak["ici_bits_per_s"] / 8.0
    return max(max(h / peak["hbm_bytes_per_s"], i / ici)
               for h, i in zip(work["hbm_bytes"], work["ici_bytes"]))
