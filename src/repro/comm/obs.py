"""Spans and counters inside the planner, off unless switched on.

The planner's hot path (:func:`repro.comm.best_strategy_many`, the
:class:`repro.serve.StrategyService` request path and the device kernels
of :mod:`repro.kernels.comm_stack`) opens a named span at each layer
boundary and counts its device traffic here.  With tracing on, a span is
a ``jax.profiler.TraceAnnotation``: it lands on the profiler's host plane,
on the same clock as the device planes, so a trace taken with
``jax.profiler.trace`` attributes each idle gap of the device to the host
work around it.  Keyword stats given to :func:`span` arrive as the
event's stats; the event's name stays as given.

Spans (all named ``repro.<layer>.<what>``):

==============================  ============================================
``repro.plan.sweep``            one ``best_strategy_many`` call (stats
                                ``patterns``, ``candidates``, ``messages``)
``repro.plan.bind``             binding patterns to the machine
``repro.plan.rewrite``          every strategy rewrite of a sweep
``repro.plan.arrivals``         every seeded random-arrival draw of a sweep
``repro.plan.arena``            one arena build (``PhaseStack.build``)
``repro.plan.model``            model-ladder pricing (``phase_cost_many``)
``repro.plan.simulate``         simulator pricing (``simulate_many``)
``repro.plan.verdict``          a sweep's totals and winners
``repro.sim.routing``           the host routing expansion of contention
``repro.kernel.layout``         host layouts of the device kernels
``repro.device.<site>``         one device call, named by its fault site
``repro.device.sync``           the host blocked on a device->host copy
``repro.service.query``         one ``query_many`` request (stat
                                ``request``, the service's sequence number)
``repro.service.<station>``     ``validate``, ``admit``, ``key``, ``cache``
                                and ``sweep``: one request station each
``repro.workload.route``        one MoE routing decision of every token
                                (stats ``tokens``, ``groups``)
``repro.workload.lower``        one MoE routing lowered to its exchange
==============================  ============================================

Counters: ``device.syncs`` and ``device.d2h_bytes`` (device->host copies,
:func:`repro.kernels.comm_stack.to_host`), ``device.h2d_bytes`` (host
arrays shipped, :func:`repro.kernels.comm_stack.to_device` and
``count_shipped``),
``device.calls.<site>`` (device calls per fault site) and
``rewrite.fan_passes`` (the aggregated rewrites' masked fan-out passes,
one per injector rank that only some messages reach: 0 where every node
is full), and per MoE lowering ``moe.expert_copies`` (off-rank (token,
expert) assignments), ``moe.token_copies`` (token copies the dispatch
carries: fewer where a token goes once per rank) and ``moe.dropped``
(assignments lost to capacity), and per lowered exchange program
(:func:`repro.exec.executor_program`) its round tables by lowering:
``exec.block_tables`` (window copies of runs of consecutive units),
``exec.gather_tables`` (per-word gather or scatter-add) and
``exec.dropped_tables`` (no unit on any rank).

Off (the default), :func:`span` returns one shared null context and
:func:`count` returns at once: one global check per site, and no import
of jax.  No span sits inside a per-phase or per-message loop.  Counters
are plain integers under a lock, exact across threads.

Layering: stdlib-only until :func:`enable`, which imports
``jax.profiler``; importable from everywhere, like
:mod:`repro.comm.health`.
"""
from __future__ import annotations

import contextlib
import threading

__all__ = ["enable", "disable", "enabled", "span", "count", "counters",
           "reset"]

_NULL = contextlib.nullcontext()
_annotation = None          # jax.profiler.TraceAnnotation while enabled
_counts: dict[str, int] = {}
_lock = threading.Lock()


def enable() -> None:
    """Switch spans and counters on for the whole process.  Spans reach a
    trace only while a profiler session runs (``jax.profiler.trace``)."""
    global _annotation
    from jax.profiler import TraceAnnotation
    _annotation = TraceAnnotation


def disable() -> None:
    """Switch spans and counters off; the counts so far are kept."""
    global _annotation
    _annotation = None


def enabled() -> bool:
    """Whether spans and counters are on."""
    return _annotation is not None


def span(name: str, **stats):
    """A context manager timing the block as the span ``name``, with
    ``stats`` (ints or strings) attached to the trace event; the shared
    null context when tracing is off."""
    if _annotation is None:
        return _NULL
    return _annotation(name, **stats)


def count(name: str, n: int = 1) -> None:
    """Add ``n`` to the counter ``name`` (nothing when tracing is off)."""
    if _annotation is None:
        return
    with _lock:
        _counts[name] = _counts.get(name, 0) + int(n)


def counters() -> dict[str, int]:
    """A snapshot of every counter."""
    with _lock:
        return dict(_counts)


def reset() -> None:
    """Clear every counter."""
    with _lock:
        _counts.clear()
