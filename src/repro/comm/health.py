"""Backend health accounting: degradation events, quarantine, warn-once.

The comm stack degrades gracefully: when a device backend (jax / Pallas)
fails — an injected fault, a compile error, an int32-overflow arena, a
verify-mode mismatch — the failing call falls back to the numpy bit-identity
reference and *records the event here* instead of crashing the sweep.  This
module is the per-process ledger of those events:

* :class:`BackendHealth` keeps a **bounded** event ring (a week-long soak
  cannot grow memory without bound: the newest ``max_events`` events are
  retained, older ones are dropped with :attr:`BackendHealth.dropped_events`
  counting the loss; :attr:`BackendHealth.n_events` stays the monotone
  total, so snapshot-and-compare degradation probes keep working across a
  wrap), per-backend consecutive-failure streaks, and a quarantine set: a
  backend that fails ``quarantine_after`` times in a row is quarantined —
  subsequent requests for it resolve straight to numpy without
  re-attempting the device path — until :meth:`BackendHealth.reset` (or a
  recorded success, which clears the streak but not an existing
  quarantine).
* The same object owns the process's **resettable warn-once registry**
  (:meth:`BackendHealth.warn_once`): every "warn once per process" message
  in the stack (backend fallbacks, first degradations, breaker openings)
  goes through it, so tests can reset warning state instead of poking
  module globals.
* :class:`CircuitBreaker` is the *service-path* failure policy
  (:class:`repro.serve.StrategyService`), replacing the stack's one-shot
  quarantine counter one level up: repeated failures **open** the breaker
  (requests route straight to numpy), an open breaker **half-opens** after
  ``reset_after`` seconds letting exactly one probe through, and the
  probe's outcome closes or re-opens it.  Per-backend breakers live on the
  ledger (:meth:`BackendHealth.breaker_for`) so :func:`reset_health`
  clears them with everything else.

One process-wide instance is served by :func:`get_health`;
:func:`reset_health` restores it to a clean slate (the autouse pytest
fixture in ``tests/conftest.py`` does this around every test).

Layering: stdlib-only (no numpy, no jax), importable from everywhere —
:mod:`repro.kernels.comm_stack` and :mod:`repro.comm.stack` both report
here.  See DESIGN.md §12 for the failure-handling contract.
"""
from __future__ import annotations

import collections
import dataclasses
import itertools
import os
import threading
import time
import warnings

__all__ = ["HealthEvent", "BackendHealth", "CircuitBreaker", "get_health",
           "reset_health", "DEFAULT_QUARANTINE_AFTER", "DEFAULT_MAX_EVENTS",
           "BREAKER_STATES"]

#: Consecutive failures of one backend before it is quarantined (override
#: per process with the ``REPRO_STACK_QUARANTINE`` env var; ``0`` disables
#: quarantine entirely — every call re-attempts the device path).
DEFAULT_QUARANTINE_AFTER = 3

#: Retained-event cap of the ledger ring (override per process with the
#: ``REPRO_HEALTH_MAX_EVENTS`` env var).  Older events beyond the cap are
#: dropped and counted, never silently lost.
DEFAULT_MAX_EVENTS = 4096

#: The circuit-breaker state machine: ``closed`` (requests flow),
#: ``open`` (requests shed to numpy), ``half_open`` (one probe in flight).
BREAKER_STATES = ("closed", "open", "half_open")


class CircuitBreaker:
    """Per-backend circuit breaker for the service request path.

    The stack's quarantine counter is one-shot: once a backend trips it,
    only :func:`reset_health` re-arms the device path.  A long-lived
    service needs the full state machine instead — transient failures must
    not permanently degrade throughput:

    * ``closed`` — requests flow to the backend; ``fail_threshold``
      *consecutive* failures (any success resets the count) **open** it;
    * ``open`` — :meth:`allow` answers False (route the query straight to
      numpy) until ``reset_after`` seconds have passed, then the breaker
      **half-opens**;
    * ``half_open`` — exactly one caller gets True (the probe); its
      :meth:`record_success` closes the breaker, its :meth:`record_failure`
      re-opens it for another ``reset_after`` window.

    ``backend`` names the guarded backend (labels and warn-once keys);
    ``clock`` is injectable (monotonic seconds) so tests drive transitions
    without sleeping.  Thread-safe; state transitions to ``open`` are
    surfaced once per breaker through the owning ledger's warn-once
    registry when the breaker was created by
    :meth:`BackendHealth.breaker_for`.
    """

    def __init__(self, backend: str, *, fail_threshold: int = 3,
                 reset_after: float = 30.0, clock=time.monotonic,
                 _health: "BackendHealth | None" = None):
        if fail_threshold < 1:
            raise ValueError(
                f"fail_threshold must be >= 1, got {fail_threshold}")
        if reset_after < 0:
            raise ValueError(f"reset_after must be >= 0, got {reset_after}")
        self.backend = backend
        self.fail_threshold = int(fail_threshold)
        self.reset_after = float(reset_after)
        self._clock = clock
        self._health = _health
        self._lock = threading.Lock()
        self._state = "closed"
        self._failures = 0
        self._opened_at = 0.0
        self._n_opens = 0
        self._n_shed = 0

    @property
    def state(self) -> str:
        """Current state (one of :data:`BREAKER_STATES`); an expired
        ``open`` window reads as ``open`` until the next :meth:`allow`
        half-opens it."""
        with self._lock:
            return self._state

    @property
    def n_opens(self) -> int:
        """How many times the breaker has opened since construction."""
        with self._lock:
            return self._n_opens

    @property
    def n_shed(self) -> int:
        """How many :meth:`allow` calls answered False (requests routed
        around the backend) since construction."""
        with self._lock:
            return self._n_shed

    def allow(self) -> bool:
        """Whether the next request may try the guarded backend.

        ``closed`` → True.  ``open`` → False until ``reset_after`` seconds
        since opening, then the breaker half-opens and this call (only)
        gets True as the probe.  ``half_open`` → False: one probe is
        already in flight.
        """
        with self._lock:
            if self._state == "closed":
                return True
            if (self._state == "open"
                    and self._clock() - self._opened_at >= self.reset_after):
                self._state = "half_open"
                return True
            self._n_shed += 1
            return False

    def record_success(self) -> None:
        """A guarded call succeeded: close the breaker, clear the streak."""
        with self._lock:
            self._state = "closed"
            self._failures = 0

    def record_failure(self) -> None:
        """A guarded call failed: bump the streak; at ``fail_threshold``
        consecutive failures (or any half-open probe failure) the breaker
        opens for ``reset_after`` seconds."""
        with self._lock:
            self._failures += 1
            opening = (self._state == "half_open"
                       or (self._state == "closed"
                           and self._failures >= self.fail_threshold))
            if opening:
                self._state = "open"
                self._opened_at = self._clock()
                self._n_opens += 1
        if opening and self._health is not None:
            self._health.warn_once(
                f"breaker:{self.backend}",
                f"circuit breaker for backend {self.backend!r} opened after "
                f"repeated failures; service queries route to numpy and a "
                f"half-open probe re-tries the backend after "
                f"{self.reset_after:g}s")

    def reset(self) -> None:
        """Force the breaker back to ``closed`` with a clear streak."""
        with self._lock:
            self._state = "closed"
            self._failures = 0


@dataclasses.dataclass(frozen=True)
class HealthEvent:
    """One recorded degradation: ``backend`` failed at injection ``site``.

    ``error`` is the triggering exception's ``repr`` (the exception object
    itself is not retained — events outlive their tracebacks); ``seq`` is a
    process-wide monotone sequence number, so event ordering is total even
    across interleaved arenas.
    """

    seq: int
    backend: str
    site: str
    error: str

    def __str__(self) -> str:
        return f"[{self.seq}] {self.backend} failed at {self.site}: {self.error}"


class BackendHealth:
    """Per-process backend failure ledger + quarantine + warn-once registry.

    Thread-safe (one lock around all mutation).  ``quarantine_after=None``
    reads the ``REPRO_STACK_QUARANTINE`` env var (default
    :data:`DEFAULT_QUARANTINE_AFTER`); ``0`` disables quarantine.
    ``max_events=None`` reads ``REPRO_HEALTH_MAX_EVENTS`` (default
    :data:`DEFAULT_MAX_EVENTS`); the ledger retains at most that many
    events (newest win), counting what it drops in
    :attr:`dropped_events` — a week-long soak stays bounded while the
    monotone :attr:`n_events` keeps snapshot-compare probes exact.
    """

    def __init__(self, quarantine_after: int | None = None,
                 max_events: int | None = None):
        if quarantine_after is None:
            quarantine_after = int(os.environ.get(
                "REPRO_STACK_QUARANTINE", DEFAULT_QUARANTINE_AFTER))
        if quarantine_after < 0:
            raise ValueError(
                f"quarantine_after must be >= 0, got {quarantine_after}")
        if max_events is None:
            max_events = int(os.environ.get(
                "REPRO_HEALTH_MAX_EVENTS", DEFAULT_MAX_EVENTS))
        if max_events < 1:
            raise ValueError(f"max_events must be >= 1, got {max_events}")
        self.quarantine_after = quarantine_after
        self.max_events = max_events
        self._lock = threading.Lock()
        self._seq = itertools.count()
        self._events: collections.deque[HealthEvent] = collections.deque(
            maxlen=max_events)
        self._total = 0
        self._dropped = 0
        self._streak: dict[str, int] = {}
        self._quarantined: set[str] = set()
        self._warned: set[str] = set()
        self._breakers: dict[str, CircuitBreaker] = {}

    # -- event accounting ----------------------------------------------------
    def record_failure(self, backend: str, site: str,
                       error: BaseException | str) -> HealthEvent:
        """Record one backend failure at a named injection ``site``.

        ``error`` is the triggering exception (or a plain string), kept as
        its ``repr`` on the event.  Bumps ``backend``'s
        consecutive-failure streak and quarantines it
        when the streak reaches ``quarantine_after``; warns once per
        (backend, site) pair so a million-message sweep degrades with one
        line of noise, not one per call.  Returns the recorded event.
        """
        err = error if isinstance(error, str) else repr(error)
        with self._lock:
            ev = HealthEvent(seq=next(self._seq), backend=backend, site=site,
                             error=err)
            if len(self._events) == self._events.maxlen:
                self._dropped += 1      # deque drops the oldest on append
            self._events.append(ev)
            self._total += 1
            streak = self._streak.get(backend, 0) + 1
            self._streak[backend] = streak
            newly_quarantined = (self.quarantine_after
                                 and streak >= self.quarantine_after
                                 and backend not in self._quarantined)
            if newly_quarantined:
                self._quarantined.add(backend)
        self.warn_once(
            f"fallback:{backend}:{site}",
            f"backend {backend!r} failed at {site} ({err}); falling back to "
            "the numpy reference for this and further failures at this site")
        if newly_quarantined:
            self.warn_once(
                f"quarantine:{backend}",
                f"backend {backend!r} quarantined after {streak} consecutive "
                "failures; requests resolve to numpy until "
                "BackendHealth.reset()")
        return ev

    def record_success(self, backend: str) -> None:
        """Record a successful device call: clears ``backend``'s
        consecutive-failure streak (an existing quarantine stays until
        :meth:`reset` — a quarantined backend is not re-attempted, so a
        success can only come from an explicit direct call)."""
        with self._lock:
            self._streak[backend] = 0

    def is_quarantined(self, backend: str) -> bool:
        """Whether ``backend`` is quarantined (resolve it to numpy)."""
        with self._lock:
            return backend in self._quarantined

    def breaker_for(self, backend: str, *, fail_threshold: int = 3,
                    reset_after: float = 30.0,
                    clock=time.monotonic) -> CircuitBreaker:
        """The per-``backend`` :class:`CircuitBreaker`, created on first use.

        ``fail_threshold`` / ``reset_after`` / ``clock`` configure a breaker
        being created and are ignored for an existing one (first caller
        wins — one policy per backend per process).  Breakers created here
        report open transitions through :meth:`warn_once` and are cleared
        by :meth:`reset`.
        """
        with self._lock:
            br = self._breakers.get(backend)
            if br is None:
                br = CircuitBreaker(backend, fail_threshold=fail_threshold,
                                    reset_after=reset_after, clock=clock,
                                    _health=self)
                self._breakers[backend] = br
            return br

    # -- inspection ----------------------------------------------------------
    @property
    def events(self) -> tuple[HealthEvent, ...]:
        """The retained degradation events, in sequence order (the newest
        ``max_events``; see :attr:`dropped_events` for what the ring shed)."""
        with self._lock:
            return tuple(self._events)

    @property
    def n_events(self) -> int:
        """Monotone count of every event ever recorded since the last
        :meth:`reset` — including events the bounded ring has since dropped
        (cheap degradation probe: snapshot it before a call, compare
        after; a ring wrap can never mask a new failure)."""
        with self._lock:
            return self._total

    @property
    def dropped_events(self) -> int:
        """How many events the bounded ring has dropped since the last
        :meth:`reset` (``n_events - len(events)``)."""
        with self._lock:
            return self._dropped

    def failure_streak(self, backend: str) -> int:
        """Current consecutive-failure count for ``backend``."""
        with self._lock:
            return self._streak.get(backend, 0)

    def events_for(self, backend: str | None = None,
                   site: str | None = None) -> tuple[HealthEvent, ...]:
        """Events filtered by ``backend`` and/or ``site`` (None = any)."""
        with self._lock:
            return tuple(ev for ev in self._events
                         if (backend is None or ev.backend == backend)
                         and (site is None or ev.site == site))

    # -- warn-once registry --------------------------------------------------
    def warn_once(self, key: str, message: str,
                  category: type[Warning] = RuntimeWarning,
                  stacklevel: int = 3) -> bool:
        """Issue ``message`` as a warning the first time ``key`` is seen.

        The resettable replacement for module-level ``_warned_*`` globals:
        ``category`` and ``stacklevel`` pass through to ``warnings.warn``;
        returns True when the warning was actually issued.  :meth:`reset`
        clears the seen-set (the pytest autouse fixture relies on this to
        stop warn-once state leaking across tests).
        """
        with self._lock:
            if key in self._warned:
                return False
            self._warned.add(key)
        warnings.warn(message, category, stacklevel=stacklevel)
        return True

    def warned(self, key: str) -> bool:
        """Whether warn-once ``key`` has fired since the last reset."""
        with self._lock:
            return key in self._warned

    # -- lifecycle -----------------------------------------------------------
    def reset(self) -> None:
        """Clear events (and the dropped counter), streaks, quarantines,
        circuit breakers and warn-once state."""
        with self._lock:
            self._events.clear()
            self._total = 0
            self._dropped = 0
            self._streak.clear()
            self._quarantined.clear()
            self._warned.clear()
            self._breakers.clear()


_health = BackendHealth()


def get_health() -> BackendHealth:
    """The process-wide :class:`BackendHealth` ledger."""
    return _health


def reset_health() -> None:
    """Reset the process-wide ledger (events, quarantines, warn-once)."""
    _health.reset()
