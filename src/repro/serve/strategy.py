"""StrategyService: the never-fail production query path for strategy
selection.

The strategy sweep (:func:`repro.comm.best_strategy_many`) runs here as a
long-lived service: callers hand it traffic shapes (patterns) and expect an
answer for every one of them, whatever the state of the device backends,
the caches, or the input itself.  Contract:
:meth:`StrategyService.query_many` **returns one :class:`ServiceResult`
per pattern and never raises**.  The request path, in order
(DESIGN.md §13):

1. **validation** — an invalid pattern (NaN sizes, out-of-range ranks, …)
   comes back as a result with ``verdict=None`` and the precise typed
   :class:`repro.comm.guard.PatternError` in ``error``; the rest of the
   batch still prices.
2. **admission** — a bounded :class:`repro.serve.admission.AdmissionQueue`
   sheds whole batches under overload (typed
   :class:`~repro.serve.admission.Overloaded` in ``error``) or blocks until
   capacity frees, bounded by the per-request
   :class:`~repro.serve.admission.Deadline` (cooperatively checked at every
   service loop point, never mid-kernel).
3. **cache** — pattern fingerprints
   (:func:`repro.comm.delta.pattern_fingerprint`) key priced verdicts in a
   crash-consistent :class:`repro.serve.cache.ArenaCache`; hits skip the
   sweep entirely (``cached=True``, ``plans`` empty on restored verdicts).
4. **sweep** — cache misses price in one arena sweep on the requested
   backend, wrapped in the service's
   :class:`~repro.serve.admission.RetryPolicy` and a per-backend
   :class:`repro.comm.health.CircuitBreaker`: repeated primary-backend
   failures open the breaker and subsequent batches route straight to the
   numpy reference (full strategy set, ``degraded=True``) until a
   half-open probe heals it.
5. **worst case** — should a sweep still fail, each affected pattern
   retries alone as ``strategies=('standard',)`` on ``backend='numpy'``;
   only if *that* fails does the pattern get ``verdict=None`` with the
   error recorded (never raised).

Traffic drift prices incrementally: :meth:`StrategyService.reprice` diffs
the new shape against a retained :class:`repro.comm.delta.DeltaStack`
arena (:func:`repro.comm.delta.message_delta`), applies the delta at
O(changed) cost, and falls back to a full rebuild when the drift fraction
exceeds the service's threshold or delta verification trips.

numpy-only import: ``from repro.serve import StrategyService`` works
without jax (the batched :class:`repro.serve.ServeEngine` is a separate,
lazily-imported module).
"""
from __future__ import annotations

import collections
import dataclasses
import hashlib
import itertools
import threading
from typing import Any

from .admission import (AdmissionQueue, Deadline, DeadlineExceeded,
                        Overloaded, RetryPolicy)
from .cache import ArenaCache

__all__ = ["ServiceResult", "StrategyService"]

# "use the service's default timeout" marker for per-call overrides, so an
# explicit timeout=None can still mean "no deadline for this call"
_DEFAULT_TIMEOUT = object()


@dataclasses.dataclass(frozen=True)
class ServiceResult:
    """One pattern's answer from :class:`StrategyService`.

    ``verdict`` is the :class:`repro.comm.StrategyVerdict` (None when even
    the worst-case retry could not price the pattern — then ``error`` holds
    the reason).  ``degraded`` marks any answer that did not come from the
    requested configuration: a backend fallback inside the stack, a
    breaker-open reroute to numpy, or the service's standard-on-numpy
    retry.  ``error`` is the triggering exception for rejected/failed
    patterns (a typed :class:`repro.comm.guard.PatternError` for invalid
    input, :class:`~repro.serve.admission.Overloaded` for shed batches,
    :class:`~repro.serve.admission.DeadlineExceeded` for expired ones),
    None for clean answers.  ``cached`` marks verdicts served from the
    arena cache (exact same numbers as a fresh sweep; ``plans`` is empty
    on verdicts restored from disk or a snapshot).
    """

    verdict: Any | None
    degraded: bool = False
    error: Exception | None = None
    cached: bool = False

    @property
    def ok(self) -> bool:
        """Whether a verdict was produced (possibly degraded)."""
        return self.verdict is not None

    @property
    def overloaded(self) -> bool:
        """Whether the admission queue shed this request."""
        return isinstance(self.error, Overloaded)


def _verdict_body(v) -> dict:
    """A verdict's cacheable numbers as a JSON-safe dict (plans excluded)."""
    return {"model": {k: float(x) for k, x in v.model.items()},
            "sim": {k: float(x) for k, x in v.sim.items()},
            "model_winner": v.model_winner, "sim_winner": v.sim_winner}


def _verdict_from_body(body):
    from repro.comm.strategies import StrategyVerdict
    return StrategyVerdict(plans={}, model=dict(body["model"]),
                           sim=dict(body["sim"]),
                           model_winner=body["model_winner"],
                           sim_winner=body["sim_winner"], degraded=False)


class StrategyService:
    """A hardened, stateful wrapper around :func:`repro.comm.best_strategy_many`.

    Parameters
    ----------
    machine : the machine preset queries bind to (any
        :class:`repro.net.MachineSpec`).
    level : model-ladder level queries price at (default ``'contention'``).
    arrival : simulator arrival regime (``'random'`` / ``'posted'``).
    seed : per-candidate arrival seed (default 0).
    backend : stacked-pass backend request (None = the session default).
    strategies : strategy names to sweep (default: every strategy the
        machine supports, via :func:`repro.comm.strategies_for`).
    validate : run the typed validation layer over every query pattern
        (default True — the service's whole point is rejecting garbage
        precisely instead of pricing it).
    cache : an :class:`repro.serve.cache.ArenaCache` for priced verdicts
        (share one across services for a shared cache), or None for a
        fresh memory-only cache.  Keys mix the pattern fingerprint with
        the full pricing configuration, so services with different
        levels/seeds/machines never cross-serve.
    admission : an :class:`repro.serve.admission.AdmissionQueue` (share
        one across services for a global load bound), or None for a fresh
        default queue (capacity 64, policy ``'reject'``).
    retry : a :class:`repro.serve.admission.RetryPolicy` for the primary
        sweep, or None for a single attempt (no retry) — note the pinned
        fallback ladder runs either way.
    timeout : default per-request deadline in seconds (None = none);
        ``query_many(timeout=...)`` overrides per call.
    breaker_threshold / breaker_reset : the per-backend circuit breaker's
        consecutive-failure trip count and open-state hold in seconds
        (see :class:`repro.comm.health.CircuitBreaker`); the breaker lives
        in the process-wide health ledger, shared by every service
        pricing the same backend.
    drift_threshold : :meth:`reprice` falls back to a full rebuild when
        ``(removed + added) / new_messages`` exceeds this fraction
        (default 0.25).
    verify_reprice : re-check the delta bit-identity contract on every
        reprice (slow; a trip degrades to a rebuild, never an error).
    arena_capacity : how many repricing arenas (:class:`DeltaStack`)
        the service retains in memory, LRU (default 16).

    :meth:`query` / :meth:`query_many` / :meth:`reprice` never raise; see
    the module docstring for the degradation ladder.  Thread-safe: any
    number of callers may query concurrently.
    """

    def __init__(self, machine, *, level: str = "contention",
                 arrival: str = "random", seed: int = 0,
                 backend: str | None = None,
                 strategies: tuple[str, ...] | None = None,
                 validate: bool = True,
                 cache: ArenaCache | None = None,
                 admission: AdmissionQueue | None = None,
                 retry: RetryPolicy | None = None,
                 timeout: float | None = None,
                 breaker_threshold: int = 3,
                 breaker_reset: float = 30.0,
                 drift_threshold: float = 0.25,
                 verify_reprice: bool = False,
                 arena_capacity: int = 16):
        self.machine = machine
        self.level = level
        self.arrival = arrival
        self.seed = seed
        self.backend = backend
        self.strategies = strategies
        self.validate = validate
        self.cache = cache if cache is not None else ArenaCache()
        self.admission = admission if admission is not None else AdmissionQueue()
        self.retry = retry
        self.timeout = timeout
        self.breaker_threshold = int(breaker_threshold)
        self.breaker_reset = float(breaker_reset)
        self.drift_threshold = float(drift_threshold)
        self.verify_reprice = bool(verify_reprice)
        if arena_capacity < 1:
            raise ValueError(
                f"arena_capacity must be >= 1, got {arena_capacity}")
        self.arena_capacity = int(arena_capacity)
        self._arenas: collections.OrderedDict[str, Any] = \
            collections.OrderedDict()
        self._lock = threading.Lock()
        self._requests = itertools.count()
        mname = getattr(machine, "name", type(machine).__name__)
        strat = ",".join(strategies) if strategies else "auto"
        self._config_token = (f"{mname}|{getattr(machine, 'n_procs', '?')}|"
                              f"{level}|{arrival}|{seed}|{strat}|"
                              f"{backend or 'numpy'}")

    # -- introspection --------------------------------------------------------
    def health(self):
        """The process-wide :class:`repro.comm.health.BackendHealth` ledger
        (degradation events, quarantines, circuit breakers) this service's
        queries report to."""
        from repro.comm.health import get_health
        return get_health()

    def snapshot(self) -> dict:
        """The verdict cache as a versioned, checksummed, JSON-safe dict
        (:meth:`repro.serve.cache.ArenaCache.snapshot`) — feed it to a
        fresh service's :meth:`restore` for a warm restart."""
        return self.cache.snapshot()

    def restore(self, snapshot: dict) -> int:
        """Warm-start the verdict cache from a :meth:`snapshot`; returns
        how many entries landed (0, with a health event, when ``snapshot``
        is damaged or version-skewed — never an error)."""
        return self.cache.restore(snapshot)

    def _key(self, pattern) -> str:
        from repro.comm.delta import pattern_fingerprint
        raw = pattern_fingerprint(pattern) + "|" + self._config_token
        return hashlib.sha256(raw.encode()).hexdigest()

    # -- the query path -------------------------------------------------------
    def query(self, pattern, *,
              timeout: float | None = _DEFAULT_TIMEOUT) -> ServiceResult:
        """Price one pattern (the one-pattern :meth:`query_many`, same
        ``pattern`` / ``timeout`` contract); never raises."""
        return self.query_many([pattern], timeout=timeout)[0]

    def query_many(self, patterns, *,
                   timeout: float | None = _DEFAULT_TIMEOUT
                   ) -> list[ServiceResult]:
        """Price a batch of patterns: one :class:`ServiceResult` each.

        ``timeout`` (seconds; omitted = the service's ``timeout``, an
        explicit None = no deadline for this call) arms a
        cooperative per-request deadline checked at every service loop
        point — admission wait, before the sweep, between retry attempts,
        and before each worst-case fallback pattern — turning expiry into
        per-pattern :class:`~repro.serve.admission.DeadlineExceeded` error
        results.  Invalid patterns are rejected individually (typed error
        in ``error``) without failing the batch; cache hits return
        immediately (``cached=True``); the remainder prices in one arena
        sweep behind admission control, the retry policy, and the
        per-backend circuit breaker.  Any fallback anywhere marks the
        affected results ``degraded=True``.  Never raises.
        """
        from repro.comm import obs

        with obs.span("repro.service.query", request=next(self._requests)):
            return self._query_many(list(patterns), timeout)

    def _query_many(self, patterns, timeout) -> list[ServiceResult]:
        """The body of :meth:`query_many`, one span per station."""
        from repro.comm import obs
        from repro.comm.guard import PatternError, validate_phase

        results: list[ServiceResult | None] = [None] * len(patterns)
        deadline = Deadline(self.timeout if timeout is _DEFAULT_TIMEOUT
                            else timeout)
        live: list[int] = []
        with obs.span("repro.service.validate"):
            for i, pat in enumerate(patterns):
                if self.validate:
                    try:
                        validate_phase(pat, where=f"query[{i}]")
                    except PatternError as e:
                        results[i] = ServiceResult(verdict=None, error=e)
                        continue
                live.append(i)
        if not live:
            return results

        try:
            with obs.span("repro.service.admit"):
                self.admission.acquire(len(live), deadline)
        except (Overloaded, DeadlineExceeded) as e:
            for i in live:
                results[i] = ServiceResult(verdict=None, error=e)
            return results
        try:
            with obs.span("repro.service.key"):
                keys = {i: self._key(patterns[i]) for i in live}
            misses: list[int] = []
            with obs.span("repro.service.cache"):
                for i in live:
                    body = self.cache.get(keys[i])
                    if body is not None:
                        results[i] = ServiceResult(
                            verdict=_verdict_from_body(body), cached=True)
                    else:
                        misses.append(i)
            if misses:
                with obs.span("repro.service.sweep"):
                    self._price(patterns, misses, keys, results, deadline)
        finally:
            self.admission.release(len(live))
        return results

    def _price(self, patterns, misses, keys, results, deadline) -> None:
        """Sweep the cache-miss patterns through the hardened ladder,
        filling ``results`` in place (one result per index in ``misses``,
        whatever happens)."""
        from repro.comm import strategies as _strategies
        from repro.comm.health import get_health

        health = get_health()
        backend_label = str(self.backend or "numpy")

        def sweep(idx, strats, backend):
            return _strategies.best_strategy_many(
                [patterns[i] for i in idx], self.machine,
                strategies=strats, level=self.level, arrival=self.arrival,
                seed=self.seed, backend=backend,
                validate=False)          # already validated above

        def fill(idx, verdicts, *, degraded=None, cacheable=True):
            for i, v in zip(idx, verdicts):
                deg = v.degraded if degraded is None else degraded
                results[i] = ServiceResult(verdict=v, degraded=deg)
                if cacheable:
                    self.cache.put(keys[i], _verdict_body(v))

        def expire(idx, e):
            for i in idx:
                if results[i] is None:
                    results[i] = ServiceResult(verdict=None, error=e)

        try:
            deadline.check(where="sweep")
        except DeadlineExceeded as e:
            expire(misses, e)
            return

        rerouted = False
        if backend_label != "numpy":
            breaker = health.breaker_for(
                backend_label, fail_threshold=self.breaker_threshold,
                reset_after=self.breaker_reset)
            if breaker.allow():
                retry = self.retry if self.retry is not None \
                    else RetryPolicy(attempts=1)

                def on_failure(e, attempt):
                    breaker.record_failure()

                try:
                    verdicts = retry.run(
                        lambda: sweep(misses, self.strategies, self.backend),
                        deadline=deadline, on_failure=on_failure)
                    breaker.record_success()
                    fill(misses, verdicts)
                    return
                except DeadlineExceeded as e:
                    expire(misses, e)
                    return
                except Exception as e:  # noqa: BLE001 - the service answers
                    health.record_failure(backend_label, "serve.query_many", e)
            else:
                rerouted = True
        if rerouted or backend_label == "numpy":
            # breaker open: full strategy set on the numpy reference (same
            # numbers — the fallback is the bit-identity reference); or
            # numpy was the requested backend in the first place
            try:
                deadline.check(where="numpy sweep")
                verdicts = sweep(misses, self.strategies, "numpy")
                fill(misses, verdicts, degraded=rerouted or None)
                return
            except DeadlineExceeded as e:
                expire(misses, e)
                return
            except Exception as e:  # noqa: BLE001
                health.record_failure("numpy", "serve.query_many", e)

        # worst case: the standard strategy alone, priced on numpy — one
        # pattern at a time so a single pathological pattern cannot take
        # the rest of the batch down with it.  Not cached: the one-strategy
        # verdict is not the configured sweep's answer.
        for i in misses:
            try:
                deadline.check(where=f"fallback[{i}]")
                v = sweep([i], ("standard",), "numpy")[0]
                results[i] = ServiceResult(verdict=v, degraded=True)
            except DeadlineExceeded as e:
                results[i] = ServiceResult(verdict=None, error=e)
            except Exception as e:  # noqa: BLE001
                health.record_failure("numpy", "serve.query_many", e)
                results[i] = ServiceResult(verdict=None, degraded=True,
                                           error=e)

    # -- drift repricing ------------------------------------------------------
    def _remember_arena(self, fp: str, arena) -> None:
        with self._lock:
            self._arenas[fp] = arena
            self._arenas.move_to_end(fp)
            while len(self._arenas) > self.arena_capacity:
                self._arenas.popitem(last=False)

    def reprice(self, old, new, *,
                timeout: float | None = _DEFAULT_TIMEOUT) -> ServiceResult:
        """Price drifted traffic ``new`` incrementally against ``old``.

        ``old`` is a previously-repriced (or any) pattern; ``new`` is the
        drifted shape; ``timeout`` arms the same per-request deadline as
        :meth:`query_many`.  The service diffs the shapes as message
        multisets (:func:`repro.comm.delta.message_delta`), applies the
        delta to a retained :class:`repro.comm.delta.DeltaStack` arena at
        O(changed) cost, and prices the mutated phase through the full
        hardened query path (admission, cache, breaker, fallbacks) — so
        repeated drift against a warm cache is nearly free.  Falls back to
        a plain :meth:`query` of ``new`` when the drift fraction exceeds
        ``drift_threshold``, no arena for ``old`` can be built, or delta
        verification trips (``verify_reprice=True``) — with the trip
        recorded in the health ledger.  Never raises.

        The repriced verdict is for the *canonical mutated order*
        (survivors of ``old`` in place, additions appended): bit-identical
        to rebuilding that order from scratch, and the same message
        multiset as ``new``.
        """
        from repro.comm.delta import (DeltaStack, message_delta,
                                      pattern_fingerprint)
        from repro.comm.guard import PatternError, validate_phase
        from repro.comm.health import get_health

        if self.validate:
            try:
                validate_phase(new, where="reprice(new)")
            except PatternError as e:
                return ServiceResult(verdict=None, error=e)

        old_fp = pattern_fingerprint(old)
        with self._lock:
            arena = self._arenas.get(old_fp)
        if arena is None:
            try:
                arena = DeltaStack.from_phases([old.bind(self.machine)]
                                               if hasattr(old, "bind")
                                               else [old])
                self._remember_arena(old_fp, arena)
            except Exception as e:  # noqa: BLE001 - degrade to full rebuild
                get_health().record_failure("numpy", "serve.reprice", e)
                return self.query(new, timeout=timeout)

        removed, added = message_delta(arena.phases[0], new)
        n_new = int(getattr(new, "n_msgs", len(new.src)))
        frac = (removed.size + added[0].size) / max(1, n_new)
        if frac > self.drift_threshold:
            result = self.query(new, timeout=timeout)
            if result.ok:
                try:
                    fresh = DeltaStack.from_phases(
                        [new.bind(self.machine)] if hasattr(new, "bind")
                        else [new])
                    self._remember_arena(pattern_fingerprint(new), fresh)
                except Exception:  # noqa: BLE001 - arena retention is best-effort
                    pass
            return result

        try:
            mutated = arena.apply(removed, {0: added},
                                  verify=self.verify_reprice)
        except Exception as e:  # noqa: BLE001 - verify trip or bad delta
            get_health().record_failure("numpy", "serve.reprice", e)
            return self.query(new, timeout=timeout)

        phase = mutated.phases[0]
        result = self.query_many([phase], timeout=timeout)[0]
        if result.ok:
            self._remember_arena(
                pattern_fingerprint(phase), mutated)
        return result
