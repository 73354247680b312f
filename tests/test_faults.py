"""Fault injection, graceful degradation, and the chaos acceptance sweep.

Covers the DESIGN.md §12 contract end to end: the deterministic
fault-injection framework (:mod:`repro.comm.faults`), the per-site
degradation wrappers in :mod:`repro.kernels.comm_stack` and
:mod:`repro.comm.stack`, the :class:`repro.comm.health.BackendHealth`
quarantine ledger, and — the acceptance
criterion — the PR-7 scenario-registry sweep under every fault mode,
bit-identical to a clean numpy run with ``degraded=True`` on every row.
"""
import numpy as np
import pytest

from repro.comm import faults
from repro.comm.faults import (FaultSpec, InjectedFault, InjectedTimeout,
                               inject)
from repro.comm.health import get_health, reset_health
from repro.kernels import comm_stack as cs

requires_jax = pytest.mark.skipif(not cs.have_jax(), reason="needs jax")


# -- the framework itself -----------------------------------------------------

def test_site_and_mode_registries():
    assert set(faults.SITES) == {
        "kernel.segment_reduce", "kernel.queue_walk", "stack.device_store",
        "serve.cache_read", "serve.cache_write", "serve.deadline"}
    assert set(faults.MODES) == {"raise", "timeout", "nan", "corrupt"}


def test_spec_rejects_bad_mode_and_times():
    with pytest.raises(ValueError, match="unknown fault mode"):
        FaultSpec(site="kernel.queue_walk", mode="explode")
    with pytest.raises(ValueError, match="times must be >= 1"):
        FaultSpec(site="kernel.queue_walk", mode="raise", times=0)


def test_spec_glob_matching():
    spec = FaultSpec(site="kernel.*", mode="raise")
    assert spec.matches("kernel.segment_reduce")
    assert spec.matches("kernel.queue_walk")
    assert not spec.matches("stack.device_store")
    exact = FaultSpec(site="serve.deadline", mode="timeout")
    assert exact.matches("serve.deadline")
    assert not exact.matches("serve.cache_read")


def test_fail_point_fires_and_counts():
    with inject("kernel.segment_reduce", "raise") as spec:
        with pytest.raises(InjectedFault):
            faults.fail_point("kernel.segment_reduce")
        faults.fail_point("kernel.queue_walk")      # non-matching: no-op
    assert spec.fired == 1
    faults.fail_point("kernel.segment_reduce")      # disarmed outside block


def test_times_caps_firing():
    with inject("stack.device_store", "raise", times=2) as spec:
        for _ in range(2):
            with pytest.raises(InjectedFault):
                faults.fail_point("stack.device_store")
        faults.fail_point("stack.device_store")     # exhausted: no-op
    assert spec.fired == 2
    assert not spec.armed


def test_timeout_mode_is_a_timeout_error():
    with inject("serve.cache_read", "timeout"):
        with pytest.raises(TimeoutError):
            faults.fail_point("serve.cache_read")
        with inject("serve.cache_read", "timeout"):
            pass
    # InjectedTimeout is also an OSError, so disk-cache handlers catch it
    assert issubclass(InjectedTimeout, OSError)
    assert issubclass(InjectedTimeout, InjectedFault)


def test_poison_nan_and_corrupt_shapes():
    f = np.array([1.0, 2.0])
    i = np.array([1, 2])
    with inject("kernel.segment_reduce", "nan"):
        out = faults.poison("kernel.segment_reduce", f)
        assert np.isnan(out).all()
        # integer outputs cannot hold NaN and finite-verify cannot see a
        # shift: nan-mode leaves them intact (corrupt is the integer mode)
        assert (faults.poison("kernel.segment_reduce", i) == i).all()
    with inject("kernel.segment_reduce", "corrupt"):
        a, b = faults.poison("kernel.segment_reduce", (f, i))
        # floats shift relatively (allclose-proof at any magnitude),
        # integers by +1 (parity compares them exactly)
        assert (a == f * 1.01 + 1.0).all() and (b == i + 1).all()
        assert faults.poison("kernel.segment_reduce",
                             '{"x": 1}').startswith("\x00corrupt\x00")
    assert faults.poison("kernel.segment_reduce", f) is f  # disarmed


def test_innermost_context_fires_first():
    with inject("kernel.*", "raise") as outer:
        with inject("kernel.queue_walk", "timeout") as inner:
            with pytest.raises(InjectedTimeout):
                faults.fail_point("kernel.queue_walk")
        assert inner.fired == 1 and outer.fired == 0


def test_env_plan_parses_globs_and_times(monkeypatch):
    monkeypatch.setenv(faults.ENV_VAR,
                       "kernel.*:raise, serve.deadline:timeout:1")
    with pytest.raises(InjectedFault):
        faults.fail_point("kernel.segment_reduce")
    with pytest.raises(InjectedTimeout):
        faults.fail_point("serve.deadline")
    faults.fail_point("serve.deadline")             # times=1 exhausted
    with pytest.raises(InjectedFault):
        faults.fail_point("kernel.queue_walk")      # unbounded glob spec


def test_env_plan_rejects_bad_entries(monkeypatch):
    monkeypatch.setenv(faults.ENV_VAR, "kernel.segment_reduce")
    with pytest.raises(ValueError, match="expected site:mode"):
        faults.any_armed()


# -- device_guard degradation -------------------------------------------------

@requires_jax
def test_segment_reduce_degrades_bit_identically():
    rng = np.random.default_rng(0)
    ids = rng.integers(0, 16, size=2048)
    vals = rng.random(2048)
    want = np.bincount(ids, weights=vals, minlength=16)
    with inject("kernel.segment_reduce", "raise") as spec:
        got = cs.segment_sum(vals, ids, 16, backend="jax")
    assert spec.fired == 1
    np.testing.assert_array_equal(got, want)
    events = get_health().events_for("jax", "kernel.segment_reduce")
    assert len(events) == 1 and "InjectedFault" in events[0].error
    assert get_health().failure_streak("jax") == 1


@requires_jax
def test_queue_walk_degrades_bit_identically():
    from repro.comm.primitives import batched_queue_traversal_steps
    rng = np.random.default_rng(1)
    bounds = np.array([0, 5, 12, 12, 20])
    posted = np.concatenate([rng.permutation(n)
                             for n in np.diff(bounds)]).astype(np.int64)
    arrival = np.concatenate([rng.permutation(n)
                              for n in np.diff(bounds)]).astype(np.int64)
    want = batched_queue_traversal_steps(posted, arrival, bounds)
    with inject("kernel.queue_walk", "timeout"):
        got = cs.queue_walk(posted, arrival, bounds, backend="jax")
    np.testing.assert_array_equal(got, want)
    assert get_health().events_for("jax", "kernel.queue_walk")


@requires_jax
def test_success_clears_failure_streak():
    rng = np.random.default_rng(2)
    ids = rng.integers(0, 8, size=512)
    vals = rng.random(512)
    with inject("kernel.segment_reduce", "raise", times=2):
        cs.segment_sum(vals, ids, 8, backend="jax")
        cs.segment_sum(vals, ids, 8, backend="jax")
        assert get_health().failure_streak("jax") == 2
        cs.segment_sum(vals, ids, 8, backend="jax")   # spec exhausted: clean
    assert get_health().failure_streak("jax") == 0
    assert not get_health().is_quarantined("jax")


@requires_jax
def test_quarantine_after_consecutive_failures():
    health = get_health()
    assert health.quarantine_after == 3
    rng = np.random.default_rng(3)
    ids = rng.integers(0, 8, size=256)
    vals = rng.random(256)
    want = np.bincount(ids, weights=vals, minlength=8)
    with inject("kernel.segment_reduce", "raise"):
        for _ in range(3):
            cs.segment_sum(vals, ids, 8, backend="jax")
    assert health.is_quarantined("jax")
    assert health.warned("quarantine:jax")
    # quarantined: resolve_backend reroutes to numpy (with one warning)...
    assert cs.resolve_backend("jax") == "numpy"
    # ...and device_guard short-circuits without recording new events
    n = health.n_events
    out = cs.device_guard("kernel.segment_reduce", "jax",
                          lambda: 1 / 0, lambda: want)
    np.testing.assert_array_equal(out, want)
    assert health.n_events == n
    reset_health()
    assert cs.resolve_backend("jax") == "jax"         # reset lifts quarantine


def test_fallback_warns_once_per_backend_site():
    health = get_health()
    with pytest.warns(RuntimeWarning, match="falling back to"):
        health.record_failure("jax", "kernel.queue_walk", RuntimeError("x"))
    import warnings as w
    with w.catch_warnings():
        w.simplefilter("error")                       # repeat must be silent
        health.record_failure("jax", "kernel.queue_walk", RuntimeError("y"))


# -- REPRO_STACK_VERIFY post-kernel checks ------------------------------------

def test_verify_mode_rejects_unknown_value(monkeypatch):
    monkeypatch.setenv("REPRO_STACK_VERIFY", "bogus")
    with pytest.raises(ValueError, match="REPRO_STACK_VERIFY"):
        cs.verify_mode()


@requires_jax
@pytest.mark.parametrize("mode,verify", [("nan", "finite"),
                                         ("corrupt", "parity")])
def test_verify_catches_poisoned_device_output(mode, verify, monkeypatch):
    monkeypatch.setenv("REPRO_STACK_VERIFY", verify)
    rng = np.random.default_rng(4)
    ids = rng.integers(0, 16, size=1024)
    vals = rng.random(1024)
    want = np.bincount(ids, weights=vals, minlength=16)
    with inject("kernel.segment_reduce", mode) as spec:
        got = cs.segment_sum(vals, ids, 16, backend="jax")
    assert spec.fired == 1
    np.testing.assert_array_equal(got, want)
    events = get_health().events_for("jax", "kernel.segment_reduce")
    assert len(events) == 1 and "BackendVerifyError" in events[0].error


@requires_jax
def test_poison_without_verify_passes_through(monkeypatch):
    monkeypatch.delenv("REPRO_STACK_VERIFY", raising=False)
    rng = np.random.default_rng(5)
    ids = rng.integers(0, 16, size=1024)
    vals = rng.random(1024)
    with inject("kernel.segment_reduce", "nan"):
        got = cs.segment_sum(vals, ids, 16, backend="jax")
    # no verify mode: the poisoned output is NOT caught — this is exactly
    # what REPRO_STACK_VERIFY exists to close
    assert np.isnan(got).all()
    assert get_health().n_events == 0


# -- stack + sweep degradation ------------------------------------------------

def _small_pattern():
    from repro.sparse.partition import CommPattern
    rng = np.random.default_rng(6)
    n = 200
    return CommPattern(src=rng.integers(0, 32, n),
                       dst=rng.integers(0, 32, n),
                       size=rng.integers(1, 1 << 16, n).astype(np.float64),
                       n_procs=32)


@requires_jax
def test_device_store_fault_degrades_sweep_bit_identically():
    from repro.comm.strategies import best_strategy
    from repro.net.machine import lassen_machine
    machine = lassen_machine((2, 2, 2))
    pat = _small_pattern()
    clean = best_strategy(pat, machine, backend="numpy")
    assert not clean.degraded
    with inject("*", "raise"):
        chaos = best_strategy(pat, machine, backend="jax")
    assert chaos.degraded
    assert chaos.model == clean.model and chaos.sim == clean.sim
    assert get_health().events_for(site="stack.device_store")


@requires_jax
def test_sweep_retries_on_numpy_when_pricing_raises(monkeypatch):
    from repro.comm.strategies import best_strategy
    from repro.core import models
    from repro.net.machine import lassen_machine
    machine = lassen_machine((2, 2, 2))
    pat = _small_pattern()
    clean = best_strategy(pat, machine, backend="numpy")
    real = models.phase_cost_many

    def flaky(stack, *a, backend=None, **kw):
        if backend != "numpy":
            raise RuntimeError("pricing pass exploded")
        return real(stack, *a, backend=backend, **kw)

    monkeypatch.setattr(models, "phase_cost_many", flaky)
    verdict = best_strategy(pat, machine, backend="jax")
    assert verdict.degraded
    assert verdict.model == clean.model and verdict.sim == clean.sim
    events = get_health().events_for("jax", "strategies.best_strategy_many")
    assert len(events) == 1


# -- the acceptance criterion: chaos registry sweep ---------------------------

@requires_jax
@pytest.mark.parametrize("mode,verify", [
    ("raise", ""),
    ("timeout", ""),
    ("nan", "finite"),
    ("corrupt", "parity"),
])
def test_chaos_registry_sweep_bit_identical_to_clean_numpy(mode, verify,
                                                           monkeypatch):
    """ISSUE 8 acceptance: every fault mode over the PR-7 scenario registry
    completes on all machine presets, prices bit-identically to a clean
    numpy run, and marks every row degraded with events in the ledger."""
    from repro.workloads.registry import default_machines, sweep

    clean = sweep(machines=default_machines(), backend="numpy")
    assert clean and not any(r.degraded for r in clean)

    reset_health()
    monkeypatch.setenv("REPRO_STACK_VERIFY", verify)
    monkeypatch.setenv(faults.ENV_VAR, f"*:{mode}")
    chaos = sweep(machines=default_machines(), backend="jax")

    assert get_health().n_events > 0
    assert all(r.degraded for r in chaos)
    assert {r.machine for r in chaos} == set(default_machines())
    for a, b in zip(clean, chaos):
        assert (a.machine, a.scenario, a.phase) == (b.machine, b.scenario,
                                                    b.phase)
        assert a.model_winner == b.model_winner
        assert a.sim_winner == b.sim_winner
        assert a.model == b.model                 # bit-identical floats
        assert a.sim == b.sim
