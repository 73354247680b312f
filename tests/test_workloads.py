"""Certification of the derived LLM workload patterns (the ISSUE-7 harness).

Four layers of trust, weakest to strongest:

* **Flow conservation** (property tests): the MoE combine exchange returns
  exactly the bytes dispatch sent per (src, dst) pair, TP ring volumes
  match the analytic ``2 * (M - 1) / M * bytes`` all-reduce formula, and
  pipeline totals are ``microbatches x boundaries x activation bytes``.
* **RNG contract**: the same seed gives bit-identical histograms and
  patterns across calls (pinned in the module docstrings).
* **Cross-check**: the pattern from the real seeded router forward pass
  (:func:`repro.workloads.router_routing_counts` — the numpy twin of the
  :mod:`repro.nn.moe` router math) equals the histogram lowering of its own
  counts, and obeys the same conservation law as the synthetic generator.
* **jax parity** (skipped where jax is absent): the numpy top-K routing
  reproduces ``jax.lax.top_k`` decisions on identical logits, and the
  numpy-only row-parallel op count matches the count read off the real
  ``param_pspecs`` sharding tree on a fake 8-device mesh.

Property tests ride the optional-hypothesis shim; every deterministic test
is numpy-only and runs without jax.
"""
import os
import subprocess
import sys

import numpy as np
import pytest

from _hypothesis_compat import given, settings, st
from repro.configs import get_config, get_smoke_config
from repro.workloads import (a2a_capacity, moe_a2a_pattern,
                             pattern_from_counts, pipeline_p2p_pattern,
                             router_routing_counts, row_parallel_ops_per_layer,
                             synthetic_routing_counts, tp_collective_patterns)

try:
    import jax  # noqa: F401
    HAVE_JAX = True
except ImportError:
    HAVE_JAX = False

needs_jax = pytest.mark.skipif(not HAVE_JAX, reason="jax not installed")


def _pair_bytes(pattern):
    """(src, dst) -> total bytes, as a dict."""
    out = {}
    for s, d, z in zip(pattern.src, pattern.dst, pattern.size):
        out[(int(s), int(d))] = out.get((int(s), int(d)), 0.0) + float(z)
    return out


# ------------------------------------------------- MoE flow conservation ----
@settings(max_examples=25, deadline=None)
@given(n_ranks=st.sampled_from([2, 4, 8]),
       tokens=st.integers(min_value=1, max_value=64),
       experts_per_rank=st.integers(min_value=1, max_value=4),
       top_k=st.integers(min_value=1, max_value=3),
       seed=st.integers(min_value=0, max_value=2**31 - 1))
def test_moe_flow_conservation(n_ranks, tokens, experts_per_rank, top_k, seed):
    E = n_ranks * experts_per_rank
    top_k = min(top_k, E)
    counts = synthetic_routing_counts(n_ranks, tokens, E, top_k, seed=seed)
    assert counts.shape == (n_ranks, E)
    assert counts.sum() == n_ranks * tokens * top_k
    pat = pattern_from_counts(counts, d_model=32, capacity=tokens)
    # combine returns exactly what dispatch sent, per pair, reversed
    disp, comb = _pair_bytes(pat.dispatch), _pair_bytes(pat.combine)
    assert comb == {(d, s): z for (s, d), z in disp.items()}
    assert pat.dispatch.total_bytes == pat.combine.total_bytes
    # no self-messages; clip bounded by both counts and capacity
    assert np.all(pat.dispatch.src != pat.dispatch.dst)
    assert np.all(pat.sent <= pat.counts)
    assert np.all(pat.sent <= pat.capacity)
    assert pat.dropped_tokens == (pat.counts - pat.sent).sum() >= 0
    # every wire byte is a clipped routed token that left its origin rank
    owner = np.repeat(np.arange(n_ranks), E // n_ranks)
    offrank = sum(int(pat.sent[r, e]) for r in range(n_ranks)
                  for e in range(E) if owner[e] != r)
    assert pat.dispatch.total_bytes == offrank * pat.token_bytes


# ------------------------------------------------------ TP ring volumes ----
@settings(max_examples=25, deadline=None)
@given(tp=st.sampled_from([2, 4, 8, 16]),
       tokens=st.integers(min_value=1, max_value=512),
       n_groups=st.sampled_from([1, 2]))
def test_tp_ring_matches_allreduce_formula(tp, tokens, n_groups):
    cfg = get_smoke_config("llama3.2-3b")     # wo: 64, w2: 128 — both divide
    tc = tp_collective_patterns(cfg, tp, tokens, n_groups=n_groups)
    payload = tokens * cfg.d_model * 2.0
    assert tc.payload_bytes == payload
    assert tc.n_ops == row_parallel_ops_per_layer(cfg, tp) == 2
    for _, phase in tc.phases():
        assert phase.n_procs == n_groups * tp
        sent = np.bincount(phase.src, weights=phase.size,
                           minlength=phase.n_procs)
        # each phase is half the all-reduce: (M-1)/M x payload per rank
        assert np.allclose(sent, tc.n_ops * (tp - 1) / tp * payload)
        # ring: every message goes to the in-group successor
        group = phase.src // tp
        assert np.array_equal(phase.dst,
                              group * tp + (phase.src % tp + 1) % tp)
    assert 2 * sent.sum() == pytest.approx(n_groups * tp * tc.per_rank_bytes)


def test_tp_rejects_degenerate():
    cfg = get_smoke_config("llama3.2-3b")
    with pytest.raises(ValueError):
        tp_collective_patterns(cfg, 1, 16)
    with pytest.raises(ValueError):              # 64 and 128 both indivisible
        tp_collective_patterns(cfg, 7, 16)


# ------------------------------------------------------- pipeline totals ----
@settings(max_examples=25, deadline=None)
@given(n_stages=st.integers(min_value=2, max_value=8),
       n_microbatches=st.integers(min_value=1, max_value=16),
       mb_tokens=st.integers(min_value=1, max_value=256))
def test_pipeline_totals(n_stages, n_microbatches, mb_tokens):
    cfg = get_smoke_config("llama3.2-3b")
    pat = pipeline_p2p_pattern(cfg, n_stages, n_microbatches, mb_tokens)
    mb_bytes = mb_tokens * cfg.d_model * 2
    assert pat.n_msgs == (n_stages - 1) * n_microbatches
    assert pat.total_bytes == (n_stages - 1) * n_microbatches * mb_bytes
    # every message crosses exactly one interior boundary, forward
    assert np.array_equal(np.unique(pat.src), np.arange(n_stages - 1))
    assert np.array_equal(pat.dst, pat.src + 1)


def test_pipeline_rank_blocks():
    cfg = get_smoke_config("llama3.2-3b")
    pat = pipeline_p2p_pattern(cfg, 4, 2, 16, n_procs=64)
    assert pat.n_procs == 64
    assert np.array_equal(np.unique(pat.src), [0, 16, 32])
    assert np.array_equal(np.unique(pat.dst), [16, 32, 48])
    with pytest.raises(ValueError):
        pipeline_p2p_pattern(cfg, 3, 2, 16, n_procs=64)   # 3 !| 64


# ----------------------------------------------------------- RNG contract ----
@settings(max_examples=10, deadline=None)
@given(seed=st.integers(min_value=0, max_value=2**31 - 1))
def test_same_seed_bit_identical(seed):
    cfg = get_smoke_config("qwen3-moe-30b-a3b")
    for source in ("synthetic", "router"):
        a = moe_a2a_pattern(cfg, 4, 16, seed=seed, source=source)
        b = moe_a2a_pattern(cfg, 4, 16, seed=seed, source=source)
        assert np.array_equal(a.counts, b.counts)
        for pa, pb in ((a.dispatch, b.dispatch), (a.combine, b.combine)):
            assert np.array_equal(pa.src, pb.src)
            assert np.array_equal(pa.dst, pb.dst)
            assert np.array_equal(pa.size, pb.size)


def test_seed_actually_matters():
    cfg = get_smoke_config("qwen3-moe-30b-a3b")
    a = moe_a2a_pattern(cfg, 4, 64, seed=0)
    b = moe_a2a_pattern(cfg, 4, 64, seed=1)
    assert not np.array_equal(a.counts, b.counts)


# ------------------------------------------- router / histogram cross-check ----
def test_router_pattern_matches_histogram_lowering():
    """The pattern from the real (numpy) router forward pass is exactly the
    histogram lowering of that forward pass's own routing counts — the
    generator adds nothing the counts don't determine."""
    cfg = get_smoke_config("qwen3-moe-30b-a3b")
    via_router = moe_a2a_pattern(cfg, 4, 32, seed=7, source="router")
    counts = router_routing_counts(cfg, 4, 32, seed=7)
    via_counts = pattern_from_counts(counts, cfg.d_model,
                                     a2a_capacity(32, cfg))
    assert np.array_equal(via_router.counts, via_counts.counts)
    for pa, pb in ((via_router.dispatch, via_counts.dispatch),
                   (via_router.combine, via_counts.combine)):
        assert np.array_equal(pa.src, pb.src)
        assert np.array_equal(pa.dst, pb.dst)
        assert np.array_equal(pa.size, pb.size)
    # and the router-derived pattern obeys the same conservation law
    disp = _pair_bytes(via_router.dispatch)
    assert _pair_bytes(via_router.combine) == \
        {(d, s): z for (s, d), z in disp.items()}
    # a real top-K router routes every token K times (before clipping)
    assert via_router.counts.sum() == 4 * 32 * cfg.n_experts_active


def test_capacity_formula_pinned_to_ep_a2a():
    # the exact inline expression of repro.parallel.ep_a2a.moe_ffn_ep
    cfg = get_smoke_config("qwen3-moe-30b-a3b")
    for T in (1, 16, 256, 4096):
        expected = max(8, int(T * cfg.n_experts_active * cfg.capacity_factor
                              // cfg.n_experts) + 1)
        assert a2a_capacity(T, cfg) == expected


# --------------------------------------------------------------- jax parity ----
@needs_jax
def test_numpy_topk_matches_jax_topk():
    """router_routing_counts' stable argsort reproduces jax.lax.top_k expert
    choices (including lowest-index tie-breaking) on the identical logits."""
    import jax
    import jax.numpy as jnp

    cfg = get_smoke_config("qwen3-moe-30b-a3b")
    n_ranks, T, seed = 4, 32, 3
    counts = router_routing_counts(cfg, n_ranks, T, seed=seed)
    # rebuild the exact same logits the numpy path drew
    rng = np.random.default_rng(seed)
    d, E, K = cfg.d_model, cfg.n_experts, cfg.n_experts_active
    x = rng.standard_normal((n_ranks * T, d)).astype(np.float32)
    router = (rng.standard_normal((d, E)) / np.sqrt(d)).astype(np.float32)
    logits = jnp.asarray(x) @ jnp.asarray(router)
    probs = jax.nn.softmax(logits, axis=-1)       # the moe_ffn routing path
    _, idx = jax.lax.top_k(probs, K)
    rank_of_token = np.repeat(np.arange(n_ranks), T)
    flat = rank_of_token[:, None] * E + np.asarray(idx)
    jax_counts = np.bincount(flat.ravel(),
                             minlength=n_ranks * E).reshape(n_ranks, E)
    assert np.array_equal(counts, jax_counts)


_PSPEC_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
from repro.configs import get_smoke_config
from repro.launch.mesh import make_mesh
from repro.parallel.sharding import make_mesh_plan
from repro.workloads import row_parallel_ops_from_pspecs, \
    row_parallel_ops_per_layer

plan = make_mesh_plan(make_mesh((1, 8), ("data", "model")))
for arch in ("llama3.2-3b", "qwen3-moe-30b-a3b", "deepseek-moe-16b",
             "mamba2-130m", "hymba-1.5b"):
    cfg = get_smoke_config(arch)
    analytic = row_parallel_ops_per_layer(cfg, 8)
    actual = row_parallel_ops_from_pspecs(cfg, plan)
    assert analytic == actual, (arch, analytic, actual)
    print(arch, actual)
"""


@needs_jax
def test_row_parallel_ops_match_real_pspecs():
    """The numpy-only op count equals the count read off the real
    param_pspecs tree, per arch, on a fake 8-device mesh (subprocess, so the
    main process keeps its single-device view)."""
    proc = subprocess.run([sys.executable, "-c", _PSPEC_SCRIPT],
                          env=dict(os.environ, JAX_PLATFORMS="cpu"),
                          capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr
    got = dict(line.split() for line in proc.stdout.strip().splitlines())
    # attention wo everywhere (but mamba), +w2/shared_w2/out_proj per family
    assert got == {"llama3.2-3b": "2", "qwen3-moe-30b-a3b": "1",
                   "deepseek-moe-16b": "2", "mamba2-130m": "1",
                   "hymba-1.5b": "3"}


# ------------------------------------------------- full-size registry shapes ----
def test_registry_scenarios_derive():
    """Every shipped scenario derives: full-size configs, 64 ranks."""
    from repro.workloads import DEFAULT_SCENARIOS, scenario_patterns
    for sc in DEFAULT_SCENARIOS:
        for label, pat in scenario_patterns(sc):
            assert pat.n_procs == sc.n_ranks
            assert pat.n_msgs > 0
            assert np.all(pat.src != pat.dst)
            assert np.all(pat.size > 0)


def test_moe_full_size_conservation():
    cfg = get_config("qwen3-moe-30b-a3b")
    pat = moe_a2a_pattern(cfg, 64, 256, seed=0)
    disp = _pair_bytes(pat.dispatch)
    assert _pair_bytes(pat.combine) == \
        {(d, s): z for (s, d), z in disp.items()}
    assert pat.capacity == a2a_capacity(256, cfg)
