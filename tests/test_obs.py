"""The planner's spans and counters (:mod:`repro.comm.obs`).

Off, they cost one check and import no jax; on, the spans land in a
profiler trace, nest on each thread, and the counters are exact.  The
device call and sync counts of a small AMG sweep on ``pallas`` are pinned:
a change that removes a host sync updates the pin knowingly.
"""
import glob
import os
import subprocess
import sys
import threading

import numpy as np
import pytest

from repro.comm import CommPhase, obs, rewrite
from repro.comm.strategies import best_strategy_many
from repro.kernels import comm_stack as cs
from repro.net import blue_waters_machine
from repro.net.machine import lassen_machine
from repro.serve import ArenaCache, StrategyService
from repro.sparse import (RowPartition, build_hierarchy, elasticity_like_3d,
                          spmv_comm_pattern)
from repro.sparse.partition import CommPattern
from repro.workloads import (node_limited_topk, pattern_from_choices,
                             pattern_from_counts)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

requires_jax = pytest.mark.skipif(not cs.have_jax(), reason="needs jax")


@pytest.fixture(autouse=True)
def _tracing_off():
    obs.disable()
    obs.reset()
    yield
    obs.disable()
    obs.reset()


def _amg_patterns():
    """A 3-level AMG hierarchy (grid 10) on 256 Blue Waters ranks: 10,628
    messages."""
    m = blue_waters_machine((2, 2, 2))
    out = []
    for lvl in build_hierarchy(elasticity_like_3d(10)):
        n = min(m.n_procs, max(lvl.A.n_rows // 2, 2))
        cp = spmv_comm_pattern(lvl.A, RowPartition.balanced(lvl.A.n_rows, n))
        if cp.n_msgs:
            out.append(cp)
    return m, out


def _patterns(P, k, m=2, n=48):
    """``m`` patterns of ``n`` messages: the same ranks for every ``k``, so
    the same arena sizes, and sizes drawn from ``k``, so a new
    fingerprint."""
    ranks, sizes = np.random.default_rng(0), np.random.default_rng(k)
    return [CommPattern(src=ranks.integers(0, P, n),
                        dst=ranks.integers(0, P, n),
                        size=sizes.integers(64, 4096, n).astype(float),
                        n_procs=P)
            for _ in range(m)]


def _key(v):
    return (v.model, v.sim, v.model_winner, v.sim_winner, v.degraded)


def _traced(tmp_path, fn):
    """Run ``fn`` with tracing on under a profiler session; return its
    result and the ``repro.`` spans of the trace, by thread line:
    ``{line: [(name, start_ns, end_ns, stats), ...]}``."""
    import jax
    from jax.profiler import ProfileData

    obs.enable()
    try:
        with jax.profiler.trace(str(tmp_path)):
            out = fn()
    finally:
        obs.disable()
    (path,) = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    lines = {}
    for p, plane in enumerate(ProfileData.from_file(path).planes):
        if not plane.name.startswith("/host:"):
            continue
        for k, line in enumerate(plane.lines):
            evs = [(ev.name, ev.start_ns, ev.start_ns + ev.duration_ns,
                    {key: v for key, v in ev.stats}) for ev in line.events
                   if ev.name.startswith("repro.")]
            if evs:
                lines[(p, k)] = evs
    return out, lines


def _assert_nested(spans):
    """Spans of one thread either nest or follow each other."""
    stack = []
    for name, s, e, _ in sorted(spans, key=lambda sp: (sp[1], -sp[2])):
        while stack and stack[-1][2] <= s:
            stack.pop()
        if stack:
            assert e <= stack[-1][2], (name, stack[-1][0])
        stack.append((name, s, e))


def _inside(spans, outer):
    """Names of the spans that lie inside the spans named ``outer``."""
    outs = [(s, e) for n, s, e, _ in spans if n == outer]
    return {n for n, s, e, _ in spans
            if n != outer and any(a <= s and e <= b for a, b in outs)}


# -- off ---------------------------------------------------------------------

def test_off_span_is_one_shared_null_context_and_count_does_nothing():
    assert not obs.enabled()
    a, b = obs.span("repro.x"), obs.span("repro.y", n=3)
    assert a is b
    with a:
        with b:
            obs.count("device.syncs", 5)
    assert obs.counters() == {}


def test_module_and_serve_path_import_no_jax_when_off():
    code = (
        "import sys\n"
        "from repro.comm import obs\n"
        "with obs.span('repro.plan.sweep', patterns=1):\n"
        "    obs.count('device.syncs')\n"
        "from repro.serve import StrategyService\n"
        "from repro.net.machine import lassen_machine\n"
        "from repro.sparse.partition import CommPattern\n"
        "import numpy as np\n"
        "m = lassen_machine()\n"
        "p = CommPattern(src=np.array([0, 1]), dst=np.array([5, 0]),\n"
        "                size=np.array([64.0, 4096.0]), n_procs=m.n_procs)\n"
        "r = StrategyService(m, backend='numpy').query(p)\n"
        "assert r.ok and obs.counters() == {}, r\n"
        "assert 'jax' not in sys.modules, 'tracing off pulled in jax'\n")
    subprocess.run([sys.executable, "-c", code], check=True, cwd=ROOT,
                   env=dict(os.environ, PYTHONPATH="src"), timeout=120)


def test_to_host_of_a_host_array_is_no_sync():
    obs.enable()
    a = np.arange(4, dtype=np.int32)
    out = cs.to_host(a, np.int64)
    assert out.dtype == np.int64 and (out == a).all()
    assert obs.counters() == {}


@pytest.mark.parametrize("strategy,short,passes", [
    ("three_step", 0, 0), ("three_step", 5, 2 * 5), ("two_step", 5, 0)])
def test_rewrite_counts_its_masked_fan_out_passes(strategy, short, passes):
    """``rewrite.fan_passes``: 0 where every node is full (one shared pass
    serves every injector rank) or one share a message; with the last of
    8 nodes ``short`` ranks short, its ``16 - short`` shares leave
    ``short`` ranks that only some messages reach, one masked pass each
    on the gather and on the scatter side.  Nothing is counted off."""
    m = blue_waters_machine((2, 2, 1))          # 8 nodes of 16 ranks
    P = m.n_procs - short
    rng = np.random.default_rng(4)
    src, dst = rng.integers(0, P, 600), rng.integers(0, P, 600)
    keep = src != dst
    phase = CommPhase.build(m, src[keep], dst[keep],
                            rng.integers(8, 4096, 600)[keep].astype(float),
                            n_procs=P)
    rewrite(phase, strategy)
    assert obs.counters() == {}
    obs.enable()
    rewrite(phase, strategy)
    assert obs.counters() == {"rewrite.fan_passes": passes}


def _v3_choices(seed=0):
    """256 tokens on each of 4 ranks routed node-limited, top-8 of 256
    experts in 4 of 8 groups."""
    scores = np.random.default_rng(seed).random((4 * 256, 256))
    return node_limited_topk(scores, 8, 8, 4)


def test_moe_lowering_counts_its_copies():
    """A copy per chip: ``moe.token_copies`` are the (token, other rank)
    pairs and no more than ``moe.expert_copies``, the off-rank (token,
    expert) assignments; nothing is dropped.  Nothing is counted off."""
    choices = _v3_choices()
    pattern_from_choices(choices, 4, 256, 7392, 14336)
    assert obs.counters() == {}
    obs.enable()
    pat = pattern_from_choices(choices, 4, 256, 7392, 14336)
    c = obs.counters()
    rank = np.repeat(np.arange(4), 256)[:, None]
    owner = choices // 64
    pairs = sum(len(set(row) - {r}) for row, r in
                zip(owner.tolist(), rank[:, 0].tolist()))
    assert c == {"moe.expert_copies": int((owner != rank).sum()),
                 "moe.token_copies": pairs, "moe.dropped": 0}
    assert c["moe.token_copies"] < c["moe.expert_copies"]
    assert pat.dispatch.total_bytes == pairs * 7392


def test_moe_capacity_lowering_counts_its_drops():
    """A copy per expert: every off-rank assignment that survives the
    capacity is a token copy, and the rest are ``moe.dropped``."""
    counts = np.array([[5, 0, 3, 9], [1, 7, 2, 2]])
    obs.enable()
    pat = pattern_from_counts(counts, 8, capacity=4)
    # rank 0 holds experts 0-1 and sends to 2-3, rank 1 the other way;
    # clipped at 4: 5 -> 4, 9 -> 4, 7 -> 4
    assert obs.counters() == {"moe.expert_copies": (3 + 9) + (1 + 7),
                              "moe.token_copies": (3 + 4) + (1 + 4),
                              "moe.dropped": 1 + 5 + 3}
    assert pat.dropped_tokens == 9


# -- on ----------------------------------------------------------------------

def test_counters_are_exact_across_threads():
    obs.enable()
    n_threads, n = 8, 2000
    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        threads = [threading.Thread(
            target=lambda: [obs.count("c", 3) for _ in range(n)])
            for _ in range(n_threads)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
    finally:
        sys.setswitchinterval(old)
    assert not any(t.is_alive() for t in threads)
    snap = obs.counters()
    assert snap == {"c": 3 * n * n_threads}
    obs.count("c")
    assert snap["c"] == 3 * n * n_threads      # a snapshot, not a view
    obs.reset()
    assert obs.counters() == {}


@requires_jax
def test_sweep_spans_nest_and_carry_their_stats(tmp_path):
    m, pats = _amg_patterns()
    out, lines = _traced(tmp_path, lambda: best_strategy_many(
        pats, m, seed=1, backend="numpy"))
    assert len(out) == len(pats)
    (spans,) = lines.values()                 # one thread
    _assert_nested(spans)
    (sweep,) = [sp for sp in spans if sp[0] == "repro.plan.sweep"]
    assert sweep[3] == {"patterns": len(pats), "candidates": 3 * len(pats),
                        "messages": sum(p.n_msgs for p in pats)}
    assert _inside(spans, "repro.plan.sweep") == {
        "repro.plan.bind", "repro.plan.rewrite", "repro.plan.arrivals",
        "repro.plan.arena", "repro.plan.model", "repro.plan.simulate",
        "repro.plan.verdict", "repro.sim.routing"}
    assert sum(sp[0] == "repro.plan.bind" for sp in spans) == len(pats)
    assert all("#" not in sp[0] for sp in spans)


@requires_jax
def test_threaded_queries_nest_and_count_exactly(tmp_path):
    m = lassen_machine()
    one = StrategyService(m, backend="pallas", cache=ArenaCache())
    one.query_many(_patterns(m.n_procs, 0))     # compile every shape
    obs.enable()
    obs.reset()
    assert all(r.ok for r in one.query_many(_patterns(m.n_procs, 1)))
    per_query = obs.counters()
    obs.disable()
    assert per_query["device.syncs"] > 0

    svc = StrategyService(m, backend="pallas", cache=ArenaCache())
    n_threads, per_thread = 4, 3
    obs.reset()

    def work(t):
        for q in range(per_thread):
            res = svc.query_many(_patterns(m.n_procs, 100 + t * per_thread
                                           + q))
            assert all(r.ok and not r.cached for r in res)

    def run():
        threads = [threading.Thread(target=work, args=(t,))
                   for t in range(n_threads)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=300)
        assert not any(t.is_alive() for t in threads)

    _, lines = _traced(tmp_path, run)
    n = n_threads * per_thread
    assert obs.counters() == {k: v * n for k, v in per_query.items()}
    queries = []
    for spans in lines.values():
        _assert_nested(spans)
        queries += [sp[3]["request"] for sp in spans
                    if sp[0] == "repro.service.query"]
        assert _inside(spans, "repro.service.query") >= {
            "repro.service.validate", "repro.service.admit",
            "repro.service.key", "repro.service.cache",
            "repro.service.sweep", "repro.plan.sweep", "repro.device.sync"}
    assert sorted(queries) == list(range(n))
    assert len(lines) == n_threads


@requires_jax
@pytest.mark.parametrize("backend", ["numpy", "pallas"])
def test_verdicts_are_bit_identical_with_tracing_on_and_off(backend):
    m, pats = _amg_patterns()
    off = best_strategy_many(pats, m, seed=5, backend=backend)
    obs.enable()
    on = best_strategy_many(pats, m, seed=5, backend=backend)
    obs.disable()
    assert [_key(v) for v in on] == [_key(v) for v in off]
    assert not any(v.degraded for v in on)


@requires_jax
def test_amg_sweep_on_pallas_makes_four_device_calls_and_nine_syncs():
    """Two pricing passes (model, simulator) each ship the arena's key
    column back to sort it and return sums and maxima (3 syncs each); the
    queue walk returns its steps (1) and the contention reduction its sums
    and maxima (2)."""
    m, pats = _amg_patterns()
    phases = [p.bind(m) for p in pats]
    best_strategy_many(phases, seed=2, backend="pallas")   # compile
    obs.enable()
    best_strategy_many(phases, seed=2, backend="pallas")
    c = obs.counters()
    assert c["device.calls.kernel.segment_reduce"] == 3
    assert c["device.calls.kernel.queue_walk"] == 1
    assert c["device.calls.stack.device_store"] == 2
    assert c["device.syncs"] == 9
    assert c["device.d2h_bytes"] > 0 and c["device.h2d_bytes"] > 0


@requires_jax
@pytest.mark.parametrize("on", [False, True])
def test_segment_reduce_hands_its_layout_to_the_kernel_as_host_arrays(
        monkeypatch, on):
    """The jitted kernel ships the host layout itself, traced or not; its
    bytes are counted, not shipped ahead of the call."""
    rng = np.random.default_rng(3)
    vals = rng.random(1000)
    ids = rng.integers(0, 40, 1000)
    seen = []
    real = cs._pallas_segreduce

    def spy(n_msgs, n_seg):
        fn = real(n_msgs, n_seg)

        def call(*args):
            seen.extend(type(a) for a in args[1:])
            return fn(*args)
        return call

    monkeypatch.setattr(cs, "_pallas_segreduce", spy)
    if on:
        obs.enable()
    s, mx = cs.fused_segment_reduce(vals, ids, 40)
    np.testing.assert_allclose(s, cs._segment_sum_numpy(vals, ids, 40),
                               rtol=1e-5)
    np.testing.assert_allclose(mx, cs._segment_max_numpy(vals, ids, 40),
                               rtol=1e-6)
    assert seen and all(t is np.ndarray for t in seen), seen
    layout = cs._segreduce_layout(ids, 40)
    want = {"device.calls.kernel.segment_reduce": 1, "device.syncs": 2,
            "device.d2h_bytes": 2 * 40 * 4,
            "device.h2d_bytes": vals.size * 4 + sum(a.nbytes
                                                    for a in layout)}
    assert obs.counters() == (want if on else {})


@requires_jax
def test_moe_route_and_lower_spans(tmp_path):
    """Routing and lowering each open their span once, the routing with
    its tokens and groups."""
    scores = np.random.default_rng(1).random((64, 16))

    def derive():
        choices = node_limited_topk(scores, 4, 4, 2)
        return pattern_from_choices(choices, 4, 16, 7392, 14336)

    _, lines = _traced(tmp_path, derive)
    (spans,) = lines.values()
    assert [(n, st) for n, _, _, st in spans] == [
        ("repro.workload.route", {"tokens": 64, "groups": 4}),
        ("repro.workload.lower", {})]
