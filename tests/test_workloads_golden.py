"""Golden winner table for the shipped LLM workload scenarios (ISSUE 7).

The registry sweep is deterministic end to end (seeded routing histograms,
seeded arrival streams, noise-free simulator defaults), so the strategy the
model predicts — and the one the simulator confirms — for every
(machine, scenario, phase) cell is a reproducible artifact.  This test pins
the full table the way PR 5's crossover golden pinned the GPU-strategy
switch: a strategy-selection regression anywhere in the model ladder, the
rewrites, the simulator or the workload derivations flips a cell and fails
loudly with the diff.

The pinned verdicts are physics, not coincidence: on lassen (dual-rail
host NICs, host-staged path competitive) the dense MoE all-to-alls
aggregate via ``host_staged`` and the bulk-volume TP/pipeline phases via
``three_step``; on frontier (GPU-side NICs) and the CPU baseline
(blue_waters Gemini) the cheap paths win — ``standard`` for the
already-minimal-message shapes, ``three_step`` where combine-side
aggregation pays.  Model and simulator agree on every cell.
"""
import pytest

from repro.workloads import DEFAULT_SCENARIOS, default_machines, sweep

# (machine, scenario, phase) -> (model_winner, sim_winner)
GOLDEN = {
    ("lassen", "qwen3-moe-a2a", "dispatch"): ("host_staged", "host_staged"),
    ("lassen", "qwen3-moe-a2a", "combine"): ("host_staged", "host_staged"),
    ("lassen", "deepseek-moe-a2a", "dispatch"): ("host_staged", "host_staged"),
    ("lassen", "deepseek-moe-a2a", "combine"): ("host_staged", "host_staged"),
    ("lassen", "llama3-tp", "reduce_scatter"): ("three_step", "three_step"),
    ("lassen", "llama3-tp", "all_gather"): ("three_step", "three_step"),
    ("lassen", "llama3-pipeline", "p2p"): ("three_step", "three_step"),
    ("frontier", "qwen3-moe-a2a", "dispatch"): ("standard", "standard"),
    ("frontier", "qwen3-moe-a2a", "combine"): ("three_step", "three_step"),
    ("frontier", "deepseek-moe-a2a", "dispatch"): ("standard", "standard"),
    ("frontier", "deepseek-moe-a2a", "combine"): ("three_step", "three_step"),
    ("frontier", "llama3-tp", "reduce_scatter"): ("standard", "standard"),
    ("frontier", "llama3-tp", "all_gather"): ("standard", "standard"),
    ("frontier", "llama3-pipeline", "p2p"): ("three_step", "three_step"),
    ("blue_waters", "qwen3-moe-a2a", "dispatch"): ("standard", "standard"),
    ("blue_waters", "qwen3-moe-a2a", "combine"): ("three_step", "three_step"),
    ("blue_waters", "deepseek-moe-a2a", "dispatch"): ("standard", "standard"),
    ("blue_waters", "deepseek-moe-a2a", "combine"): ("three_step", "three_step"),
    ("blue_waters", "llama3-tp", "reduce_scatter"): ("standard", "standard"),
    ("blue_waters", "llama3-tp", "all_gather"): ("standard", "standard"),
    ("blue_waters", "llama3-pipeline", "p2p"): ("standard", "standard"),
}


@pytest.fixture(scope="module")
def rows():
    return sweep()


def test_table_covers_the_full_cross_product(rows):
    keys = [(r.machine, r.scenario, r.phase) for r in rows]
    assert len(keys) == len(set(keys)) == len(GOLDEN)
    assert set(keys) == set(GOLDEN)
    # machines in preset order, scenarios in registry order within each
    machine_order = [m for m, _, _ in keys]
    assert machine_order == sorted(machine_order,
                                   key=list(default_machines()).index)


def test_winners_match_golden(rows):
    got = {(r.machine, r.scenario, r.phase): (r.model_winner, r.sim_winner)
           for r in rows}
    mismatches = {k: (got[k], GOLDEN[k]) for k in GOLDEN if got[k] != GOLDEN[k]}
    assert not mismatches, f"winner table drifted: {mismatches}"


def test_model_and_simulator_agree_everywhere(rows):
    disagree = [(r.machine, r.scenario, r.phase, r.model_winner, r.sim_winner)
                for r in rows if not r.agree]
    assert not disagree


def test_costs_are_sane(rows):
    for r in rows:
        assert 0 < r.sim < 1.0, (r.scenario, r.sim)      # sub-second phases
        assert 0 < r.model < 1.0
        assert r.n_msgs > 0 and r.total_bytes > 0


def test_sweep_is_deterministic(rows):
    again = sweep()
    assert [(r.machine, r.scenario, r.phase, r.model_winner, r.sim_winner,
             r.model, r.sim) for r in rows] == \
           [(r.machine, r.scenario, r.phase, r.model_winner, r.sim_winner,
             r.model, r.sim) for r in again]


def test_scenarios_are_the_shipped_set():
    assert [sc.name for sc in DEFAULT_SCENARIOS] == \
        ["qwen3-moe-a2a", "deepseek-moe-a2a", "llama3-tp", "llama3-pipeline"]


@pytest.mark.parametrize("backend", ["numpy", "jax", "pallas"])
def test_sweep_backend_winners_match_numpy(rows, backend):
    """``sweep(backend=...)`` reaches the stacked passes' device backends
    and picks the numpy sweep's winners, with no fallback on the way."""
    from repro.comm.health import get_health, reset_health
    if backend != "numpy":
        pytest.importorskip("jax")
    reset_health()
    got = sweep(backend=backend)
    assert get_health().n_events == 0
    assert not any(r.degraded for r in got)
    assert [(r.model_winner, r.sim_winner) for r in got] == \
           [(r.model_winner, r.sim_winner) for r in rows]
    for g, w in zip(got, rows):
        assert g.model == pytest.approx(w.model, rel=1e-4)
        assert g.sim == pytest.approx(w.sim, rel=1e-4)
