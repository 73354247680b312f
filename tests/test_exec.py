"""The execution layer's contract: every lowered strategy schedule delivers
payloads bit-identical to the numpy reference executor.

The numpy half (planner invariants, serial oracle equality, edge cases,
hypothesis property sweep) runs in-process.  The JAX half lowers every
strategy x all four host-scale machine presets onto a forced 8-device host
mesh in a subprocess (``XLA_FLAGS`` must be set before jax imports; the
parent pytest process keeps its single-device view) and pins exact
``np.array_equal`` payload identity plus digest agreement through the
fused segment kernels.
"""
import json
import os
import subprocess
import sys

import numpy as np
import pytest

from repro.comm.phase import CommPhase
from repro.comm.strategies import ROLES, strategies_for
from repro.exec import (build_schedule, delivered_digest, host_machines,
                        pairs_subset_of_plan, reference_delivered,
                        run_reference, units_for)

from _hypothesis_compat import given, settings, st

MACHINES = host_machines()
CASES = [(mname, strat) for mname, m in MACHINES.items()
         for strat in strategies_for(m)]


def _phase(machine, n=40, seed=0, n_procs=8, max_size=6000):
    rng = np.random.default_rng(seed)
    src = rng.integers(0, n_procs, n)
    dst = (src + rng.integers(1, n_procs, n)) % n_procs
    size = rng.integers(1, max_size, n).astype(float)
    return CommPhase.build(machine, src, dst, size, n_procs=n_procs)


# ---------------------------------------------------------------- planner --

@pytest.mark.parametrize("mname,strat", CASES,
                         ids=[f"{m}-{s}" for m, s in CASES])
def test_reference_execution_is_bit_identical(mname, strat):
    ph = _phase(MACHINES[mname])
    for coloring in ("greedy", "per_message"):
        sched = build_schedule(ph, strat, coloring=coloring)
        assert np.array_equal(run_reference(sched),
                              reference_delivered(sched))


@pytest.mark.parametrize("mname,strat", CASES,
                         ids=[f"{m}-{s}" for m, s in CASES])
def test_lowered_pairs_subset_of_pricing_plan(mname, strat):
    sched = build_schedule(_phase(MACHINES[mname]), strat)
    assert pairs_subset_of_plan(sched)
    # and the plan side exposes every lowered role
    plan_roles = set(sched.plan.roles)
    for ph in sched.phases:
        assert ph.role in plan_roles or ph.role in ("standard",)


def test_flow_conservation_every_unit_delivered_once():
    m = MACHINES["lassen_8"]
    ph = _phase(m, n=64, seed=3)
    for strat in strategies_for(m):
        sched = build_schedule(ph, strat)
        deliv = run_reference(sched)
        # each unit appears exactly once, at its destination, with payload
        hits = deliv != 0
        assert hits.sum() == sched.n_units
        np.testing.assert_array_equal(hits.sum(axis=0),
                                      np.ones(sched.n_units))
        # digest through the fused kernels agrees with the payload totals
        np.testing.assert_array_equal(
            delivered_digest(deliv, sched),
            np.bincount(sched.unit_dst, weights=sched.payload.astype(float),
                        minlength=sched.n_procs))


def test_rounds_are_valid_permutations():
    m = MACHINES["frontier_8"]
    for strat in strategies_for(m):
        sched = build_schedule(_phase(m, n=64, seed=7), strat)
        for ph in sched.phases:
            for rnd in ph.rounds:
                senders = [s for s, _ in rnd.perm]
                receivers = [d for d, _ in rnd.perm]
                assert len(set(senders)) == len(senders)
                assert len(set(receivers)) == len(receivers)
            assert ph.n_rounds <= max(1, ph.n_msgs)


def test_per_message_coloring_is_one_round_per_message():
    m = MACHINES["blue_waters_8"]
    sched = build_schedule(_phase(m), "two_step", coloring="per_message")
    for ph in sched.phases:
        assert ph.n_rounds == ph.n_msgs
    greedy = build_schedule(_phase(m), "two_step")
    assert greedy.n_rounds <= sched.n_rounds


def test_units_for_floors_and_splits():
    u = units_for([0.0, 1.0, 512.0, 513.0, 5120.0], unit_bytes=512.0)
    np.testing.assert_array_equal(u, [1, 1, 1, 2, 10])


def test_split_strategies_fan_units_across_injectors():
    m = MACHINES["blue_waters_8"]
    # one big remote message: three_step must spread units over k ranks
    ph = CommPhase.build(m, [1], [6], [8 * 512.0], n_procs=8)
    sched = build_schedule(ph, "three_step")
    inter = [p for p in sched.phases if p.role == "inter"]
    assert len(inter) == 1
    assert inter[0].n_msgs == 4        # k = min(avail) = ppn = 4 injectors
    assert np.array_equal(run_reference(sched), reference_delivered(sched))


def test_edge_cases_empty_self_single_rank():
    m = MACHINES["lassen_8"]
    empty = CommPhase.build(m, [], [], [], n_procs=8)
    selfmsg = CommPhase.build(m, [0, 3, 5], [0, 3, 5],
                              [64.0, 1024.0, 0.0], n_procs=8)
    onerank = CommPhase.build(m, [0, 0], [0, 0], [100.0, 200.0], n_procs=1)
    for phase in (empty, selfmsg, onerank):
        for strat in strategies_for(m):
            sched = build_schedule(phase, strat)
            assert sched.n_rounds == 0      # nothing crosses a rank
            assert np.array_equal(run_reference(sched),
                                  reference_delivered(sched))


def test_unknown_coloring_raises():
    m = MACHINES["lassen_8"]
    with pytest.raises(ValueError, match="coloring"):
        build_schedule(_phase(m), "standard", coloring="rainbow")


def test_copy_phases_present_and_roundless_for_host_staged():
    m = MACHINES["lassen_8"]
    sched = build_schedule(_phase(m), "host_staged")
    roles = [p.role for p in sched.phases]
    assert "d2h" in roles and "h2d" in roles
    for ph in sched.phases:
        if ph.role in ("d2h", "h2d"):
            assert ph.n_rounds == 0
            np.testing.assert_array_equal(ph.msg_src, ph.msg_dst)
    # role order follows the canonical ROLES order
    assert roles == sorted(roles, key=ROLES.index)


@given(st.integers(0, 2 ** 31 - 1), st.integers(1, 48))
@settings(max_examples=25, deadline=None)
def test_property_random_patterns_bit_identical(seed, n):
    for mname in ("blue_waters_8", "lassen_8"):
        m = MACHINES[mname]
        ph = _phase(m, n=n, seed=seed)
        for strat in strategies_for(m):
            sched = build_schedule(ph, strat)
            assert np.array_equal(run_reference(sched),
                                  reference_delivered(sched))


# -------------------------------------------------- jax: 8-device mesh ----

SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import json
import numpy as np
from repro.comm.phase import CommPhase
from repro.comm.strategies import strategies_for
from repro.exec import (build_schedule, execute, host_machines,
                        run_reference, time_schedule)

results = {"mismatches": {}, "digest_err": {}}
for mname, m in host_machines().items():
    rng = np.random.default_rng(11)
    n = 40
    src = rng.integers(0, 8, n)
    dst = (src + rng.integers(1, 8, n)) % 8
    size = rng.integers(1, 6000, n).astype(float)
    ph = CommPhase.build(m, src, dst, size, n_procs=8)
    for strat in strategies_for(m):
        sched = build_schedule(ph, strat)
        want = run_reference(sched)
        got, digest = execute(sched, digest_backend="jax")
        key = f"{mname}/{strat}"
        results["mismatches"][key] = int((got != want).sum())
        # same fused-kernel backend on both sides: the device digest of the
        # executed exchange must match the reference exchange's exactly
        # (the jax path reduces in float32, so it is only comparable to
        # itself, not to a float64 bincount)
        from repro.exec import delivered_digest
        ref_digest = delivered_digest(want, sched, backend="jax")
        results["digest_err"][key] = float(np.abs(digest - ref_digest).max())

# a timed run works end to end on the mesh
m = host_machines()["lassen_8"]
rng = np.random.default_rng(5)
src = rng.integers(0, 8, 24); dst = (src + rng.integers(1, 8, 24)) % 8
ph = CommPhase.build(m, src, dst, rng.integers(1, 4096, 24).astype(float),
                     n_procs=8)
meas = time_schedule(build_schedule(ph, "three_step"), reps=3, warmup=1)
results["median_s"] = meas.median_s
results["n_rounds"] = meas.n_rounds
print(json.dumps(results))
"""


@pytest.fixture(scope="module")
def mesh_results():
    env = dict(os.environ, JAX_PLATFORMS="cpu")    # never the parent's chip
    env["PYTHONPATH"] = os.path.abspath(
        os.path.join(os.path.dirname(__file__), "..", "src"))
    out = subprocess.run([sys.executable, "-c", SCRIPT], env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


def test_every_strategy_bit_identical_on_8_device_mesh(mesh_results):
    assert mesh_results["mismatches"], "no strategy cases ran"
    bad = {k: v for k, v in mesh_results["mismatches"].items() if v != 0}
    assert not bad, f"payload mismatch vs reference executor: {bad}"
    # all four machines x their full strategy set were covered
    covered = {k.split("/")[0] for k in mesh_results["mismatches"]}
    assert covered == set(MACHINES)
    assert len(mesh_results["mismatches"]) == len(CASES)


def test_device_digest_matches_payload_totals(mesh_results):
    worst = max(mesh_results["digest_err"].values())
    assert worst == 0.0


def test_timed_run_reports_positive_median(mesh_results):
    assert mesh_results["median_s"] > 0.0
    assert mesh_results["n_rounds"] > 0


# ------------------------------------ jax: run-aligned (block) lowering ----

def _block_cases():
    """Phases whose rounds take the window lowering of
    :mod:`repro.exec.lower`, by name: ``(machine, strategy, coloring, src,
    dst, size)``."""
    from repro.exec.lower import MAX_RUNS
    rng = np.random.default_rng(11)
    src = rng.integers(0, 8, 40)
    dst = (src + rng.integers(1, 8, 40)) % 8
    size = rng.integers(1, 6000, 40).astype(float).tolist()
    # one rank sends alternately to a node leader and to its neighbour:
    # the inter-node message is one run, its arrivals alternate
    k = 2 * MAX_RUNS + 2
    relay_dst = np.where(np.arange(k) % 2 == 0, 4, 5).tolist()
    return {
        # rank 2's 3 units are the row's last, in a round 10 wide
        "clamp": ("lassen_8", "standard", "greedy", [0, 2], [1, 3],
                  [10 * 512.0, 3 * 512.0]),
        "per_message": ("frontier_8", "standard", "per_message",
                        src.tolist(), dst.tolist(), size),
        "per_message_two_step": ("blue_waters_8", "two_step", "per_message",
                                 src.tolist(), dst.tolist(), size),
        "relay": ("blue_waters_8", "two_step", "greedy", [1] * k, relay_dst,
                  [512.0] * k),
    }


BLOCK_CASES = _block_cases()


def _block_schedule(name):
    mname, strat, coloring, src, dst, size = BLOCK_CASES[name]
    ph = CommPhase.build(MACHINES[mname], src, dst, size, n_procs=8)
    return build_schedule(ph, strat, coloring=coloring)


def _lowered(sched):
    """Every round's ``(pack, stage, final)`` as :mod:`repro.exec.lower`
    lowers them: ``(rots, arg)`` each."""
    from repro.exec.lower import _lower_table
    cols = sched.n_units + 1
    return [tuple(_lower_table(t, cols, gather)
                  for t, gather in ((r.pack, True), (r.stage, False),
                                    (r.final, False)))
            for ph in sched.phases for r in ph.rounds]


def test_block_case_clamp_shifts_the_window():
    """The last message's window would overrun the row: its start moves
    inside and the data rotates, on the sender and the receiver."""
    from repro.exec.lower import _ROT, _START
    (pack, stage, final), = _lowered(_block_schedule("clamp"))
    assert stage == ((), None)
    for rots, arg in (pack, final):
        assert rots == (True,)
        assert arg[:, 0, _START].max() == 13 + 1 - 10
    assert pack[1][2, 0, _ROT] == 6 and final[1][3, 0, _ROT] == 4


def test_block_case_per_message_leaves_most_ranks_idle():
    from repro.exec.lower import _HI
    for name in ("per_message", "per_message_two_step"):
        for pack, _, final in _lowered(_block_schedule(name)):
            assert pack[0] is not None and len(pack[0]) >= 1
            # one message a round: every other rank's run is empty
            assert (pack[1][:, :, _HI] > 0).any(axis=1).sum() == 1


def test_block_case_relay_windows_the_pack_not_the_stage():
    rounds = _lowered(_block_schedule("relay"))
    assert any(pack[0] is not None and stage[0] is None
               for pack, stage, _ in rounds)


def test_lowering_counters_follow_the_tables():
    """``standard`` rounds lower as windows with the all-sink ``stage``
    dropped; ``three_step``'s striped shares stay per word; nothing is
    counted with tracing off."""
    import jax
    from repro.comm import obs
    from repro.exec import executor_program
    mesh = jax.sharding.AbstractMesh((8,), ("rank",))
    m = MACHINES["blue_waters_8"]
    standard = build_schedule(_phase(m), "standard")
    # 64-unit messages over 4 injectors: rows of 16 one-unit runs and more
    striped = build_schedule(
        CommPhase.build(m, [1, 2], [6, 5], [64 * 512.0, 96 * 512.0],
                        n_procs=8), "three_step")
    names = ("exec.block_tables", "exec.gather_tables",
             "exec.dropped_tables")
    obs.disable()
    obs.reset()
    try:
        executor_program(standard, mesh)
        executor_program(striped, mesh)
        assert [obs.counters().get(n, 0) for n in names] == [0, 0, 0]
        obs.enable()
        executor_program(standard, mesh)
        c = obs.counters()
        assert c["exec.block_tables"] == 2 * standard.n_rounds
        assert c["exec.dropped_tables"] == standard.n_rounds
        assert c["exec.gather_tables"] == 0
        obs.reset()
        executor_program(striped, mesh)
        assert obs.counters()["exec.gather_tables"] > 0
    finally:
        obs.disable()
        obs.reset()


BLOCK_SCRIPT = r"""
import os, sys
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import json
from repro.comm import obs
from repro.comm.phase import CommPhase
from repro.exec import build_schedule, execute, host_machines, run_reference

obs.enable()
out = {}
for name, (mname, strat, coloring, src, dst, size) in json.loads(
        sys.argv[1]).items():
    obs.reset()
    ph = CommPhase.build(host_machines()[mname], src, dst, size, n_procs=8)
    sched = build_schedule(ph, strat, coloring=coloring)
    got, _ = execute(sched)
    out[name] = {"mismatches": int((got != run_reference(sched)).sum()),
                 "counters": obs.counters()}
print(json.dumps(out))
"""


@pytest.fixture(scope="module")
def block_results():
    env = dict(os.environ, JAX_PLATFORMS="cpu")    # never the parent's chip
    env["PYTHONPATH"] = os.path.abspath(
        os.path.join(os.path.dirname(__file__), "..", "src"))
    out = subprocess.run([sys.executable, "-c", BLOCK_SCRIPT,
                          json.dumps(BLOCK_CASES)], env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("name", sorted(BLOCK_CASES))
def test_block_path_bit_identical_on_8_device_mesh(block_results, name):
    r = block_results[name]
    assert r["mismatches"] == 0
    assert r["counters"]["exec.block_tables"] > 0
    if name == "relay":
        assert r["counters"]["exec.gather_tables"] > 0
