"""Tests for the node-aware strategy layer (repro.comm.strategies).

Three layers of certification:

* **conservation** — flow identities over the rewritten message arrays alone
  must reproduce the original per-source / per-destination / per-node-pair
  payload (so a rewrite can neither drop, duplicate, nor misroute bytes);
  the power-of-two variant makes the per-destination check a *pairwise*
  certificate (sums of distinct powers of two decode uniquely);
* **equivalence** — the vectorized rewrites match a deliberately scalar
  dict-based reference, message for message, and the aggregated rewrites
  are bit-identical to expanding every message into its ``k`` shares and
  summing them by sorting;
* **golden crossover** — on a fixed AMG level the model ladder must predict
  an aggregated winner and the simulator must agree (the NAPSpMV result the
  example prints).
"""
import numpy as np
import pytest

from repro.comm import (CommPhase, STRATEGIES, best_strategy,
                        delivered_payload, injected_payload, rewrite,
                        sum_by_pairs, segmented_arange)
from repro.core import phase_cost_many, sequence_cost
from repro.net import (blue_waters_machine, tpu_v5e_machine, simulate_many,
                       simulate_sequence)
from repro.net.machine import lassen_machine
from repro.sparse import (RowPartition, build_hierarchy, elasticity_like_3d,
                          spmv_comm_pattern)

MACHINES = [blue_waters_machine((2, 2, 1)), tpu_v5e_machine((4, 4))]


def _random_phase(machine, n_msgs, seed, n_procs=None):
    rng = np.random.default_rng(seed)
    P = n_procs or machine.n_procs
    src = rng.integers(0, P, n_msgs)
    dst = rng.integers(0, P, n_msgs)
    keep = src != dst
    size = rng.integers(8, 1 << 14, n_msgs).astype(float)
    return CommPhase.build(machine, src[keep], dst[keep], size[keep],
                           n_procs=P)


# ------------------------------------------------------- conservation -------
@pytest.mark.parametrize("machine", MACHINES, ids=lambda m: m.name)
@pytest.mark.parametrize("strategy", STRATEGIES)
@pytest.mark.parametrize("seed", [0, 1, 2])
def test_payload_conservation(machine, strategy, seed):
    """Injected / delivered / node-pair payloads survive every rewrite."""
    phase = _random_phase(machine, 400, seed)
    plan = rewrite(phase, strategy)
    P = phase.n_procs
    np.testing.assert_allclose(
        injected_payload(plan),
        np.bincount(phase.src, weights=phase.size, minlength=P))
    np.testing.assert_allclose(
        delivered_payload(plan),
        np.bincount(phase.dst, weights=phase.size, minlength=P))
    # payload crossing each (send-node, recv-node) boundary is invariant
    sn_o = phase.send_node
    dn_o = np.asarray(machine.node_of(phase.dst))
    rem = sn_o != dn_o
    ref = sum_by_pairs(sn_o[rem], dn_o[rem], phase.size[rem])
    got = plan.inter_node_pair_bytes()
    for a, b in zip(got, ref):
        np.testing.assert_allclose(a, b)


@pytest.mark.parametrize("machine", MACHINES, ids=lambda m: m.name)
@pytest.mark.parametrize("strategy", ["two_step", "three_step"])
def test_phase_roles_stay_in_their_lane(machine, strategy):
    """gather/scatter never cross nodes; the inter phase always does."""
    phase = _random_phase(machine, 500, 3)
    plan = rewrite(phase, strategy)
    assert plan.roles[0] in ("local", "gather")          # execution order
    for ph, role in zip(plan.phases, plan.roles):
        crosses = ph.send_node != np.asarray(machine.node_of(ph.dst))
        if role == "inter":
            assert crosses.all()
        else:
            assert not crosses.any()


@pytest.mark.parametrize("strategy", ["two_step", "three_step"])
def test_pairwise_conservation_powers_of_two(strategy):
    """Per-destination sums of distinct powers of two decode uniquely, so
    matching them certifies delivery of each individual (src, dst) payload."""
    machine = blue_waters_machine((2, 1, 1))
    rng = np.random.default_rng(7)
    P = machine.n_procs
    src = rng.integers(0, P, 120)
    dst = rng.integers(0, P, 120)
    keep = src != dst
    src, dst = src[keep], dst[keep]
    # size = 2^(rank of the message within its destination's group)
    order = np.argsort(dst, kind="stable")
    rank = np.empty(src.size, dtype=np.int64)
    rank[order] = segmented_arange(np.bincount(dst, minlength=P))
    size = np.power(2.0, rank + 6)       # >= 64 bytes, distinct per receiver
    phase = CommPhase.build(machine, src, dst, size, n_procs=P)
    plan = rewrite(phase, strategy)
    np.testing.assert_array_equal(
        delivered_payload(plan),
        np.bincount(dst, weights=size, minlength=P))


# -------------------------------------------- scalar-reference equivalence --
def _two_step_reference(phase):
    """Dict-based per-message reference for the two_step rewrite."""
    m, ppn = phase.machine, phase.machine.procs_per_node
    local, gather, inter, scatter = {}, {}, {}, {}
    for s, d, z in zip(phase.src, phase.dst, phase.size):
        s, d, z = int(s), int(d), float(z)
        sn, dn = s // ppn, d // ppn
        if sn == dn:
            local[(s, d)] = local.get((s, d), 0.0) + z
            continue
        ls, ld = sn * ppn, dn * ppn
        if s != ls:
            gather[(s, ls)] = gather.get((s, ls), 0.0) + z
        inter[(ls, ld)] = inter.get((ls, ld), 0.0) + z
        if d != ld:
            scatter[(ld, d)] = scatter.get((ld, d), 0.0) + z
    return {"local": local, "gather": gather, "inter": inter,
            "scatter": scatter}


@pytest.mark.parametrize("machine", MACHINES, ids=lambda m: m.name)
def test_two_step_matches_scalar_reference(machine):
    """The vectorized rewrite == the per-message dict walk, exactly."""
    phase = _random_phase(machine, 600, 11)
    plan = rewrite(phase, "two_step")
    ref = _two_step_reference(phase)
    for role in ("local", "gather", "inter", "scatter"):
        ph = plan.phase_by_role(role)
        got: dict = {}
        for s, d, z in zip(*( (ph.src, ph.dst, ph.size) if ph is not None
                              else ((), (), ()) )):
            # the local phase keeps original duplicates as-is; sum them for
            # comparison against the aggregating reference
            got[(int(s), int(d))] = got.get((int(s), int(d)), 0.0) + float(z)
        assert got == pytest.approx(ref[role]), role


def _pair_sums_sorted(a, b, w):
    """Pair sums by sorting the packed key: ``np.unique``, then
    ``bincount`` on its inverse."""
    if a.size == 0:
        return a, b, w
    span = np.int64(b.max()) + 1
    uk, inv = np.unique(a * span + b, return_inverse=True)
    return uk // span, uk % span, np.bincount(inv, weights=w)


def _expanded_rewrite(phase, split, staged=False):
    """The aggregated rewrites by expansion: every remote message repeated
    into its ``k`` shares, each phase's pairs summed by sorting.  Returns
    ``(role, src, dst, size, loc)`` per phase, as ``rewrite`` builds them."""
    m, P = phase.machine, phase.n_procs
    ppn = np.int64(m.procs_per_node)
    remote = phase.send_node != phase.dst // ppn

    def avail(nodes):
        return np.minimum(ppn, P - nodes * ppn)

    parts = [("local", phase.src[~remote], phase.dst[~remote],
              phase.size[~remote], None)]
    rs, rd, rsz = phase.src[remote], phase.dst[remote], phase.size[remote]
    rsn, rdn = rs // ppn, rd // ppn
    inter_loc = h2d = None
    if staged:
        h2d = m.params.class_index("h2d")
        inter_loc = m.params.class_index("host_staged")
        parts.append(("d2h", *_pair_sums_sorted(rs, rs, rsz), h2d))
    k = (np.minimum(avail(rsn), avail(rdn)) if split
         else np.ones(rs.size, dtype=np.int64))
    rep = np.repeat(np.arange(rs.size), k)
    rank = segmented_arange(k)
    share = rsz[rep] / k[rep]
    g_src, g_dst = rs[rep], rsn[rep] * ppn + rank
    keep = g_src != g_dst
    parts.append(("gather", *_pair_sums_sorted(g_src[keep], g_dst[keep],
                                               share[keep]), None))
    Sn, Dn, B = _pair_sums_sorted(rsn, rdn, rsz)
    kp = (np.minimum(avail(Sn), avail(Dn)) if split
          else np.ones(Sn.size, dtype=np.int64))
    prep = np.repeat(np.arange(Sn.size), kp)
    prank = segmented_arange(kp)
    parts.append(("inter", Sn[prep] * ppn + prank, Dn[prep] * ppn + prank,
                  B[prep] / kp[prep], inter_loc))
    s_src, s_dst = rdn[rep] * ppn + rank, rd[rep]
    keep = s_src != s_dst
    parts.append(("scatter", *_pair_sums_sorted(s_src[keep], s_dst[keep],
                                                share[keep]), None))
    if staged:
        parts.append(("h2d", *_pair_sums_sorted(rd, rd, rsz), h2d))
    out = []
    for role, src, dst, size, loc in parts:
        if len(src):
            ph = CommPhase.build(m, src, dst, size, n_procs=P, loc=loc)
            out.append((role, ph.src, ph.dst, ph.size, ph.loc))
    return out


def _fan_out_phase(case, seed):
    """A random phase for the bit-identity test.  ``partial`` leaves the
    last node part-filled, so the number of shares varies by message.
    ``zero_sizes`` adds zero-byte messages, messages sent off node by
    injector ranks (every node's first ranks), and a rank whose only
    messages to and from full nodes are empty: its fan-out rows above the
    part-filled node's share count carry 0 bytes and must still exist."""
    machine = (lassen_machine() if case.startswith("lassen")
               else blue_waters_machine((2, 2, 1)))
    ppn = machine.procs_per_node
    P = machine.n_procs - (ppn // 2 + 1 if "partial" in case else 0)
    rng = np.random.default_rng(seed)
    src = rng.integers(0, P, 1500)
    dst = rng.integers(0, P, 1500)
    # sizes over many decades, so that another order of addition would
    # change the sums' low bits
    size = rng.random(1500) * 10.0 ** rng.integers(0, 7, 1500)
    if case.endswith("zero_sizes"):
        size[::5] = 0.0
        quiet, peer = ppn + 1, 3 * ppn + 2       # both on full nodes
        alone = (src != quiet) & (dst != quiet)
        inj = np.arange(0, P, ppn) + seed % 2
        src = np.concatenate([src[alone], inj, [quiet, quiet, peer, P - 1]])
        dst = np.concatenate([dst[alone], (inj + ppn) % P,
                              [peer, P - 1, quiet, quiet]])
        size = np.concatenate([size[alone], np.zeros(inj.size),
                               [0.0, 7.0, 0.0, 5.0]])
    keep = src != dst
    return CommPhase.build(machine, src[keep], dst[keep], size[keep],
                           n_procs=P)


@pytest.mark.parametrize("seed", [0, 1])
@pytest.mark.parametrize("case", ["blue_waters_full", "blue_waters_partial",
                                  "lassen_zero_sizes",
                                  "lassen_partial_zero_sizes"])
def test_aggregated_rewrites_are_bit_identical_to_share_expansion(case,
                                                                  seed):
    """``two_step``, ``three_step`` and ``host_staged`` sum each fan-out
    row from the messages directly; every phase matches the expanded
    shares summed by sorting, bit for bit."""
    phase = _fan_out_phase(case, seed)
    cases = [("two_step", False, False), ("three_step", True, False)]
    if case.startswith("lassen"):
        cases.append(("host_staged", True, True))
    for name, split, staged in cases:
        plan = rewrite(phase, name)
        want = _expanded_rewrite(phase, split, staged)
        assert plan.roles == tuple(w[0] for w in want), name
        for ph, (role, src, dst, size, loc) in zip(plan.phases, want):
            np.testing.assert_array_equal(ph.src, src, err_msg=role)
            np.testing.assert_array_equal(ph.dst, dst, err_msg=role)
            np.testing.assert_array_equal(ph.size, size, err_msg=role)
            # bit for bit: no reordered addition, no -0.0 for 0.0
            np.testing.assert_array_equal(ph.size.view(np.uint64),
                                          size.view(np.uint64),
                                          err_msg=role)
            np.testing.assert_array_equal(ph.loc, loc, err_msg=role)


def test_two_step_reduces_inter_node_msgs_clustered():
    """On a clustered pattern (every process talks to every process of two
    peer nodes) aggregation collapses inter-node traffic to one message per
    node pair."""
    machine = blue_waters_machine((2, 2, 1))
    ppn = machine.procs_per_node
    src, dst = [], []
    for node in range(4):
        for peer in ((node + 1) % 4, (node + 2) % 4):
            for i in range(ppn):
                for j in range(0, ppn, 4):
                    src.append(node * ppn + i)
                    dst.append(peer * ppn + j)
    size = np.full(len(src), 256.0)
    phase = CommPhase.build(machine, src, dst, size, n_procs=4 * ppn)
    std = rewrite(phase, "standard")
    two = rewrite(phase, "two_step")
    assert std.inter_node_msgs == len(src)
    assert two.inter_node_msgs == 8          # one per (node, peer) pair
    assert two.inter_node_msgs < std.inter_node_msgs
    # three_step trades message count for injection spread, but still far
    # fewer than standard on a clustered pattern
    three = rewrite(phase, "three_step")
    assert two.inter_node_msgs <= three.inter_node_msgs
    assert three.inter_node_msgs < std.inter_node_msgs


# ------------------------------------------------------ cost plumbing -------
def test_sequence_cost_and_simulation_sum_over_phases():
    machine = blue_waters_machine((2, 2, 1))
    phase = _random_phase(machine, 300, 5)
    plan = rewrite(phase, "three_step")
    seq = sequence_cost(plan.phases, level="contention")
    parts = phase_cost_many(plan.phases, level="contention")
    assert seq.total == pytest.approx(sum(p.total for p in parts))
    assert seq.queue == pytest.approx(sum(p.queue for p in parts))
    sim = simulate_sequence(plan.phases)
    sims = simulate_many(plan.phases)
    assert sim.time == pytest.approx(sum(r.time for r in sims))
    assert len(sim.phases) == plan.n_phases


def test_standard_is_identity():
    machine = blue_waters_machine((2, 1, 1))
    phase = _random_phase(machine, 100, 9)
    plan = rewrite(phase, "standard")
    assert plan.phases == (phase,)
    assert plan.roles == ("standard",)
    assert sequence_cost(plan.phases).total == pytest.approx(
        phase_cost_many([phase])[0].total)


def test_unknown_strategy_raises():
    machine = blue_waters_machine((2, 1, 1))
    phase = _random_phase(machine, 10, 0)
    with pytest.raises(ValueError, match="unknown strategy"):
        rewrite(phase, "four_step")


def test_intra_node_phase_degenerates_to_identity():
    """A phase with no inter-node traffic is untouched by every strategy."""
    machine = blue_waters_machine((2, 1, 1))
    src = np.arange(0, 8)
    dst = np.arange(8, 16)        # same node (ppn = 16)
    phase = CommPhase.build(machine, src, dst, np.full(8, 64.0), n_procs=16)
    for s in STRATEGIES:
        plan = rewrite(phase, s)
        assert plan.roles == ("standard",)
        assert plan.phases == (phase,)


# ------------------------------------------------------ golden crossover ----
def test_golden_amg_crossover_model_and_simulator_agree():
    """The message-heavy AMG level flips to an aggregated strategy: the
    model ladder predicts it and the simulator confirms it, with a solid
    margin (golden expectations pinned from the example output)."""
    A = elasticity_like_3d(12)
    levels = build_hierarchy(A)
    machine = blue_waters_machine((4, 2, 2))
    lvl = levels[1]
    part = RowPartition.balanced(lvl.A.n_rows, max(lvl.A.n_rows // 2, 2))
    v = spmv_comm_pattern(lvl.A, part).best_strategy(machine, seed=0)
    assert v.model_winner == "three_step"
    assert v.sim_winner == "three_step"
    assert v.agree
    # aggregation must win by a real margin on both sides of the gap
    assert v.model["three_step"] < 0.75 * v.model["standard"]
    assert v.sim["three_step"] < 0.75 * v.sim["standard"]
    # and the coarsest level must NOT flip (little traffic, nothing to win)
    coarse = levels[-1]
    partc = RowPartition.balanced(coarse.A.n_rows,
                                  max(coarse.A.n_rows // 2, 2))
    vc = spmv_comm_pattern(coarse.A, partc).best_strategy(machine, seed=0)
    assert vc.sim_winner == "standard"


def test_best_strategy_requires_machine_for_patterns():
    A = elasticity_like_3d(8)
    part = RowPartition.balanced(A.n_rows, 8)
    cp = spmv_comm_pattern(A, part)
    with pytest.raises(ValueError, match="needs a machine"):
        best_strategy(cp)
    with pytest.raises(ValueError, match="unknown arrival"):
        best_strategy(cp, blue_waters_machine((2, 1, 1)), arrival="Random")


def test_best_strategy_rebinds_phase_to_explicit_machine():
    """Passing a bound phase plus a different machine must re-evaluate on
    that machine, not silently reuse the stale binding."""
    bw = blue_waters_machine((2, 1, 1))          # 32 procs
    tpu = tpu_v5e_machine((8, 4))                # 32 procs, other parameters
    phase = _random_phase(bw, 300, 13, n_procs=bw.n_procs)
    v_bw = best_strategy(phase, seed=0)
    v_tpu = best_strategy(phase, tpu, seed=0)
    assert v_tpu.plans["standard"].phases[0].machine is tpu
    assert v_tpu.sim != v_bw.sim      # other parameter table -> other times
