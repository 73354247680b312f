"""Compile-only checks with the TPU v5e compiler, no chip attached.

The device programs of the main path are compiled at their real sizes for
a described ``v5e:2x2`` topology: the Pallas segment reduce (interpret
mode on a CPU host hides what Mosaic refuses), the jitted queue walk, and
the lowered 4-rank exchange.  Nothing runs, so these say nothing about
results or times; ``chip_smoke.py`` runs the same programs on a chip.

The topology is described inside a fixture, never at import: only the
worker that runs this file loads the TPU compiler.
"""
import functools

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402
from jax.sharding import (Mesh, NamedSharding, PartitionSpec,  # noqa: E402
                          SingleDeviceSharding)

from repro.kernels import comm_stack as cs  # noqa: E402

N_MSGS = 1 << 20
#: segments of chip_smoke.py's AMG arena: 48 candidate phases x 8192 ranks
N_SEG = 48 * 8192


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means "cannot"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def no_persistent_cache():
    """A compile for a described chip can be written to the persistent
    cache but never read back without one: keep the cache out of it."""
    from jax.experimental.compilation_cache import compilation_cache
    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", before)
    compilation_cache.reset_cache()


def _shape(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def test_segment_reduce_compiles_for_v5e(one_chip, no_persistent_cache):
    from jax.experimental import pallas as pl

    call = pl.pallas_call(cs._segreduce_kernel,
                          **cs._segreduce_spec(N_MSGS, N_SEG),
                          interpret=False)
    fn = jax.jit(functools.partial(cs._segreduce_device, n_seg=N_SEG,
                                   call=call))
    n_chunks = N_MSGS // cs._CHUNK
    width = n_chunks + -(-(N_SEG + 1) // cs._LANE)
    compiled = fn.lower(
        _shape((N_MSGS,), jnp.float32, one_chip),
        _shape((N_MSGS,), jnp.int32, one_chip),
        _shape((n_chunks, 1, cs._CHUNK), jnp.int32, one_chip),
        _shape((width,), jnp.int32, one_chip),
        _shape((width,), jnp.int32, one_chip),
        _shape((1,), jnp.int32, one_chip)).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_jax_queue_walk_compiles_for_v5e(one_chip, no_persistent_cache):
    # one receive queue per (phase, rank) slot of the AMG arena, 2^20
    # arrivals, Fenwick trees of span 32 (depth 6)
    n_regions, depth = N_SEG, 6
    tree = n_regions * 33 + 1
    compiled = cs._jax_queue_walk(depth).lower(
        _shape((tree,), jnp.int32, one_chip),
        _shape((N_MSGS,), jnp.int32, one_chip),
        *[_shape((n_regions,), jnp.int32, one_chip)] * 4,
        _shape((), jnp.int32, one_chip)).compile()
    assert "while" in compiled.as_text()


def _four_rank_phase():
    """A seeded irregular exchange on four v5e chips as two 2-chip nodes."""
    from repro.comm import CommPhase
    from repro.core.params import tpu_v5e
    from repro.core.topology import TorusTopology
    from repro.net.machine import MachineSpec
    m = MachineSpec(name="tpu_v5e_4", params=tpu_v5e(),
                    torus=TorusTopology((2, 2), wrap=True),
                    nodes_per_torus_node=1, procs_per_node=2,
                    sockets_per_node=1, link_bw=50e9,
                    torus_over_procs=True, cross_node_locality=1)
    rng = np.random.default_rng(7)
    src = rng.integers(0, 4, 96)
    dst = (src + rng.integers(1, 4, 96)) % 4
    size = rng.integers(256, 8192, 96).astype(float)
    return CommPhase.build(m, src, dst, size, n_procs=4)


@pytest.mark.parametrize("strategy", ["standard", "two_step", "three_step"])
def test_exec_program_compiles_on_v5e_mesh(topo, strategy,
                                           no_persistent_cache):
    from repro.exec import build_schedule, executor_program

    mesh = Mesh(np.asarray(topo.devices), ("rank",))
    sched = build_schedule(_four_rank_phase(), strategy)
    fn, args = executor_program(sched, mesh)
    rank = NamedSharding(mesh, PartitionSpec("rank"))
    shapes = jax.tree.map(lambda a: _shape(a.shape, a.dtype, rank), args)
    compiled = fn.lower(*shapes).compile()
    assert "collective-permute" in compiled.as_text()
