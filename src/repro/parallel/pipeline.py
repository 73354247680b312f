"""GPipe-style pipeline parallelism over a mesh axis (default: "pod").

Multi-pod note: inter-pod DCN bandwidth is far below ICI, so the pod axis is
the natural pipeline boundary — each pod holds a contiguous stage of layers
and only [microbatch, seq, d_model] activations cross the DCN per tick,
instead of per-layer collectives.  The schedule is plain GPipe: M
microbatches flow through S stages in M + S - 1 ticks via
``collective_permute`` (ppermute); bubble ticks compute on garbage and are
masked out.

``gpipe`` is generic over a ``stage_fn(stage_params, x) -> y`` with matching
x/y shapes (transformer blocks).  The dry-run exposes it as a variant config;
tests validate numerically on a fake multi-device mesh.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P


def stack_stages(layer_params, n_stages: int):
    """[L, ...] stacked layer params -> [S, L/S, ...] stage-major params."""
    def f(a):
        L = a.shape[0]
        assert L % n_stages == 0, (L, n_stages)
        return a.reshape((n_stages, L // n_stages) + a.shape[1:])
    return jax.tree.map(f, layer_params)


def gpipe(stage_fn, stage_params, microbatches, mesh, axis: str = "pod"):
    """Run microbatches through pipeline stages laid out on ``axis``.

    stage_fn: (per-stage params, x [mb, ...]) -> y [mb, ...]
    stage_params: pytree with leading stage dim S == mesh.shape[axis]
    microbatches: [M, mb, ...] (replicated input)
    Returns [M, mb, ...] outputs of the last stage (replicated).
    """
    S = mesh.shape[axis]
    M = microbatches.shape[0]
    perm = [(i, (i + 1) % S) for i in range(S)]

    @functools.partial(
        jax.shard_map, mesh=mesh,
        in_specs=(jax.tree.map(lambda _: P(axis), stage_params), P()),
        out_specs=P())
    def _run(params_local, mb):
        p = jax.tree.map(lambda a: a[0], params_local)
        s = jax.lax.axis_index(axis)
        is_first = (s == 0)
        is_last = (s == S - 1)

        def tick(t, state):
            carry, outs = state
            recv = jax.lax.ppermute(carry, axis, perm)
            feed_idx = jnp.clip(t, 0, M - 1)
            x_in = jnp.where(is_first, mb[feed_idx], recv)
            y = stage_fn(p, x_in)
            out_idx = jnp.clip(t - (S - 1), 0, M - 1)
            valid = jnp.logical_and(is_last, t >= S - 1)
            outs = jnp.where(valid, outs.at[out_idx].set(y), outs)
            return y, outs

        carry0 = jax.lax.pcast(jnp.zeros_like(mb[0]), (axis,), to="varying")
        outs0 = jax.lax.pcast(jnp.zeros_like(mb), (axis,), to="varying")
        _, outs = jax.lax.fori_loop(0, M + S - 1, tick, (carry0, outs0))
        # broadcast the last stage's outputs to every stage
        outs = jnp.where(is_last, outs, jnp.zeros_like(outs))
        return jax.lax.psum(outs, axis)

    return _run(stage_params, microbatches)
