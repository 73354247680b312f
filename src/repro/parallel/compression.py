"""Gradient compression: int8 quantized all-reduce with error feedback.

The DP gradient reduce dominates wire bytes at scale; quantizing to int8
with per-block scales cuts them 4x (bf16) / 8x (f32).  Error feedback keeps
the *accumulated* quantization error bounded, preserving convergence
(Karimireddy et al., 2019).

``compressed_psum`` runs inside shard_map: each device quantizes its local
shard, the int8 payload is summed (as int32 — no overflow below ~2^23
participants), and the result is dequantized with the globally-maxed scale.
The error-feedback residual is returned for the caller to carry.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P


def quantize_int8(x, scale):
    q = jnp.clip(jnp.round(x / scale), -127, 127).astype(jnp.int8)
    return q


def compressed_psum(x, axis_name: str, error: jnp.ndarray | None = None):
    """int8 + error-feedback psum over ``axis_name`` (call inside shard_map).

    Returns (mean-reduced value, new error-feedback residual).
    """
    xf = x.astype(jnp.float32)
    if error is not None:
        xf = xf + error
    # one scale per device-shard, maxed across the axis so dequant agrees
    local_max = jnp.max(jnp.abs(xf))
    gmax = jax.lax.pmax(local_max, axis_name)
    scale = jnp.maximum(gmax, 1e-12) / 127.0
    q = quantize_int8(xf, scale)
    new_error = xf - q.astype(jnp.float32) * scale
    total = jax.lax.psum(q.astype(jnp.int32), axis_name)
    n = jax.lax.psum(jnp.ones((), jnp.int32), axis_name)
    return total.astype(jnp.float32) * scale / n.astype(jnp.float32), new_error


def dp_grads_compressed(loss_fn, params, batch, mesh,
                        axis_name: str = "data", errors=None):
    """Data-parallel gradients with int8+EF compressed all-reduce.

    ``loss_fn(params, batch) -> scalar`` computed on each device's batch
    shard inside shard_map; per-shard grads are reduced with
    :func:`compressed_psum`.  Returns (mean grads, new error pytree).
    The uncompressed reference is ``jax.grad`` of the mean loss.
    """
    n_dev = mesh.shape[axis_name]
    if errors is None:
        # per-device EF residuals, stacked on a leading device axis
        errors = jax.tree.map(
            lambda g: jnp.zeros((n_dev,) + g.shape, jnp.float32), params)

    @functools.partial(
        jax.shard_map, mesh=mesh,
        in_specs=(P(), jax.tree.map(lambda _: P(axis_name), batch),
                  jax.tree.map(lambda _: P(axis_name), errors)),
        out_specs=(P(), jax.tree.map(lambda _: P(axis_name), errors)))
    def _grads(p, b, e):
        # grad w.r.t. a *varying* copy of the params: differentiating the
        # replicated input directly would insert an implicit psum (transpose
        # of replication), defeating quantize-before-reduce.
        p_local = jax.tree.map(
            lambda a: jax.lax.pcast(a, (axis_name,), to="varying"), p)
        g = jax.grad(loss_fn)(p_local, b)
        flat_g, td = jax.tree.flatten(g)
        flat_e, _ = jax.tree.flatten(e)
        outs = [compressed_psum(gl, axis_name, el[0])
                for gl, el in zip(flat_g, flat_e)]
        return (jax.tree.unflatten(td, [o[0] for o in outs]),
                jax.tree.unflatten(td, [o[1][None] for o in outs]))

    return _grads(params, batch, errors)
