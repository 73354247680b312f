"""Multi-device validation of shard_map components (compression, pipeline,
EP all-to-all).  Runs in a subprocess with 8 fake host devices so the main
pytest process keeps its single-device view."""
import json
import os
import subprocess
import sys

import pytest

SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import json
import jax, jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P
from repro.launch.mesh import make_mesh

results = {}

# ---------------------------------------------------------- compression ----
from repro.parallel.compression import dp_grads_compressed
mesh = make_mesh((8,), ("data",))
rng = np.random.default_rng(0)
params = {"w": jnp.asarray(rng.standard_normal((16, 4)), jnp.float32)}
batch = {"x": jnp.asarray(rng.standard_normal((32, 16)), jnp.float32),
         "y": jnp.asarray(rng.standard_normal((32, 4)), jnp.float32)}

def loss_fn(p, b):
    pred = b["x"] @ p["w"]
    return jnp.mean((pred - b["y"]) ** 2)

g_ref = jax.grad(lambda p: loss_fn(p, batch))(params)
# per-shard mean-of-grads == grad of mean loss when shards are equal-sized
g_c, err = dp_grads_compressed(loss_fn, params, batch, mesh)
rel = float(jnp.linalg.norm(g_c["w"] - g_ref["w"]) / jnp.linalg.norm(g_ref["w"]))
results["compress_rel_err"] = rel
results["compress_err_state_shape"] = list(err["w"].shape)

# error feedback: with EF, averaged compressed grads over repeated steps
# converge to the true gradient
acc_ef = jnp.zeros_like(g_ref["w"])
errs = None
for _ in range(30):
    g_c, errs = dp_grads_compressed(loss_fn, params, batch, mesh, errors=errs)
    acc_ef = acc_ef + g_c["w"]
rel_ef = float(jnp.linalg.norm(acc_ef / 30 - g_ref["w"])
               / jnp.linalg.norm(g_ref["w"]))
results["compress_ef_rel_err"] = rel_ef

# -------------------------------------------------------------- pipeline ---
from repro.parallel.pipeline import gpipe, stack_stages
mesh2 = make_mesh((4, 2), ("pod", "data"))
L, d = 8, 16
layers = {"w": jnp.asarray(rng.standard_normal((L, d, d)) / np.sqrt(d),
                           jnp.float32)}

def layer_fn(w, x):
    return jnp.tanh(x @ w)

def stage_fn(stage_params, x):
    def body(h, w):
        return layer_fn(w, h), ()
    h, _ = jax.lax.scan(body, x, stage_params["w"])
    return h

M, mb = 6, 4
xs = jnp.asarray(rng.standard_normal((M, mb, d)), jnp.float32)
stages = stack_stages(layers, 4)
y_pipe = gpipe(stage_fn, stages, xs, mesh2, axis="pod")
# sequential reference
y_ref = xs
for i in range(L):
    y_ref = jax.vmap(lambda x: layer_fn(layers["w"][i], x))(y_ref)
results["pipeline_max_err"] = float(jnp.max(jnp.abs(y_pipe - y_ref)))

# ---------------------------------------------------------------- EP a2a ---
from repro.parallel.ep_a2a import moe_ffn_ep
from repro.nn.moe import moe_ffn
from repro.configs import get_smoke_config
import dataclasses
cfg = get_smoke_config("qwen3-moe-30b-a3b")
cfg = dataclasses.replace(cfg, capacity_factor=8.0)   # no drops
mesh3 = make_mesh((8,), ("model",))
d, E, f = cfg.d_model, cfg.n_experts, cfg.moe_d_ff
p = {"router": jnp.asarray(rng.standard_normal((d, E)) * 0.02, jnp.float32),
     "w1": jnp.asarray(rng.standard_normal((E, d, f)) / np.sqrt(d), jnp.float32),
     "w3": jnp.asarray(rng.standard_normal((E, d, f)) / np.sqrt(d), jnp.float32),
     "w2": jnp.asarray(rng.standard_normal((E, f, d)) / np.sqrt(f), jnp.float32)}
x = jnp.asarray(rng.standard_normal((2, 16, d)), jnp.float32)
y_ref, _ = moe_ffn(x, p, cfg)
y_ep = moe_ffn_ep(x, p, cfg, mesh3, axis_name="model")
results["ep_rel_err"] = float(jnp.linalg.norm(y_ep - y_ref)
                              / jnp.linalg.norm(y_ref))
print(json.dumps(results))
"""


@pytest.fixture(scope="module")
def multidevice_results():
    env = dict(os.environ, JAX_PLATFORMS="cpu")    # never the parent's chip
    env["PYTHONPATH"] = os.path.abspath(
        os.path.join(os.path.dirname(__file__), "..", "src"))
    out = subprocess.run([sys.executable, "-c", SCRIPT], env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


def test_compressed_allreduce_close(multidevice_results):
    r = multidevice_results
    assert r["compress_rel_err"] < 0.02          # int8 one-shot error
    assert r["compress_err_state_shape"][0] == 8  # per-device EF state


def test_error_feedback_reduces_bias(multidevice_results):
    r = multidevice_results
    assert r["compress_ef_rel_err"] < r["compress_rel_err"]
    assert r["compress_ef_rel_err"] < 0.005


def test_pipeline_matches_sequential(multidevice_results):
    assert multidevice_results["pipeline_max_err"] < 1e-5


def test_ep_a2a_matches_dense_dispatch(multidevice_results):
    assert multidevice_results["ep_rel_err"] < 1e-4
