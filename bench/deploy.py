"""The deployments a configuration file names, built through the program.

What the benchmark hands the system under test: its machine presets, the
AMG halo-exchange patterns of the paper's deployment, and the four-chip
v5e spec.  ``CompileClock`` counts jax's compilations.  These were the
sound pieces of the repository's chip smoke test, copied so that a change
there cannot move the yardstick.
"""
from __future__ import annotations

import hashlib

import numpy as np


class CompileClock:
    """Backend compilations since ``reset``, from jax's monitoring
    events; a program loaded from the persistent cache counts too."""

    def __init__(self):
        from jax import monitoring
        self.count = 0
        monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, name, duration, **_):
        self.count += name == "/jax/core/compile/backend_compile_duration"

    def reset(self) -> None:
        self.count = 0


def tpu_v5e_4():
    """Four TPU v5e chips as two 2-chip "nodes", shaped like the program's
    ``tpu_v5e_8`` preset."""
    from repro.core.params import tpu_v5e
    from repro.core.topology import TorusTopology
    from repro.net.machine import MachineSpec
    return MachineSpec(name="tpu_v5e_4", params=tpu_v5e(),
                       torus=TorusTopology((2, 2), wrap=True),
                       nodes_per_torus_node=1, procs_per_node=2,
                       sockets_per_node=1, link_bw=50e9,
                       torus_over_procs=True, cross_node_locality=1)


def machine(spec: dict):
    """The program's machine for a configuration's ``machine`` entry:
    ``{"preset": name, "args": {...}}``."""
    if spec["preset"] == "tpu_v5e_4":
        return tpu_v5e_4()
    import repro.net as net
    args = {k: tuple(v) if isinstance(v, list) else v
            for k, v in spec.get("args", {}).items()}
    return getattr(net, spec["preset"])(**args)


def amg_patterns(problem: dict, n_procs_max: int):
    """Unbound SpMV halo-exchange patterns of every AMG level of
    ``problem``, each level balanced over ``min(n_procs_max, rows / 2)``
    ranks; levels with no message are left out."""
    from repro.sparse import (RowPartition, build_hierarchy,
                              elasticity_like_3d, spmv_comm_pattern)
    if problem["operator"] != "elasticity_like_3d":
        raise ValueError(f"unknown operator {problem['operator']!r}")
    out = []
    for lvl in build_hierarchy(elasticity_like_3d(int(problem["grid"]))):
        n_procs = min(n_procs_max, max(lvl.A.n_rows // 2, 2))
        cp = spmv_comm_pattern(lvl.A, RowPartition.balanced(lvl.A.n_rows,
                                                            n_procs))
        if cp.n_msgs:
            out.append(cp)
    return out


def digest(src, dst, size) -> str:
    """A short digest of a message set, order included."""
    h = hashlib.sha256()
    for a, dt in ((src, np.int64), (dst, np.int64), (size, np.float64)):
        h.update(np.ascontiguousarray(a, dtype=dt).tobytes())
    return h.hexdigest()[:16]


def verdict_body(v) -> dict:
    """A program verdict's costs and winners, as the reference gives them."""
    return {"model": dict(v.model), "sim": dict(v.sim),
            "model_winner": v.model_winner, "sim_winner": v.sim_winner}
