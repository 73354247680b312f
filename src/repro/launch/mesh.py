"""Production meshes.  A FUNCTION (not module-level constant): importing this
module never touches jax device state."""
from __future__ import annotations

import jax


def make_mesh(shape, axes):
    """``jax.make_mesh`` with ``Auto`` sharding on every axis."""
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 single-pod (256 chips) or 2x16x16 multi-pod (512 chips)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_rank_mesh(n_ranks: int):
    """1-D ``("rank",)`` mesh over the first ``n_ranks`` local devices — the
    execution mesh :mod:`repro.exec` lowers strategy schedules onto (one
    mesh rank per simulated MPI rank).  Raises ``ValueError`` when fewer
    than ``n_ranks`` devices exist; tests force an 8-device host mesh with
    ``XLA_FLAGS=--xla_force_host_platform_device_count=8``."""
    import numpy as np
    devices = jax.devices()
    if len(devices) < n_ranks:
        raise ValueError(
            f"make_rank_mesh({n_ranks}) needs {n_ranks} devices but only "
            f"{len(devices)} exist; set "
            "XLA_FLAGS=--xla_force_host_platform_device_count=<n> before "
            "importing jax to fake a host mesh")
    return jax.sharding.Mesh(np.asarray(devices[:n_ranks]), ("rank",))


def make_host_mesh(model_parallel: int = 1):
    """Mesh over whatever devices exist locally (tests / examples)."""
    n = len(jax.devices())
    assert n % model_parallel == 0
    return make_mesh((n // model_parallel, model_parallel), ("data", "model"))
