"""Backend-agnostic array namespace ("xp") for the comm engine.

The segmented arena passes are written against a small numpy-compatible
surface (``asarray`` / ``where`` / ``minimum`` / ``ceil`` / arithmetic).
This module maps a resolved backend name to the module implementing that
surface — ``numpy`` itself, or ``jax.numpy`` for the device backends — so
:func:`repro.comm.primitives.transport_times` and the stack's pricing path
run unchanged under either, without per-call host<->device conversion.

Contract: with ``xp is numpy`` the engine's bit-identity guarantee holds
(same ops, same accumulation order, float64).  With ``xp is jax.numpy``
arrays stay device-resident end to end and results are float32-allclose.
"""
from __future__ import annotations

import numpy as np

#: backend names served by :func:`get_xp` with a device namespace
JAX_BACKENDS = ("jax", "pallas")


def get_xp(backend: str | None):
    """The array namespace for a *resolved* backend name.

    ``None`` / ``"numpy"`` -> :mod:`numpy`; ``"jax"`` / ``"pallas"`` ->
    :mod:`jax.numpy` (imported lazily — tier-1 environments without jax
    never pay the import).
    """
    if backend is None or backend == "numpy":
        return np
    if backend in JAX_BACKENDS:
        import jax.numpy as jnp
        return jnp
    raise ValueError(f"no array namespace for backend {backend!r}; "
                     f"expected 'numpy' or one of {JAX_BACKENDS}")


def float_dtype(xp):
    """The working float dtype under ``xp``: float64 on numpy (bit-identity
    contract), float32 on the device namespaces (allclose contract)."""
    return np.float64 if xp is np else xp.float32


def is_device_array(a) -> bool:
    """True when ``a`` lives on a device backend (a jax Array)."""
    return type(a).__module__.split(".")[0] == "jaxlib" or \
        type(a).__module__.split(".")[0] == "jax"


def to_numpy(a) -> np.ndarray:
    """Materialise ``a`` on the host as a numpy array (no-op for numpy)."""
    return np.asarray(a)
