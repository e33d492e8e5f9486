"""Production meshes (v5e).

Defined as functions, never module-level constants: importing this module
must not touch jax device state (the dry-run pins the device count before
any jax initialization).
"""
from __future__ import annotations

import numpy as np

import jax
from jax.sharding import Mesh


def device_summary() -> dict:
    """``{"platform", "kind", "count"}`` of the devices JAX sees."""
    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def _mesh(shape, axes):
    n = int(np.prod(shape))
    devs = jax.devices()
    # a real error, not an assert: a too-small device pool must fail loudly
    # even under `python -O` (a silently mis-shaped Mesh crashes far later)
    if len(devs) < n:
        raise ValueError(f"need {n} devices, have {len(devs)}")
    return Mesh(np.asarray(devs[:n]).reshape(shape), axes)


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 = 256 chips per pod; 2 pods = 512 chips multi-pod."""
    if multi_pod:
        return _mesh((2, 16, 16), ("pod", "data", "model"))
    return _mesh((16, 16), ("data", "model"))


def make_test_mesh(n_data: int = 2, n_model: int = 2):
    """Small host-device mesh for tests (requires the XLA host-device flag)."""
    return _mesh((n_data, n_model), ("data", "model"))


def data_parallel_size(mesh) -> int:
    """Product of the cluster-carrying axes ('pod' x 'data')."""
    n = mesh.shape.get("data", 1)
    return n * mesh.shape.get("pod", 1)
