"""Production meshes. A FUNCTION (not a module constant) so importing never
touches jax device state — the dry-run must set XLA_FLAGS first."""
from __future__ import annotations

import os

import jax


def _axis_types_kw(ndim: int) -> dict:
    return {"axis_types": (jax.sharding.AxisType.Auto,) * ndim}


def make_production_mesh(*, multi_pod: bool = False):
    """16×16 = 256 chips per pod; 2 pods = 512 chips multi-pod.

    REPRO_MESH_SHAPE ("d,m" or "p,d,m") overrides for reduced-device test
    runs of the same code path (tests use 8 virtual CPU devices).
    """
    override = os.environ.get("REPRO_MESH_SHAPE")
    if override:
        dims = tuple(int(x) for x in override.split(","))
        if multi_pod and len(dims) == 2:
            dims = (2,) + dims
        if not multi_pod and len(dims) == 3:
            dims = dims[1:]
    else:
        dims = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model")[-len(dims):]
    return jax.make_mesh(dims, axes, **_axis_types_kw(len(dims)))


def make_host_mesh(model_parallel: int = 1):
    """Whatever this host has (tests / examples): (n_dev/mp, mp)."""
    n = len(jax.devices())
    mp = max(1, min(model_parallel, n))
    return jax.make_mesh((n // mp, mp), ("data", "model"),
                         **_axis_types_kw(2))
