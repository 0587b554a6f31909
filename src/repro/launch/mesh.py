"""Production meshes.

Single pod: 16 x 16 = 256 chips, axes ("data", "model").
Multi-pod:  2 x 16 x 16 = 512 chips, axes ("pod", "data", "model") — "pod"
is an outer data-parallel axis (gradient all-reduce spans pod x data; the
serving engine treats pods as replica groups behind one scheduler).

Functions, not module constants: importing this module never touches jax
device state (the dry-run sets XLA_FLAGS before first jax init).
"""
from __future__ import annotations

import jax


def make_mesh(axis_shapes, axis_names):
    """``jax.make_mesh`` with every axis Auto (the sharding rules here
    are written for Auto axes; jax's default is Explicit)."""
    return jax.make_mesh(
        tuple(axis_shapes), tuple(axis_names),
        axis_types=(jax.sharding.AxisType.Auto,) * len(axis_names))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_host_mesh(data: int = 1, model: int = 1):
    """Mesh over however many devices this host actually has (tests)."""
    n = len(jax.devices())
    data = min(data, n)
    model = max(1, min(model, n // max(1, data)))
    return make_mesh((data, model), ("data", "model"))


def make_elastic_mesh(pods: int, data: int, model: int):
    """Rebuild a mesh after failures (fault_tolerance.ElasticPlan)."""
    if pods > 1:
        return make_mesh((pods, data, model), ("pod", "data", "model"))
    return make_mesh((data, model), ("data", "model"))
