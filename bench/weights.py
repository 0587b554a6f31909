"""Random weights for a program's parameter tree, drawn on the device in
one jitted call from the seed.

Each leaf gets a rule: ``ones``, ``zeros``, ``normal`` (times a scale)
or ``clipped`` (a normal clipped at two sigma, times a scale).  The
normals come from a counter hash (two rounds of a 32-bit mixer, then
Box-Muller) rather than ``jax.random``: the same values for the same
seed, at a tenth of threefry's compile time for the 633 leaves of
sd-v1 (12 s against 128 s on the host's CPU), which a cold run pays.
One program per distinct leaf shape is traced and reused.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

_GOLDEN = 0x9E3779B9


def _mix(x):
    x = x ^ (x >> 16)
    x = x * jnp.uint32(0x7FEB352D)
    x = x ^ (x >> 15)
    x = x * jnp.uint32(0x846CA68B)
    return x ^ (x >> 16)


@partial(jax.jit, static_argnums=1)
def _normal(base, shape):
    """Standard normals of ``shape`` from the 32-bit stream ``base``."""
    n = int(np.prod(shape))
    i = jax.lax.iota(jnp.uint32, n) * jnp.uint32(2)
    a = _mix(i ^ base)
    b = _mix((i + jnp.uint32(1)) ^ base)
    u1 = (a >> 8).astype(jnp.float32) * (1.0 / (1 << 24)) + 0.5 / (1 << 24)
    u2 = (b >> 8).astype(jnp.float32) * (1.0 / (1 << 24))
    z = jnp.sqrt(-2.0 * jnp.log(u1)) * jnp.cos(2.0 * np.pi * u2)
    return z.reshape(shape)


def draw(shapes, rule, seed: int):
    """A tree like ``shapes`` (of ShapeDtypeStructs) with the values
    ``rule(path, shape) -> (kind, scale)`` asks for, on the device."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(shapes)
    specs = [(*rule(p, s.shape), s.shape, s.dtype) for p, s in flat]

    def gen(seed):
        out = []
        for i, (kind, scale, shape, dtype) in enumerate(specs):
            if kind == "ones":
                out.append(jnp.ones(shape, dtype))
            elif kind == "zeros":
                out.append(jnp.zeros(shape, dtype))
            else:
                base = _mix(seed + jnp.uint32(i) * jnp.uint32(_GOLDEN))
                x = _normal(base, tuple(shape))
                if kind == "clipped":
                    x = jnp.clip(x, -2.0, 2.0)
                out.append((x * scale).astype(dtype))
        return jax.tree_util.tree_unflatten(treedef, out)

    params = jax.jit(gen)(jnp.uint32(seed & 0xFFFFFFFF))
    jax.block_until_ready(params)
    return params
