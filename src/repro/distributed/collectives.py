"""Explicit collective patterns (shard_map + lax collectives).

``ring_all_gather`` is the overlap-friendly building block: each of the
N-1 steps moves one shard to the ring neighbor via collective-permute,
so a consumer that needs the gathered tensor shard-by-shard (e.g. a
TP matmul against a weight panel) can overlap compute with the next hop —
the schedule the §Perf collective analysis assumes for the TP psums.

``reduce_scatter_then_gather`` decomposes an all-reduce into its two
phases explicitly (what GSPMD does internally for ZeRO); useful when the
intermediate (scattered) value is what you actually want to keep.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P


def ring_all_gather(x, axis_name: str):
    """Inside shard_map: gather shards over `axis_name` with N-1
    collective-permutes (ring schedule).  x: (chunk, ...) local shard.
    Returns (N*chunk, ...) — bitwise equal to jax.lax.all_gather."""
    n = jax.lax.axis_size(axis_name)
    idx = jax.lax.axis_index(axis_name)
    perm = [(i, (i + 1) % n) for i in range(n)]
    pieces = [x]
    cur = x
    for _ in range(n - 1):
        cur = jax.lax.ppermute(cur, axis_name, perm)
        pieces.append(cur)
    # piece j arrived from shard (idx - j) mod n; roll into rank order
    stacked = jnp.stack(pieces)                       # (n, chunk, ...)
    order = jnp.mod(idx - jnp.arange(n), n)
    inv = jnp.argsort(order)
    return jnp.reshape(jnp.take(stacked, inv, axis=0),
                       (n * x.shape[0],) + x.shape[1:])


def reduce_scatter_then_gather(x, axis_name: str):
    """all_reduce(x) == all_gather(reduce_scatter(x)); explicit phases."""
    n = jax.lax.axis_size(axis_name)
    assert x.shape[0] % n == 0
    scattered = jax.lax.psum_scatter(x, axis_name, scatter_dimension=0,
                                     tiled=True)
    return jax.lax.all_gather(scattered, axis_name, axis=0, tiled=True)


def make_ring_all_gather(mesh, axis_name: str):
    """jit-able global-array wrapper around ring_all_gather."""
    def fn(x):
        body = lambda s: ring_all_gather(s, axis_name)
        return jax.shard_map(
            body, mesh=mesh,
            in_specs=P(axis_name), out_specs=P(), check_vma=False)(x)
    return jax.jit(fn)
