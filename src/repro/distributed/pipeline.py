"""GPipe-style pipeline parallelism over a mesh axis.

The multi-pod mesh's "pod" axis can host pipeline stages instead of outer
data parallelism when a model's layers do not fit one pod's HBM even with
TP=16 (the 1000+-node deployment case).  This module implements the
schedule with explicit shard_map + collective-permute:

  * stage s holds layer groups [s*G/S, (s+1)*G/S) (params sharded over the
    stage axis on their group dim);
  * M microbatches flow through S stages in M+S-1 ticks; each tick every
    stage processes one microbatch (or a masked bubble) and ppermutes its
    activation to the next stage;
  * outputs are collected on the last stage and all-gathered.

Bubble fraction = (S-1)/(M+S-1) — reported by ``bubble_fraction`` so the
launcher can size M.  Forward-only (serving / the paper's cloud side);
training PP would add the 1F1B backward schedule on the same skeleton.
"""
from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P


def bubble_fraction(n_micro: int, n_stages: int) -> float:
    return (n_stages - 1) / (n_micro + n_stages - 1)


def gpipe_forward(stage_fn: Callable, stage_params, micro_x, *,
                  mesh, axis_name: str):
    """Run microbatches through pipeline stages.

    stage_fn(params_local, x) -> y        (one stage's compute; shapes of
                                           x and y must match)
    stage_params: pytree with leading dim = n_stages (sharded over axis)
    micro_x: (M, micro_batch, ...) inputs (replicated over the axis)
    Returns (M, micro_batch, ...) outputs (replicated over the axis).
    """
    n_stages = mesh.shape[axis_name]
    M = micro_x.shape[0]

    def body(params_stage, xs):
        # params_stage: leading dim 1 (this stage's slice); xs: (M, b, ...)
        p_local = jax.tree.map(lambda a: a[0], params_stage)
        idx = jax.lax.axis_index(axis_name)
        S = jax.lax.axis_size(axis_name)
        perm = [(i, i + 1) for i in range(n_stages - 1)]

        buf = jnp.zeros_like(xs[0])                 # current stage input
        outs = jnp.zeros_like(xs)                   # collected on last stage

        def tick(t, carry):
            buf, outs = carry
            mb = t - idx                            # microbatch at this stage
            active = jnp.logical_and(mb >= 0, mb < M)
            # stage 0 ingests microbatch t from the global input
            inject = jnp.logical_and(idx == 0, jnp.logical_and(t >= 0, t < M))
            x_in = jnp.where(inject,
                             jax.lax.dynamic_index_in_dim(
                                 xs, jnp.clip(t, 0, M - 1), keepdims=False),
                             buf)
            y = stage_fn(p_local, x_in)
            y = jnp.where(active, y, x_in)          # bubbles pass through
            # last stage writes its finished microbatch
            done = jnp.logical_and(idx == S - 1, active)
            outs = jax.lax.cond(
                done,
                lambda o: jax.lax.dynamic_update_index_in_dim(
                    o, y, jnp.clip(mb, 0, M - 1), 0),
                lambda o: o,
                outs)
            # hand activations to the next stage
            buf_next = jax.lax.ppermute(y, axis_name, perm)
            return buf_next, outs

        buf, outs = jax.lax.fori_loop(0, M + n_stages - 1, tick, (buf, outs))
        # broadcast the last stage's collected outputs to every stage
        outs = jax.lax.psum(
            jnp.where(idx == S - 1, outs, jnp.zeros_like(outs)), axis_name)
        return outs

    pspec = jax.tree.map(lambda _: P(axis_name), stage_params)
    fn = jax.shard_map(
        body, mesh=mesh,
        in_specs=(pspec, P()), out_specs=P(), check_vma=False)
    return fn(stage_params, micro_x)
