"""Train-step factory + the host-side training loop.

``make_train_step`` builds a single jit-compiled function:
    (params, opt_state, batch) -> (params, opt_state, metrics)
with remat (scan-over-layers checkpointing), chunked vocab-sharded loss,
AdamW with fp32 masters, and optional int8 gradient compression with
error feedback (``compress_grads="int8"``).

``TrainLoop`` drives it: data prefetch, periodic checkpointing, automatic
resume, and hooks the fault-tolerance harness uses to inject failures.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp

from repro.data.pipeline import DataConfig, batch_for_config
from repro.models import transformer as tr
from repro.models.moe import LOCAL_CTX, ShardCtx
from repro.train import checkpoint as ckpt_lib
from repro.train.optimizer import AdamWConfig, apply_updates, init_opt_state


@dataclasses.dataclass(frozen=True)
class TrainConfig:
    optimizer: AdamWConfig = AdamWConfig()
    checkpoint_dir: Optional[str] = None
    checkpoint_every: int = 200
    keep_checkpoints: int = 3
    log_every: int = 10
    compress_grads: Optional[str] = None       # None | "int8"


def make_train_step(model_cfg, train_cfg: TrainConfig,
                    ctx: ShardCtx = LOCAL_CTX,
                    donate: bool = True) -> Callable:
    opt_cfg = train_cfg.optimizer

    def loss_fn(params, batch):
        return tr.train_forward(params, batch, model_cfg, ctx)

    def step_fn(params, opt_state, batch):
        (loss, metrics), grads = jax.value_and_grad(
            loss_fn, has_aux=True)(params, batch)
        if train_cfg.compress_grads == "int8":
            from repro.distributed.compression import compress_tree_int8
            grads, comp_err = compress_tree_int8(grads)
            metrics = dict(metrics, compression_err=comp_err)
        params, opt_state, opt_metrics = apply_updates(
            opt_cfg, params, grads, opt_state)
        metrics = dict(metrics, **opt_metrics)
        return params, opt_state, metrics

    return jax.jit(step_fn, donate_argnums=(0, 1) if donate else ())


@dataclasses.dataclass
class TrainLoop:
    model_cfg: Any
    data_cfg: DataConfig
    train_cfg: TrainConfig
    ctx: ShardCtx = LOCAL_CTX

    def init_or_resume(self, seed: int = 0):
        params = tr.init_params(self.model_cfg, jax.random.PRNGKey(seed))
        opt_state = init_opt_state(params)
        start_step = 0
        if self.train_cfg.checkpoint_dir:
            try:
                step, tree, _ = ckpt_lib.restore(
                    self.train_cfg.checkpoint_dir,
                    {"params": params, "opt": opt_state})
                params, opt_state = tree["params"], tree["opt"]
                params = jax.tree.map(jnp.asarray, params)
                opt_state = jax.tree.map(jnp.asarray, opt_state)
                start_step = step
            except FileNotFoundError:
                pass
        return params, opt_state, start_step

    def run(self, num_steps: int, seed: int = 0,
            on_step: Optional[Callable] = None):
        """Train for num_steps (resuming if a checkpoint exists).

        Returns (params, opt_state, history list of metric dicts).
        """
        params, opt_state, start = self.init_or_resume(seed)
        step_fn = make_train_step(self.model_cfg, self.train_cfg, self.ctx)
        history = []
        t0 = time.perf_counter()
        for step in range(start, start + num_steps):
            batch = batch_for_config(self.model_cfg, self.data_cfg, step)
            batch = {k: jnp.asarray(v) for k, v in batch.items()}
            params, opt_state, metrics = step_fn(params, opt_state, batch)
            if on_step is not None:
                on_step(step, params, opt_state, metrics)
            if (step + 1) % self.train_cfg.log_every == 0 or step == start:
                m = {k: float(v) for k, v in metrics.items()}
                m["step"] = step
                m["wall_s"] = time.perf_counter() - t0
                history.append(m)
            if (self.train_cfg.checkpoint_dir
                    and (step + 1) % self.train_cfg.checkpoint_every == 0):
                ckpt_lib.save(self.train_cfg.checkpoint_dir, step + 1,
                              {"params": params, "opt": opt_state},
                              metadata={"model": self.model_cfg.name})
                ckpt_lib.prune_old(self.train_cfg.checkpoint_dir,
                                   self.train_cfg.keep_checkpoints)
        return params, opt_state, history
