"""Mixture-of-Experts layer: top-k token-choice routing, capacity dispatch.

Two partitioning strategies, both expressed with an explicit ``shard_map``
so the dispatch scatter/gather never relies on GSPMD guessing:

  * ``tp`` — TP-within-expert: every shard holds all experts with the expert
    hidden width ``d_ff`` sliced over the model axis.  Works for ANY expert
    count (granite's 40 experts are not divisible by a 16-way axis).
  * ``ep`` — expert-parallel: experts sliced over the model axis; tokens are
    replicated across it (they are sharded over data axes only), each shard
    computes only the tokens routed to its local experts, and a single
    psum over the model axis combines per-token contributions.  Requires
    ``num_experts % model_axis_size == 0`` (olmoe: 64 % 16 == 0).

In both modes the only collective is one psum of the (tokens, d_model)
output over the model axis — identical in shape to the dense-TP FFN psum,
so MoE does not change the collective roofline term vs. dense TP.

Dispatch uses the capacity trick: scatter into an (E, C+1, d) buffer where
row C is the overflow sink for capacity-dropped tokens, then slice it off.
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.models.common import dense_init, pdtype, split_keys


@dataclasses.dataclass(frozen=True)
class ShardCtx:
    """How model code should shard itself.  mesh=None => single-device."""
    mesh: Optional[object] = None          # jax.sharding.Mesh
    data_axes: Tuple[str, ...] = ("data",)
    model_axis: Optional[str] = "model"

    @property
    def model_size(self) -> int:
        if self.mesh is None or self.model_axis is None:
            return 1
        return self.mesh.shape[self.model_axis]


LOCAL_CTX = ShardCtx(mesh=None, data_axes=(), model_axis=None)


def init_moe(key, cfg):
    m = cfg.moe
    dt = pdtype(cfg)
    d, f, E = cfg.d_model, m.d_ff, m.num_experts
    ks = split_keys(key, 4)
    p = {"router": dense_init(ks[0], (d, E), jnp.float32)}
    if cfg.activation == "swiglu":
        p["w_gate"] = dense_init(ks[1], (E, d, f), dt, fan_in=d)
        p["w_up"] = dense_init(ks[2], (E, d, f), dt, fan_in=d)
    else:
        p["w_in"] = dense_init(ks[1], (E, d, f), dt, fan_in=d)
    p["w_down"] = dense_init(ks[3], (E, f, d), dt, fan_in=f)
    return p


def _activation(h, kind):
    hf = h.astype(jnp.float32)
    if kind == "relu2":
        return jnp.square(jax.nn.relu(hf)).astype(h.dtype)
    return jax.nn.gelu(hf).astype(h.dtype)


def _expert_ffn(p, buf, activation):
    """buf: (E, C, d) -> (E, C, d) through each expert's FFN."""
    if "w_gate" in p:
        g = jnp.einsum("ecd,edf->ecf", buf, p["w_gate"])
        u = jnp.einsum("ecd,edf->ecf", buf, p["w_up"])
        h = jax.nn.silu(g.astype(jnp.float32)).astype(buf.dtype) * u
    else:
        h = _activation(jnp.einsum("ecd,edf->ecf", buf, p["w_in"]), activation)
    return jnp.einsum("ecf,efd->ecd", h, p["w_down"])


def _route(x2d, router_w, top_k):
    """x2d (T, d) -> gates (T,k) fp32, ids (T,k) int32, aux losses."""
    logits = jnp.einsum("td,de->te", x2d.astype(jnp.float32), router_w)
    probs = jax.nn.softmax(logits, axis=-1)
    gates, ids = jax.lax.top_k(probs, top_k)
    gates = gates / jnp.maximum(jnp.sum(gates, -1, keepdims=True), 1e-9)
    # switch-style load-balance loss + router z-loss
    E = router_w.shape[-1]
    frac_prob = jnp.mean(probs, axis=0)                              # (E,)
    frac_tok = jnp.mean(
        jax.nn.one_hot(ids[:, 0], E, dtype=jnp.float32), axis=0)
    aux = {
        "load_balance": E * jnp.sum(frac_prob * frac_tok),
        "router_z": jnp.mean(jnp.square(jax.nn.logsumexp(logits, axis=-1))),
    }
    return gates, ids, aux


def _dispatch_compute_combine(p, x2d, gates, ids, capacity, activation,
                              expert_offset=0, n_local_experts=None):
    """Scatter tokens to (E_local, C(+1 overflow), d), run FFNs, gather back.

    expert_offset / n_local_experts implement the EP mode: choices routed to
    experts outside [offset, offset+n_local) are sent to the overflow row.
    """
    T, d = x2d.shape
    k = ids.shape[1]
    E = p["w_down"].shape[0]  # local expert count
    n_local = n_local_experts or E
    flat_ids = ids.reshape(-1) - expert_offset                       # (T*k,)
    local = (flat_ids >= 0) & (flat_ids < n_local)
    flat_ids_c = jnp.clip(flat_ids, 0, n_local - 1)
    # position of each (token, choice) within its expert queue
    oh = jax.nn.one_hot(flat_ids_c, n_local, dtype=jnp.int32) * local[:, None].astype(jnp.int32)
    pos = jnp.take_along_axis(jnp.cumsum(oh, axis=0) - 1, flat_ids_c[:, None], axis=1)[:, 0]
    keep = local & (pos >= 0) & (pos < capacity)
    slot = jnp.where(keep, pos, capacity)                            # overflow row C
    x_rep = jnp.repeat(x2d, k, axis=0)                               # (T*k, d)
    buf = jnp.zeros((n_local, capacity + 1, d), x2d.dtype)
    buf = buf.at[flat_ids_c, slot].set(x_rep, mode="drop")
    out_buf = _expert_ffn(p, buf[:, :capacity], activation)          # (E, C, d)
    out_buf = jnp.pad(out_buf, ((0, 0), (0, 1), (0, 0)))             # overflow row -> 0
    y_rep = out_buf[flat_ids_c, jnp.minimum(slot, capacity)]         # (T*k, d)
    y_rep = y_rep * keep[:, None].astype(y_rep.dtype)
    w = gates.reshape(-1).astype(y_rep.dtype)
    return jnp.sum((y_rep * w[:, None]).reshape(T, k, d), axis=1)


def _capacity(n_tokens: int, top_k: int, n_experts: int, factor: float) -> int:
    return max(1, int(n_tokens * top_k / n_experts * factor + 0.999))


def apply_moe(p, x, cfg, ctx: ShardCtx = LOCAL_CTX):
    """x: (B, S, d) -> (y (B,S,d), aux dict of scalars)."""
    m = cfg.moe
    B, S, d = x.shape
    mdl_size = ctx.model_size

    def body(p_l, x_l):
        Bl, Sl, _ = x_l.shape
        x2d = x_l.reshape(Bl * Sl, d)
        # TPU path: the dispatch scatter/gather + position bookkeeping are
        # a megablox-style grouped-matmul kernel; the (T,E) one-hot /
        # cumsum and the capacity-padded (E,C,d) buffers stay in VMEM.
        with jax.named_scope("moe_dispatch"):
            gates, ids, aux = _route(x2d, p_l["router"], m.top_k)
            if m.partitioning == "ep" and mdl_size > 1:
                n_local = m.num_experts // mdl_size
                idx = jax.lax.axis_index(ctx.model_axis)
                cap = _capacity(Bl * Sl, m.top_k, m.num_experts,
                                m.capacity_factor)
                y = _dispatch_compute_combine(
                    p_l, x2d, gates, ids, cap, cfg.activation,
                    expert_offset=idx * n_local, n_local_experts=n_local)
            else:
                cap = _capacity(Bl * Sl, m.top_k, m.num_experts,
                                m.capacity_factor)
                y = _dispatch_compute_combine(p_l, x2d, gates, ids, cap,
                                              cfg.activation)
        if ctx.mesh is not None and ctx.model_axis is not None:
            # tp: partial sums over f slices; ep: per-token expert contributions
            y = jax.lax.psum(y, ctx.model_axis)
        return y.reshape(Bl, Sl, d), aux

    if ctx.mesh is None:
        return body(p, x)

    x_spec = P(ctx.data_axes, None, None)
    if m.partitioning == "ep" and mdl_size > 1:
        w_spec = P(ctx.model_axis, None, None)
    else:
        w_spec = P(None, None, ctx.model_axis)
    p_specs = {}
    for name in p:
        if name == "router":
            p_specs[name] = P(None, None)
        elif name == "w_down":
            p_specs[name] = (P(ctx.model_axis, None, None)
                             if m.partitioning == "ep" and mdl_size > 1
                             else P(None, ctx.model_axis, None))
        else:
            p_specs[name] = w_spec
    aux_spec = {"load_balance": P(), "router_z": P()}

    fn = jax.shard_map(
        body, mesh=ctx.mesh,
        in_specs=(p_specs, x_spec),
        out_specs=(x_spec, aux_spec),
        check_vma=False,
    )
    return fn(p, x)
