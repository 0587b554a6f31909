"""Decoder-only LM (plus the shared block machinery used by encdec.py).

Design notes
------------
* **scan-over-layers**: block params are stacked over "pattern groups"
  (``cfg.block_pattern`` tiled), so HLO size is O(1) in depth and compile
  times stay flat for 32k-seq x 512-device dry-runs.  The remainder layers
  (``cfg.tail_pattern()``) are unrolled.
* **three entry points** per model: ``train_forward`` (full-seq, loss),
  ``prefill`` (full-seq, returns caches), ``decode_step`` (one token).
* **layer-range execution** (``run_layer_range``) is the paper's
  segmentation hook: the cloud runs groups ``[0, g)``, ships the hidden
  state + boundary cache/recurrent state, the device runs ``[g, G)``.
  Split indices are static => one compiled executable per split group,
  which is exactly the paper's n_step quantization argument.
* **memory-safe paths**: chunked online-softmax attention for long
  sequences; sequence-chunked vocab-sharded cross entropy (never
  materializes (B, S, V) logits).
"""
from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.models import attention as attn_lib
from repro.models import moe as moe_lib
from repro.models import rglru as rglru_lib
from repro.models import ssd as ssd_lib
from repro.models.common import (
    apply_norm,
    apply_rope,
    dense_init,
    embed_init,
    init_norm,
    pdtype,
    split_keys,
)
from repro.models.mlp import apply_mlp, init_mlp
from repro.models.moe import LOCAL_CTX, ShardCtx

Params = Dict[str, Any]


# ==========================================================================
# Block init
# ==========================================================================
def init_attn_block(key, cfg, cross: bool = False) -> Params:
    d = cfg.d_model
    hd = cfg.resolved_head_dim()
    dt = pdtype(cfg)
    ks = split_keys(key, 12)
    p: Params = {
        "norm1": init_norm(cfg, d),
        "wq": dense_init(ks[0], (d, cfg.num_heads, hd), dt, fan_in=d),
        "wk": dense_init(ks[1], (d, cfg.num_kv_heads, hd), dt, fan_in=d),
        "wv": dense_init(ks[2], (d, cfg.num_kv_heads, hd), dt, fan_in=d),
        "wo": dense_init(ks[3], (cfg.num_heads, hd, d), dt, fan_in=cfg.num_heads * hd),
        "norm2": init_norm(cfg, d),
    }
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((cfg.num_heads, hd), jnp.float32)
        p["bk"] = jnp.zeros((cfg.num_kv_heads, hd), jnp.float32)
        p["bv"] = jnp.zeros((cfg.num_kv_heads, hd), jnp.float32)
    if cross:
        p["xnorm"] = init_norm(cfg, d)
        p["xwq"] = dense_init(ks[4], (d, cfg.num_heads, hd), dt, fan_in=d)
        p["xwk"] = dense_init(ks[5], (d, cfg.num_kv_heads, hd), dt, fan_in=d)
        p["xwv"] = dense_init(ks[6], (d, cfg.num_kv_heads, hd), dt, fan_in=d)
        p["xwo"] = dense_init(ks[7], (cfg.num_heads, hd, d), dt,
                              fan_in=cfg.num_heads * hd)
    if cfg.moe is not None:
        p["moe"] = moe_lib.init_moe(ks[8], cfg)
    else:
        p["mlp"] = init_mlp(ks[9], cfg)
    return p


def init_block(kind: str, key, cfg, cross: bool = False) -> Params:
    if kind == "attn":
        return init_attn_block(key, cfg, cross=cross)
    if kind == "rec":
        k1, k2, k3 = split_keys(key, 3)
        return {
            "norm1": init_norm(cfg, cfg.d_model),
            "rglru": rglru_lib.init_rglru_block(k1, cfg),
            "norm2": init_norm(cfg, cfg.d_model),
            "mlp": init_mlp(k2, cfg),
        }
    if kind == "ssd":
        k1, _ = split_keys(key, 2)
        return {
            "norm1": init_norm(cfg, cfg.d_model),
            "ssd": ssd_lib.init_ssd_block(k1, cfg),
        }
    raise ValueError(kind)


def init_params(cfg, key) -> Params:
    ks = split_keys(key, 8)
    G = cfg.num_groups()
    pattern = cfg.block_pattern
    cross = cfg.encoder_layers > 0

    def stack_init(kind, key):
        keys = jnp.stack(split_keys(key, G))
        return jax.vmap(lambda k: init_block(kind, k, cfg, cross=cross))(keys)

    blocks = {
        f"b{i}": stack_init(kind, jax.random.fold_in(ks[0], i))
        for i, kind in enumerate(pattern)
    }
    tail = {
        f"t{i}": init_block(kind, jax.random.fold_in(ks[1], i), cfg, cross=cross)
        for i, kind in enumerate(cfg.tail_pattern())
    }
    params: Params = {
        "embed": embed_init(ks[2], (cfg.padded_vocab(), cfg.d_model),
                            pdtype(cfg)),
        "blocks": blocks,
        "final_norm": init_norm(cfg, cfg.d_model),
    }
    if tail:
        params["tail"] = tail
    if not cfg.tie_embeddings:
        params["lm_head"] = dense_init(
            ks[3], (cfg.d_model, cfg.padded_vocab()), pdtype(cfg))
    if cfg.encoder_layers:
        params["encoder"] = init_encoder(ks[4], cfg)
    if cfg.frontend is not None and cfg.frontend.embed_dim != cfg.d_model:
        params["frontend_proj"] = dense_init(
            ks[5], (cfg.frontend.embed_dim, cfg.d_model), pdtype(cfg))
    return params


def init_encoder(key, cfg) -> Params:
    ks = split_keys(key, 2)
    E = cfg.encoder_layers
    keys = jnp.stack(split_keys(ks[0], E))
    blocks = jax.vmap(lambda k: init_attn_block(k, cfg, cross=False))(keys)
    return {"blocks": blocks, "final_norm": init_norm(cfg, cfg.d_model)}


# ==========================================================================
# Block apply — full-sequence mode (train / prefill)
# ==========================================================================
def _attn_sharded(t, ctx, kind):
    """Pin (B, S, H, D) attention activations.

    Without pinning, GSPMD may partition the flash-attention score dot
    over its *contracting* head_dim (when H doesn't divide the model
    axis), inserting an all-reduce of the full score tensor on EVERY kv
    chunk — observed at ~7.5 GB/chunk on qwen2.

    Policy:
      * heads divisible by the model axis  -> shard heads (classic TP);
      * otherwise -> context parallelism: q and the attention output are
        sharded over the SEQUENCE dim; k/v are replicated across the
        model axis (cheap: only the GQA kv heads are gathered).  Each
        model shard computes its own query rows against the full context;
        the flash scan then contains no collectives at all.
    """
    if ctx is None or ctx.mesh is None:
        return t
    from jax.sharding import NamedSharding, PartitionSpec as P
    mesh = ctx.mesh
    dsize = 1
    for a in ctx.data_axes:
        dsize *= mesh.shape[a]
    b_axis = (ctx.data_axes if len(ctx.data_axes) > 1 else ctx.data_axes[0]) \
        if (ctx.data_axes and t.shape[0] % dsize == 0) else None
    m = ctx.model_axis
    msize = mesh.shape[m] if m else 1
    if m and t.shape[2] % msize == 0:
        spec = P(b_axis, None, m, None)                  # head TP
    elif m and kind in ("q", "out") and t.shape[1] % msize == 0:
        spec = P(b_axis, m, None, None)                  # context parallel
    else:
        spec = P(b_axis, None, None, None)               # replicate (kv)
    return jax.lax.with_sharding_constraint(t, NamedSharding(mesh, spec))


def _hidden_replicated(x, ctx):
    """Pin (B, S, d) hidden states to (data, None, None) at TP matmul
    entries.  After context-parallel attention x is sequence-sharded; if
    left that way GSPMD prefers ALL-GATHERING THE TP WEIGHTS (e.g. qwen2's
    (3584, 18944) MLP weight, 243 GB/step measured) over re-gathering the
    58 MB activation.  This constraint forces the cheap gather."""
    if ctx is None or ctx.mesh is None:
        return x
    from jax.sharding import NamedSharding, PartitionSpec as P
    mesh = ctx.mesh
    dsize = 1
    for a in ctx.data_axes:
        dsize *= mesh.shape[a]
    b_axis = (ctx.data_axes if len(ctx.data_axes) > 1 else ctx.data_axes[0]) \
        if (ctx.data_axes and x.shape[0] % dsize == 0) else None
    return jax.lax.with_sharding_constraint(
        x, NamedSharding(mesh, P(b_axis, None, None)))


def _qkv(p, h, cfg, positions, ctx=None):
    q = jnp.einsum("bsd,dhe->bshe", h, p["wq"])
    k = jnp.einsum("bsd,dhe->bshe", h, p["wk"])
    v = jnp.einsum("bsd,dhe->bshe", h, p["wv"])
    if "bq" in p:
        q = q + p["bq"].astype(q.dtype)
        k = k + p["bk"].astype(k.dtype)
        v = v + p["bv"].astype(v.dtype)
    q = apply_rope(q, positions, cfg.rope_theta)
    k = apply_rope(k, positions, cfg.rope_theta)
    q = _attn_sharded(q, ctx, "q")
    k = _attn_sharded(k, ctx, "kv")
    v = _attn_sharded(v, ctx, "kv")
    return q, k, v


def apply_attn_block_seq(p, x, cfg, ctx, *, positions, causal=True,
                         enc_out=None, return_kv=False, attn_kernel=None):
    """Full-sequence attention block.  Returns (x, aux, kv | None).

    Self-attention (norm, q/k/v, RoPE, attention, output projection,
    residual) carries the named scope ``self_attn`` and the MLP (norm,
    MLP or MoE, residual) ``mlp``, by which a device trace puts the
    block's time down to its parts.  ``attn_kernel`` is the flash
    kernel hook of ``attention.self_attention`` (forward only)."""
    with jax.named_scope("self_attn"):
        h = apply_norm(p["norm1"], x, cfg.norm_eps)
        q, k, v = _qkv(p, h, cfg, positions, ctx)
        window = cfg.window if cfg.attention_kind == "swa" else 0
        # positions here are always arange(S): the flash path applies
        o = attn_lib.self_attention(q, k, v, causal=causal, window=window,
                                    kernel=attn_kernel)
        o = _attn_sharded(o, ctx, "out")
        x = x + jnp.einsum("bshe,hed->bsd", o, p["wo"])
        x = _hidden_replicated(x, ctx)
    if "xwq" in p and enc_out is not None:
        hx = apply_norm(p["xnorm"], x, cfg.norm_eps)
        xq = jnp.einsum("bsd,dhe->bshe", hx, p["xwq"])
        xk = jnp.einsum("bsd,dhe->bshe", enc_out, p["xwk"])
        xv = jnp.einsum("bsd,dhe->bshe", enc_out, p["xwv"])
        xq = _attn_sharded(xq, ctx, "q")
        xk = _attn_sharded(xk, ctx, "kv")
        xv = _attn_sharded(xv, ctx, "kv")
        enc_pos = jnp.arange(enc_out.shape[1])
        xo = attn_lib.attend(xq, xk, xv, q_positions=positions,
                             kv_positions=enc_pos, causal=False, window=0)
        xo = _attn_sharded(xo, ctx, "out")
        x = x + jnp.einsum("bshe,hed->bsd", xo, p["xwo"])
        x = _hidden_replicated(x, ctx)
    with jax.named_scope("mlp"):
        h2 = apply_norm(p["norm2"], x, cfg.norm_eps)
        aux = None
        if "moe" in p:
            y, aux = moe_lib.apply_moe(p["moe"], h2, cfg, ctx)
        else:
            y = apply_mlp(p["mlp"], h2, cfg)
        x = x + y
    kv = {"k": k, "v": v} if return_kv else None
    return x, aux, kv


def apply_block_seq(kind, p, x, cfg, ctx, *, positions, state=None,
                    enc_out=None, return_cache=False, kernels=None):
    """Returns (x, aux, cache_out).  cache_out pytree depends on kind.
    ``kernels`` (``ops.kernel_registry()``) is the hook of the forward-only
    split path (``run_layer_range``): no Pallas kernel has a backward."""
    kernels = kernels or {}
    if kind == "attn":
        x, aux, kv = apply_attn_block_seq(
            p, x, cfg, ctx, positions=positions, enc_out=enc_out,
            return_kv=return_cache, attn_kernel=kernels.get("attn"))
        return x, aux, kv
    if kind == "rec":
        h = apply_norm(p["norm1"], x, cfg.norm_eps)
        y, new_state = rglru_lib.apply_rglru_block(
            p["rglru"], h, cfg, state=state, kernel_fn=kernels.get("rglru"))
        x = x + y
        h2 = apply_norm(p["norm2"], x, cfg.norm_eps)
        x = x + apply_mlp(p["mlp"], h2, cfg)
        return x, None, (new_state if return_cache else None)
    if kind == "ssd":
        h = apply_norm(p["norm1"], x, cfg.norm_eps)
        y, new_state = ssd_lib.apply_ssd_block(
            p["ssd"], h, cfg, state=state, kernel_fn=kernels.get("ssd"))
        x = x + y
        return x, None, (new_state if return_cache else None)
    raise ValueError(kind)


# ==========================================================================
# Embedding / unembedding
# ==========================================================================
def embed_tokens(params, tokens, cfg):
    return jnp.take(params["embed"], tokens, axis=0)


def embed_inputs(params, batch, cfg):
    """batch: {"tokens": (B,S)} (+ {"frontend": (B,P,E)} for vlm/audio).

    Frontend embeddings are prepended (they come from the STUB modality
    tower); total sequence = P + S_text.
    """
    x = embed_tokens(params, batch["tokens"], cfg)
    if cfg.frontend is not None and "frontend" in batch:
        fe = batch["frontend"]
        if "frontend_proj" in params:
            fe = jnp.einsum("bpe,ed->bpd", fe, params["frontend_proj"])
        x = jnp.concatenate([fe.astype(x.dtype), x], axis=1)
    return x


def unembed(params, h, cfg):
    w = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
    logits = jnp.einsum("bsd,dv->bsv", h, w)
    Vp = cfg.padded_vocab()
    if Vp != cfg.vocab_size:   # padded columns can never be sampled
        logits = jnp.where(jnp.arange(Vp) < cfg.vocab_size, logits,
                           jnp.asarray(-1e30, logits.dtype))
    return logits


# ==========================================================================
# Full-sequence forward (train / prefill)
# ==========================================================================
def _scan_groups(params, x, cfg, ctx, *, positions, enc_out=None,
                 return_cache=False, remat=True):
    """Run all pattern groups + tail.  Returns (x, aux_sum, caches)."""
    pattern = cfg.block_pattern
    n_aux = 2  # load_balance, router_z

    def group_body(carry, gp):
        x, aux = carry
        caches = {}
        for i, kind in enumerate(pattern):
            x, a, c = apply_block_seq(
                kind, gp[f"b{i}"], x, cfg, ctx, positions=positions,
                enc_out=enc_out, return_cache=return_cache)
            if a is not None:
                aux = aux + jnp.stack([a["load_balance"], a["router_z"]])
            if return_cache:
                caches[f"b{i}"] = c
        return (x, aux), caches if return_cache else None

    body = group_body
    if remat:
        body = jax.checkpoint(
            group_body, policy=jax.checkpoint_policies.nothing_saveable)
    aux0 = jnp.zeros((n_aux,), jnp.float32)
    (x, aux), group_caches = jax.lax.scan(body, (x, aux0), params["blocks"])

    tail_caches = {}
    for i, kind in enumerate(cfg.tail_pattern()):
        x, a, c = apply_block_seq(
            kind, params["tail"][f"t{i}"], x, cfg, ctx, positions=positions,
            enc_out=enc_out, return_cache=return_cache)
        if a is not None:
            aux = aux + jnp.stack([a["load_balance"], a["router_z"]])
        if return_cache:
            tail_caches[f"t{i}"] = c
    caches = {"groups": group_caches, "tail": tail_caches} if return_cache else None
    return x, aux, caches


def encode(params, frames, cfg, ctx):
    """Encoder stack over frontend frames (B, S_enc, d)."""
    enc = params["encoder"]
    positions = jnp.arange(frames.shape[1])

    def body(carry, bp):
        x, = carry
        x, _, _ = apply_attn_block_seq(bp, x, cfg, ctx, positions=positions,
                                       causal=False)
        return (x,), None

    body_r = jax.checkpoint(body, policy=jax.checkpoint_policies.nothing_saveable)
    (x,), _ = jax.lax.scan(body_r, (frames,), enc["blocks"])
    return apply_norm(enc["final_norm"], x, cfg.norm_eps)


def forward_hidden(params, batch, cfg, ctx: ShardCtx = LOCAL_CTX, *,
                   return_cache=False, remat=True):
    """Embed + all blocks.  Returns (hidden (B,S,d), aux (2,), caches)."""
    enc_out = None
    if cfg.encoder_layers:
        frames = batch["frontend"]
        if "frontend_proj" in params:
            frames = jnp.einsum("bpe,ed->bpd", frames, params["frontend_proj"])
        enc_out = encode(params, frames.astype(pdtype(cfg)), cfg, ctx)
        x = embed_tokens(params, batch["tokens"], cfg)
    else:
        x = embed_inputs(params, batch, cfg)
    positions = jnp.arange(x.shape[1])
    x, aux, caches = _scan_groups(
        params, x, cfg, ctx, positions=positions, enc_out=enc_out,
        return_cache=return_cache, remat=remat)
    x = apply_norm(params["final_norm"], x, cfg.norm_eps)
    if return_cache and enc_out is not None:
        caches["enc_out"] = enc_out
    return x, aux, caches


# ==========================================================================
# Loss: sequence-chunked, vocab-sharded cross entropy
# ==========================================================================
def lm_loss(params, hidden, targets, mask, cfg, *, chunk: int = 512,
            z_weight: float = 1e-4):
    """hidden (B,S,d) -> scalar mean NLL (+ z-loss).  Never builds (B,S,V)."""
    B, S, _ = hidden.shape
    chunk = min(chunk, S)
    n = S // chunk
    Sc = n * chunk
    w = params["embed"].T if cfg.tie_embeddings else params["lm_head"]

    Vp = cfg.padded_vocab()

    def chunk_loss(h_c, t_c, m_c):
        logits = jnp.einsum("bsd,dv->bsv", h_c, w).astype(jnp.float32)
        if Vp != cfg.vocab_size:   # mask padded vocab columns out of the lse
            pad_mask = jnp.arange(Vp) < cfg.vocab_size
            logits = jnp.where(pad_mask, logits, -1e30)
        lse = jax.nn.logsumexp(logits, axis=-1)
        tgt = jnp.sum(
            logits * jax.nn.one_hot(t_c, Vp, dtype=jnp.float32),
            axis=-1)
        nll = (lse - tgt) * m_c
        zl = jnp.square(lse) * m_c
        return jnp.sum(nll) + z_weight * jnp.sum(zl)

    chunk_loss = jax.checkpoint(chunk_loss)

    def body(acc, xs):
        h_c, t_c, m_c = xs
        return acc + chunk_loss(h_c, t_c, m_c), None

    hs = hidden[:, :Sc].reshape(B, n, chunk, -1).transpose(1, 0, 2, 3)
    ts = targets[:, :Sc].reshape(B, n, chunk).transpose(1, 0, 2)
    ms = mask[:, :Sc].reshape(B, n, chunk).transpose(1, 0, 2).astype(jnp.float32)
    total, _ = jax.lax.scan(body, jnp.zeros((), jnp.float32), (hs, ts, ms))
    if Sc < S:
        total = total + chunk_loss(hidden[:, Sc:], targets[:, Sc:],
                                   mask[:, Sc:].astype(jnp.float32))
    denom = jnp.maximum(jnp.sum(mask.astype(jnp.float32)), 1.0)
    return total / denom


def train_forward(params, batch, cfg, ctx: ShardCtx = LOCAL_CTX):
    """batch: tokens (B,S), labels (B,S), mask (B,S) [+ frontend].

    Returns (loss, metrics dict).
    """
    hidden, aux, _ = forward_hidden(params, batch, cfg, ctx)
    loss = lm_loss(params, hidden, batch["labels"], batch["mask"], cfg)
    metrics = {"nll": loss}
    if cfg.moe is not None:
        lb, rz = aux[0], aux[1]
        n_moe = cfg.num_layers
        loss = loss + (cfg.moe.router_aux_weight * lb
                       + cfg.moe.router_z_weight * rz) / n_moe
        metrics.update({"load_balance": lb / n_moe, "router_z": rz / n_moe})
    metrics["loss"] = loss
    return loss, metrics


# ==========================================================================
# Decode: caches & single-token step
# ==========================================================================
def init_decode_cache(cfg, batch: int, max_len: int):
    """Cache pytree aligned with the scan structure."""
    hd = cfg.resolved_head_dim()
    kv_len = cfg.effective_kv_len(max_len)
    dt = pdtype(cfg)

    def one(kind):
        if kind == "attn":
            return attn_lib.init_kv_cache(
                batch, kv_len, cfg.num_kv_heads, hd, dt,
                quantized=cfg.kv_cache_dtype == "int8")
        if kind == "rec":
            return rglru_lib.init_rglru_state(batch, cfg)
        if kind == "ssd":
            return ssd_lib.init_ssd_state(batch, cfg)
        raise ValueError(kind)

    G = cfg.num_groups()
    groups = {
        f"b{i}": jax.tree.map(
            lambda a: jnp.broadcast_to(a, (G,) + a.shape), one(kind))
        for i, kind in enumerate(cfg.block_pattern)
    }
    tail = {f"t{i}": one(kind) for i, kind in enumerate(cfg.tail_pattern())}
    return {"groups": groups, "tail": tail}


def _decode_attn(p, x, cfg, cache, position, enc_kv=None):
    """One-token attention block.  x (B,1,d)."""
    h = apply_norm(p["norm1"], x, cfg.norm_eps)
    pos1 = position[None] if position.ndim == 0 else position
    q, k, v = _qkv(p, h, cfg, pos1)
    swa = cfg.attention_kind == "swa" and cfg.window
    if swa and cache["k"].shape[1] == cfg.window:
        cache = attn_lib.cache_update_ring(cache, k, v, position)
        kv_pos, kv_val = attn_lib.ring_positions(cfg.window, position)
    else:
        cache = attn_lib.cache_update_linear(cache, k, v, position)
        kv_pos = jnp.arange(cache["k"].shape[1])
        kv_val = kv_pos <= position
    with jax.named_scope("decode_attention"):
        # TPU path: kernels.decode_attention streams the cache through
        # VMEM once; the dequant + score tensors never hit HBM.
        ck, cv = attn_lib.dequantize_cache(cache)
        ck, cv = ck.astype(q.dtype), cv.astype(q.dtype)
        o = attn_lib.attention_einsum(
            q, ck, cv, q_positions=pos1, kv_positions=kv_pos,
            causal=True, window=cfg.window if swa else 0,
            kv_valid=kv_val[None])
    x = x + jnp.einsum("bshe,hed->bsd", o, p["wo"])
    if "xwq" in p and enc_kv is not None:
        hx = apply_norm(p["xnorm"], x, cfg.norm_eps)
        xq = jnp.einsum("bsd,dhe->bshe", hx, p["xwq"])
        xo = attn_lib.attention_einsum(
            xq, enc_kv["k"], enc_kv["v"], q_positions=pos1,
            kv_positions=jnp.arange(enc_kv["k"].shape[1]), causal=False)
        x = x + jnp.einsum("bshe,hed->bsd", xo, p["xwo"])
    h2 = apply_norm(p["norm2"], x, cfg.norm_eps)
    if "moe" in p:
        y, _ = moe_lib.apply_moe(p["moe"], h2, cfg, LOCAL_CTX)
    else:
        y = apply_mlp(p["mlp"], h2, cfg)
    return x + y, cache


def _decode_block(kind, p, x, cfg, cache, position, enc_kv=None):
    if kind == "attn":
        return _decode_attn(p, x, cfg, cache, position, enc_kv)
    if kind == "rec":
        h = apply_norm(p["norm1"], x, cfg.norm_eps)
        y, new_state = rglru_lib.apply_rglru_block(p["rglru"], h, cfg, state=cache)
        x = x + y
        h2 = apply_norm(p["norm2"], x, cfg.norm_eps)
        return x + apply_mlp(p["mlp"], h2, cfg), new_state
    if kind == "ssd":
        h = apply_norm(p["norm1"], x, cfg.norm_eps)
        y, new_state = ssd_lib.apply_ssd_block(p["ssd"], h, cfg, state=cache)
        return x + y, new_state
    raise ValueError(kind)


def build_enc_kv(params, enc_out, cfg):
    """Per-decoder-layer cross-attention K/V from encoder output (stacked)."""
    def one(bp):
        k = jnp.einsum("bsd,dhe->bshe", enc_out, bp["xwk"])
        v = jnp.einsum("bsd,dhe->bshe", enc_out, bp["xwv"])
        return {"k": k, "v": v}

    groups = {
        name: jax.vmap(lambda sl: one(sl))(stack)
        for name, stack in params["blocks"].items()
    }
    tail = {name: one(bp) for name, bp in params.get("tail", {}).items()}
    return {"groups": groups, "tail": tail}


def decode_step(params, token, cache, position, cfg,
                ctx: ShardCtx = LOCAL_CTX):
    """token (B,1) int32; position scalar int32.  Returns (logits, cache).

    For enc-dec models ``cache["enc_kv"]`` (built by ``prefill``) carries the
    cross-attention K/V; it is static during decode.
    """
    x = embed_tokens(params, token, cfg)
    pattern = cfg.block_pattern
    enc_stack = cache.get("enc_kv")

    if enc_stack is not None:
        def body(x, xs):
            gp, gc, genc = xs
            new = {}
            for i, kind in enumerate(pattern):
                x, c = _decode_block(kind, gp[f"b{i}"], x, cfg, gc[f"b{i}"],
                                     position, genc[f"b{i}"])
                new[f"b{i}"] = c
            return x, new
        x, new_groups = jax.lax.scan(
            body, x, (params["blocks"], cache["groups"], enc_stack["groups"]))
    else:
        def body(x, xs):
            gp, gc = xs
            new = {}
            for i, kind in enumerate(pattern):
                x, c = _decode_block(kind, gp[f"b{i}"], x, cfg, gc[f"b{i}"],
                                     position, None)
                new[f"b{i}"] = c
            return x, new
        x, new_groups = jax.lax.scan(
            body, x, (params["blocks"], cache["groups"]))

    new_tail = {}
    for i, kind in enumerate(cfg.tail_pattern()):
        tenc = enc_stack["tail"][f"t{i}"] if enc_stack else None
        x, c = _decode_block(kind, params["tail"][f"t{i}"], x, cfg,
                             cache["tail"][f"t{i}"], position, tenc)
        new_tail[f"t{i}"] = c
    x = apply_norm(params["final_norm"], x, cfg.norm_eps)
    logits = unembed(params, x, cfg)
    new_cache = {"groups": new_groups, "tail": new_tail}
    if enc_stack is not None:
        new_cache["enc_kv"] = enc_stack
    return logits, new_cache


def pad_kv_caches(caches, pad_to: int):
    """Grow attention KV caches (seq axis) so decode can append tokens.

    Attention caches are dicts with exactly {"k", "v"}; the seq axis is
    ndim-3 (works for both stacked (G,B,S,H,D) and unstacked (B,S,H,D)).
    """
    def fix(node):
        if isinstance(node, dict) and set(node) == {"k", "v"}:
            out = {}
            for key, a in node.items():
                ax = a.ndim - 3
                pad = pad_to - a.shape[ax]
                if pad > 0:
                    widths = [(0, 0)] * a.ndim
                    widths[ax] = (0, pad)
                    a = jnp.pad(a, widths)
                out[key] = a
            return out
        if isinstance(node, dict):
            return {k: fix(v) for k, v in node.items()}
        return node

    return {k: (fix(v) if k != "enc_kv" else v) for k, v in caches.items()}


def prefill(params, batch, cfg, ctx: ShardCtx = LOCAL_CTX, *,
            pad_to: int = 0):
    """Full-sequence prefill.  Returns (last-token logits, decode cache)."""
    hidden, _, caches = forward_hidden(
        params, batch, cfg, ctx, return_cache=True, remat=False)
    logits = unembed(params, hidden[:, -1:], cfg)
    if cfg.encoder_layers:
        caches["enc_kv"] = build_enc_kv(params, caches.pop("enc_out"), cfg)
    if pad_to:
        caches = pad_kv_caches(caches, pad_to)
    return logits, caches


# ==========================================================================
# Segmentation hook: run a static range of groups (the paper's split)
# ==========================================================================
def run_layer_range(params, x, cfg, ctx, *, start_group: int, stop_group: int,
                    positions, enc_out=None, kernels=None):
    """Run pattern groups [start_group, stop_group) over hidden states x.

    Static bounds => one compiled executable per split point; the scheduler's
    n_step quantization bounds how many of these exist (paper §4.3).
    Forward only, so it alone passes on the ``kernels`` hook.
    """
    G = cfg.num_groups()
    assert 0 <= start_group <= stop_group <= G
    sliced = jax.tree.map(lambda a: a[start_group:stop_group], params["blocks"])
    pattern = cfg.block_pattern

    def group_body(carry, gp):
        x, = carry
        for i, kind in enumerate(pattern):
            x, _, _ = apply_block_seq(
                kind, gp[f"b{i}"], x, cfg, ctx, positions=positions,
                enc_out=enc_out, kernels=kernels)
        return (x,), None

    if stop_group > start_group:
        (x,), _ = jax.lax.scan(group_body, (x,), sliced)
    if stop_group == G:
        for i, kind in enumerate(cfg.tail_pattern()):
            x, _, _ = apply_block_seq(
                kind, params["tail"][f"t{i}"], x, cfg, ctx,
                positions=positions, enc_out=enc_out, kernels=kernels)
    return x


def flash_sites(cfg, seq_len: int, *, start_group: int = 0,
                stop_group: int) -> int:
    """How many self-attention layers of ``run_layer_range`` over groups
    [start_group, stop_group) at ``seq_len`` tokens take the flash path
    (the kernel where the caller passes ``kernels["attn"]``), from the
    configuration and the length."""
    if not attn_lib.takes_flash(seq_len):
        return 0
    kinds = cfg.block_pattern * (stop_group - start_group)
    if stop_group == cfg.num_groups():
        kinds += cfg.tail_pattern()
    return kinds.count("attn")
