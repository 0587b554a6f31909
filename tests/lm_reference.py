"""Plain reference of a Mistral-type decoder (h2o-danube-1.8b's block).

Straightforward ``jax.numpy`` in float32 under
``jax.default_matmul_precision("highest")``, written from the published
block: token embedding; per layer an RMSNorm (eps from the config),
q/k/v projections, RoPE (theta from the config, the half-split
rotation), grouped-query attention with an explicit causal and
sliding-window mask (query i sees keys i - window < j <= i), the output
projection and the residual; then an RMSNorm, a SwiGLU MLP
(``silu(h W_gate) * (h W_up) W_down``) and the residual; after the last
layer the final RMSNorm and the untied head.  No kernel, scan, cache or
batching trick: one layer after the other, the whole score matrix at
once.

It reads the weights of the program's parameter tree by their key names
(``embed``, ``blocks/b0/{norm1,wq,wk,wv,wo,norm2,mlp}``, ``final_norm``,
``lm_head``), upcast to float32.  Departures from the published model:

- the vocabulary rows past ``vocab_size`` (the program pads the
  embedding and the head to a multiple of 2048) are never read: tokens
  lie below ``vocab_size`` and the logits are cut to it;
- only a pattern of plain attention layers without biases, experts or
  a tail is covered, which is what h2o-danube-1.8b is.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


def _f32(a):
    return jnp.asarray(a, jnp.float32)


def rms_norm(x, scale, eps):
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * _f32(scale)


def rope(x, theta):
    """x (B, S, H, D): rotate the pairs (x[..., i], x[..., i + D/2]) by
    position x theta^(-2i/D)."""
    S, D = x.shape[1], x.shape[-1]
    inv = 1.0 / theta ** (np.arange(0, D, 2, dtype=np.float64) / D)
    ang = np.arange(S, dtype=np.float64)[:, None] * inv[None, :]
    cos = jnp.asarray(np.cos(ang), jnp.float32)[None, :, None, :]
    sin = jnp.asarray(np.sin(ang), jnp.float32)[None, :, None, :]
    a, b = x[..., :D // 2], x[..., D // 2:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def window_mask(S, window):
    """(S, S) bool: query i attends key j iff j <= i and j > i - window
    (no window where it is 0)."""
    i = np.arange(S)[:, None]
    j = np.arange(S)[None, :]
    m = j <= i
    if window:
        m &= j > i - window
    return jnp.asarray(m)


def layer(p, x, cfg):
    """One decoder layer; ``p`` holds that layer's weights."""
    B, S, _ = x.shape
    H, Hkv = cfg.num_heads, cfg.num_kv_heads
    D = cfg.resolved_head_dim()
    h = rms_norm(x, p["norm1"]["scale"], cfg.norm_eps)
    q = jnp.einsum("bsd,dhe->bshe", h, _f32(p["wq"]))
    k = jnp.einsum("bsd,dhe->bshe", h, _f32(p["wk"]))
    v = jnp.einsum("bsd,dhe->bshe", h, _f32(p["wv"]))
    q, k = rope(q, cfg.rope_theta), rope(k, cfg.rope_theta)
    # query head n reads kv head n // (H / Hkv)
    k = jnp.repeat(k, H // Hkv, axis=2)
    v = jnp.repeat(v, H // Hkv, axis=2)
    s = jnp.einsum("bqhe,bkhe->bhqk", q, k) / np.sqrt(D)
    window = cfg.window if cfg.attention_kind == "swa" else 0
    s = jnp.where(window_mask(S, window), s, -jnp.inf)
    a = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhqk,bkhe->bqhe", a, v)
    x = x + jnp.einsum("bqhe,hed->bqd", o, _f32(p["wo"]))
    h = rms_norm(x, p["norm2"]["scale"], cfg.norm_eps)
    m = p["mlp"]
    g = jnp.einsum("bsd,df->bsf", h, _f32(m["wi_gate"]))
    u = jnp.einsum("bsd,df->bsf", h, _f32(m["wi_up"]))
    return x + jnp.einsum("bsf,fd->bsd", jax.nn.silu(g) * u, _f32(m["wo"]))


def forward(params, cfg, tokens, stop_layer=None):
    """tokens (B, S) -> (hidden states after ``stop_layer`` layers
    (B, S, d), last-position logits (B, vocab_size)), both float32.
    ``stop_layer`` None gives the hidden states after every layer."""
    assert cfg.block_pattern == ("attn",) and cfg.moe is None
    assert not cfg.qkv_bias and not cfg.tie_embeddings
    L = cfg.num_layers
    stop = L if stop_layer is None else stop_layer
    blocks = params["blocks"]["b0"]
    with jax.default_matmul_precision("highest"):
        x = _f32(params["embed"])[jnp.asarray(tokens)]
        hidden = x
        for n in range(L):
            if n == stop:
                hidden = x
            x = layer(jax.tree.map(lambda a, n=n: a[n], blocks), x, cfg)
        if stop == L:
            hidden = x
        x = rms_norm(x[:, -1], params["final_norm"]["scale"], cfg.norm_eps)
        logits = x @ _f32(params["lm_head"])
    return hidden, logits[:, :cfg.vocab_size]
