"""Latent diffusion (Stable-Diffusion-v1-class) in pure JAX.

Three phases, exactly as the paper's codebase divides them (§5.1.2):
  encode   — CLIP-like text transformer -> context (2B, 77, 768)
             (2x = classifier-free guidance pair: uncond + cond)
  diffuse  — denoising U-Net over latents (B, 4, 64, 64), n_total iterations
  decode   — VAE decoder -> images (B, 3, 512, 512)

The paper's split points are after every ``split_stride`` denoising
iterations plus between the U-Net and the VAE ("denoising50").  The
boundary tensors are (latent fp32, context fp16) — ``split_payload``
reproduces paper Table 2's byte counts exactly.

``denoise_range(params, state, start_iter, stop_iter)`` is the segmentation
hook: the cloud runs iterations [0, n_cloud), ships the payload, the device
runs [n_cloud, n_total) + VAE decode.
"""
from __future__ import annotations

import math
from typing import Any, Dict, List, Tuple

import jax
import jax.numpy as jnp

from repro.kernels import ops
from repro.models.common import dense_init, embed_init, split_keys
from repro.models.regnet import conv2d, init_conv

Params = Dict[str, Any]


# ==========================================================================
# Small helpers
# ==========================================================================
def init_ln(d):
    return {"scale": jnp.ones((d,)), "bias": jnp.zeros((d,))}


def ln(p, x, eps=1e-5):
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, -1, keepdims=True)
    var = jnp.var(xf, -1, keepdims=True)
    return ((xf - mu) * jax.lax.rsqrt(var + eps) * p["scale"] + p["bias"]
            ).astype(x.dtype)


def init_gn(c):
    return {"scale": jnp.ones((c,)), "bias": jnp.zeros((c,))}


def gn(p, x, groups=32, eps=1e-5):
    """GroupNorm over NCHW."""
    B, C, H, W = x.shape
    g = min(groups, C)
    while C % g:
        g -= 1
    xf = x.astype(jnp.float32).reshape(B, g, C // g, H, W)
    mu = jnp.mean(xf, axis=(2, 3, 4), keepdims=True)
    var = jnp.var(xf, axis=(2, 3, 4), keepdims=True)
    xf = ((xf - mu) * jax.lax.rsqrt(var + eps)).reshape(B, C, H, W)
    return (xf * p["scale"][:, None, None] + p["bias"][:, None, None]
            ).astype(x.dtype)


def silu(x):
    return jax.nn.silu(x)


def _mha(q, k, v, heads, causal=False):
    B, Sq, D = q.shape
    hd = D // heads
    q = q.reshape(B, Sq, heads, hd)
    k = k.reshape(B, k.shape[1], heads, hd)
    v = v.reshape(B, v.shape[1], heads, hd)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / math.sqrt(hd)
    if causal:
        msk = jnp.tril(jnp.ones((Sq, k.shape[1]), bool))
        s = jnp.where(msk, s, -1e30)
    p = jax.nn.softmax(s.astype(jnp.float32), -1).astype(q.dtype)
    o = jnp.einsum("bhqk,bkhd->bqhd", p, v)
    return o.reshape(B, Sq, D)


#: Self-attention over at least this many tokens runs through the Pallas
#: flash kernel, so its (S, S) scores stay in VMEM; shorter sequences,
#: cross-attention and the text encoder keep ``_mha``.  4096 is the 64x64
#: level of stable-diffusion-v1; at 1024 tokens (head_dim 80) the einsum
#: path was the faster on a v5e.
FLASH_MIN_TOKENS = 4096
#: The kernel's (q, kv) block pair: the fastest of those measured on a v5e
#: at 4096 tokens, batch 1 and 2.
FLASH_BLOCKS = (512, 1024)


def _self_attention(q, k, v, heads):
    """Self-attention of a spatial transformer, q/k/v (B, S, C).

    At ``FLASH_MIN_TOKENS`` tokens or more: the flash kernel, with q, k
    and v rounded to bf16 once (the operands a default-precision TPU dot
    takes from fp32 anyway), fp32 softmax statistics and accumulation,
    and the result in the input dtype."""
    B, S, D = q.shape
    if S < FLASH_MIN_TOKENS:
        return _mha(q, k, v, heads)
    with jax.named_scope("flash_attention"):
        o = ops.flash_attention(
            *(a.astype(jnp.bfloat16).reshape(B, S, heads, D // heads)
              for a in (q, k, v)), causal=False, bq=FLASH_BLOCKS[0],
            bk=FLASH_BLOCKS[1])
        return o.reshape(B, S, D).astype(q.dtype)


# ==========================================================================
# Text encoder (CLIP-ish)
# ==========================================================================
def init_text_encoder(cfg, key) -> Params:
    ks = split_keys(key, 2 + cfg.text_layers)
    d = cfg.text_width
    layers = []
    for i in range(cfg.text_layers):
        lk = split_keys(ks[2 + i], 6)
        layers.append({
            "ln1": init_ln(d),
            "wqkv": dense_init(lk[0], (d, 3 * d), jnp.float32),
            "wo": dense_init(lk[1], (d, d), jnp.float32),
            "ln2": init_ln(d),
            "w1": dense_init(lk[2], (d, 4 * d), jnp.float32),
            "w2": dense_init(lk[3], (4 * d, d), jnp.float32),
        })
    return {
        "tok": embed_init(ks[0], (cfg.text_vocab, d), jnp.float32),
        "pos": embed_init(ks[1], (cfg.text_len, d), jnp.float32),
        "layers": layers,
        "ln_f": init_ln(d),
    }


@jax.named_scope("text_encoder")
def encode_text(p, cfg, tokens):
    """tokens (B, 77) -> context (B, 77, width).  Causal, CLIP-style."""
    x = p["tok"][tokens] + p["pos"][None, : tokens.shape[1]]
    for lp in p["layers"]:
        h = ln(lp["ln1"], x)
        q, k, v = jnp.split(jnp.einsum("bsd,de->bse", h, lp["wqkv"]), 3, -1)
        x = x + jnp.einsum("bsd,de->bse",
                           _mha(q, k, v, cfg.text_heads, causal=True), lp["wo"])
        h = ln(lp["ln2"], x)
        x = x + jnp.einsum("bsf,fd->bsd",
                           jax.nn.gelu(jnp.einsum("bsd,df->bsf", h, lp["w1"])),
                           lp["w2"])
    return ln(p["ln_f"], x)


# ==========================================================================
# U-Net
# ==========================================================================
def _timestep_embedding(t, dim):
    half = dim // 2
    freqs = jnp.exp(-math.log(10000.0) * jnp.arange(half) / half)
    args = t[:, None].astype(jnp.float32) * freqs[None]
    return jnp.concatenate([jnp.cos(args), jnp.sin(args)], axis=-1)


def init_resblock(key, c_in, c_out, t_dim):
    ks = split_keys(key, 4)
    p = {
        "gn1": init_gn(c_in), "conv1": init_conv(ks[0], c_in, c_out, 3),
        "t_proj": dense_init(ks[1], (t_dim, c_out), jnp.float32),
        "gn2": init_gn(c_out), "conv2": init_conv(ks[2], c_out, c_out, 3),
    }
    if c_in != c_out:
        p["skip"] = init_conv(ks[3], c_in, c_out, 1)
    return p


@jax.named_scope("resblock")
def apply_resblock(p, x, t_emb):
    h = conv2d(silu(gn(p["gn1"], x)), p["conv1"])
    h = h + jnp.einsum("bt,tc->bc", silu(t_emb), p["t_proj"])[:, :, None, None]
    h = conv2d(silu(gn(p["gn2"], h)), p["conv2"])
    sc = conv2d(x, p["skip"]) if "skip" in p else x
    return h + sc


def init_xattn(key, c, ctx_dim, heads):
    ks = split_keys(key, 8)
    return {
        "gn": init_gn(c),
        "proj_in": init_conv(ks[0], c, c, 1),
        "ln1": init_ln(c), "wq1": dense_init(ks[1], (c, c), jnp.float32),
        "wkv1": dense_init(ks[2], (c, 2 * c), jnp.float32),
        "wo1": dense_init(ks[3], (c, c), jnp.float32),
        "ln2": init_ln(c), "wq2": dense_init(ks[4], (c, c), jnp.float32),
        "wkv2": dense_init(ks[5], (ctx_dim, 2 * c), jnp.float32),
        "wo2": dense_init(ks[6], (c, c), jnp.float32),
        "ln3": init_ln(c),
        "w1": dense_init(ks[7], (c, 4 * c), jnp.float32),
        "w2": dense_init(jax.random.fold_in(ks[7], 1), (4 * c, c), jnp.float32),
        "proj_out": init_conv(jax.random.fold_in(ks[0], 1), c, c, 1),
    }


def apply_xattn(p, x, ctx, heads):
    """Spatial transformer: self-attn + cross-attn(ctx) + MLP.  Each of
    the three named scopes holds its layer norm, projections and
    residual add; ``xattn_proj`` holds the way in and out of the
    sequence layout."""
    B, C, H, W = x.shape
    with jax.named_scope("xattn_proj"):
        h = conv2d(gn(p["gn"], x), p["proj_in"])
        seq = h.reshape(B, C, H * W).transpose(0, 2, 1)      # (B, HW, C)
    with jax.named_scope("self_attn"):
        t = ln(p["ln1"], seq)
        k, v = jnp.split(jnp.einsum("bsc,ce->bse", t, p["wkv1"]), 2, -1)
        seq = seq + jnp.einsum(
            "bsc,ce->bse",
            _self_attention(jnp.einsum("bsc,ce->bse", t, p["wq1"]), k, v,
                            heads),
            p["wo1"])
    with jax.named_scope("cross_attn"):
        t = ln(p["ln2"], seq)
        k, v = jnp.split(jnp.einsum("bsc,ce->bse", ctx, p["wkv2"]), 2, -1)
        seq = seq + jnp.einsum(
            "bsc,ce->bse",
            _mha(jnp.einsum("bsc,ce->bse", t, p["wq2"]), k, v, heads),
            p["wo2"])
    with jax.named_scope("mlp"):
        t = ln(p["ln3"], seq)
        seq = seq + jnp.einsum(
            "bsf,fc->bsc", jax.nn.gelu(jnp.einsum("bsc,cf->bsf", t, p["w1"])),
            p["w2"])
    with jax.named_scope("xattn_proj"):
        h = seq.transpose(0, 2, 1).reshape(B, C, H, W)
        return x + conv2d(h, p["proj_out"])


def init_unet(cfg, key) -> Params:
    ks = split_keys(key, 64)
    ki = iter(ks)
    base = cfg.unet_base
    t_dim = base * 4
    p: Params = {
        "t_w1": dense_init(next(ki), (base, t_dim), jnp.float32),
        "t_w2": dense_init(next(ki), (t_dim, t_dim), jnp.float32),
        "conv_in": init_conv(next(ki), cfg.latent_channels, base, 3),
    }
    chans = [base * m for m in cfg.unet_mults]
    downs = []
    skip_chans = [base]                     # mirrors the skips list in apply
    c_prev = base
    for lvl, c in enumerate(chans):
        blocks = []
        for _ in range(cfg.unet_res_blocks):
            blk = {"res": init_resblock(next(ki), c_prev, c, t_dim)}
            if lvl in cfg.unet_attn_levels:
                blk["attn"] = init_xattn(next(ki), c, cfg.text_width,
                                         cfg.unet_heads)
            blocks.append(blk)
            c_prev = c
            skip_chans.append(c)
        lvl_p = {"blocks": blocks}
        if lvl < len(chans) - 1:
            lvl_p["down"] = init_conv(next(ki), c, c, 3)
            skip_chans.append(c)
        downs.append(lvl_p)
    p["downs"] = downs
    p["mid1"] = init_resblock(next(ki), c_prev, c_prev, t_dim)
    p["mid_attn"] = init_xattn(next(ki), c_prev, cfg.text_width, cfg.unet_heads)
    p["mid2"] = init_resblock(next(ki), c_prev, c_prev, t_dim)
    ups = []
    for lvl in reversed(range(len(chans))):
        c = chans[lvl]
        blocks = []
        for _ in range(cfg.unet_res_blocks + 1):
            c_skip = skip_chans.pop()
            blk = {"res": init_resblock(next(ki), c_prev + c_skip, c, t_dim)}
            if lvl in cfg.unet_attn_levels:
                blk["attn"] = init_xattn(next(ki), c, cfg.text_width,
                                         cfg.unet_heads)
            blocks.append(blk)
            c_prev = c
        lvl_p = {"blocks": blocks}
        if lvl > 0:
            lvl_p["up"] = init_conv(next(ki), c, c, 3)
        ups.append(lvl_p)
    p["ups"] = ups
    p["gn_out"] = init_gn(base)
    p["conv_out"] = init_conv(next(ki), base, cfg.latent_channels, 3)
    return p


@jax.named_scope("unet")
def apply_unet(p, cfg, latent, t, ctx):
    """latent (B,4,h,w), t (B,), ctx (B,77,width) -> predicted noise.

    Named scopes: one per level (``down{l}``, ``mid``, ``up{l}``, with
    ``l`` the resolution level, 0 the finest), and inside them the
    blocks (``resblock``, ``xattn_proj``, ``self_attn``, ``cross_attn``,
    ``mlp``) and ``resample``; ``stem`` and ``head`` outside."""
    with jax.named_scope("stem"):
        t_emb = _timestep_embedding(t, cfg.unet_base)
        t_emb = jnp.einsum("bt,te->be", silu(jnp.einsum(
            "bt,te->be", t_emb, p["t_w1"])), p["t_w2"])
        x = conv2d(latent, p["conv_in"])
    skips = [x]
    for lvl, lvl_p in enumerate(p["downs"]):
        with jax.named_scope(f"down{lvl}"):
            for blk in lvl_p["blocks"]:
                x = apply_resblock(blk["res"], x, t_emb)
                if "attn" in blk:
                    x = apply_xattn(blk["attn"], x, ctx, cfg.unet_heads)
                skips.append(x)
            if "down" in lvl_p:
                with jax.named_scope("resample"):
                    x = conv2d(x, lvl_p["down"], stride=2)
                skips.append(x)
    with jax.named_scope("mid"):
        x = apply_resblock(p["mid1"], x, t_emb)
        x = apply_xattn(p["mid_attn"], x, ctx, cfg.unet_heads)
        x = apply_resblock(p["mid2"], x, t_emb)
    for i, lvl_p in enumerate(p["ups"]):
        with jax.named_scope(f"up{len(p['ups']) - 1 - i}"):
            for blk in lvl_p["blocks"]:
                with jax.named_scope("resblock"):    # the skip joins its input
                    x = jnp.concatenate([x, skips.pop()], axis=1)
                x = apply_resblock(blk["res"], x, t_emb)
                if "attn" in blk:
                    x = apply_xattn(blk["attn"], x, ctx, cfg.unet_heads)
            if "up" in lvl_p:
                with jax.named_scope("resample"):
                    B, C, H, W = x.shape
                    x = jax.image.resize(x, (B, C, 2 * H, 2 * W), "nearest")
                    x = conv2d(x, lvl_p["up"])
    with jax.named_scope("head"):
        return conv2d(silu(gn(p["gn_out"], x)), p["conv_out"])


def flash_sites(cfg) -> int:
    """How many self-attention layers of one UNet evaluation run through
    the flash kernel (``_self_attention``), from the configuration's
    shapes: level ``l`` has ``unet_res_blocks`` attention blocks down and
    one more up, the middle one more at the coarsest level."""
    L = len(cfg.unet_mults)
    size = cfg.latent_size
    sites = 0
    for lvl in range(L):
        if lvl in cfg.unet_attn_levels and size * size >= FLASH_MIN_TOKENS:
            sites += 2 * cfg.unet_res_blocks + 1
        if lvl < L - 1:
            size = -(-size // 2)                 # stride-2 "SAME" conv
    return sites + int(size * size >= FLASH_MIN_TOKENS)   # mid


# ==========================================================================
# VAE decoder
# ==========================================================================
def init_vae_decoder(cfg, key) -> Params:
    ks = split_keys(key, 32)
    ki = iter(ks)
    chans = [cfg.vae_base * m for m in reversed(cfg.vae_mults)]
    p: Params = {"conv_in": init_conv(next(ki), cfg.latent_channels,
                                      chans[0], 3)}
    stages = []
    c_prev = chans[0]
    for i, c in enumerate(chans):
        stages.append({
            "res1": init_resblock(next(ki), c_prev, c, 4),
            "res2": init_resblock(next(ki), c, c, 4),
            "up": (init_conv(next(ki), c, c, 3) if i < len(chans) - 1 else None),
        })
        c_prev = c
    p["stages"] = stages
    p["gn_out"] = init_gn(c_prev)
    p["conv_out"] = init_conv(next(ki), c_prev, 3, 3)
    return p


def apply_vae_decoder(p, cfg, latent):
    t_emb = jnp.zeros((latent.shape[0], 4), jnp.float32)
    x = conv2d(latent / 0.18215, p["conv_in"])
    for st in p["stages"]:
        x = apply_resblock(st["res1"], x, t_emb)
        x = apply_resblock(st["res2"], x, t_emb)
        if st["up"] is not None:
            B, C, H, W = x.shape
            x = jax.image.resize(x, (B, C, 2 * H, 2 * W), "nearest")
            x = conv2d(x, st["up"])
    return jnp.tanh(conv2d(silu(gn(p["gn_out"], x)), p["conv_out"]))


# ==========================================================================
# Full pipeline + segmentation hooks
# ==========================================================================
def init_params(cfg, key) -> Params:
    k1, k2, k3 = split_keys(key, 3)
    return {
        "text": init_text_encoder(cfg, k1),
        "unet": init_unet(cfg, k2),
        "vae": init_vae_decoder(cfg, k3),
    }


def ddim_alphas(cfg):
    """Linear-beta DDPM schedule subsampled to n_total DDIM steps."""
    T = 1000
    betas = jnp.linspace(8.5e-4, 0.012, T)
    alphas_bar = jnp.cumprod(1.0 - betas)
    idx = jnp.linspace(T - 1, 0, cfg.n_total_iterations).astype(jnp.int32)
    return alphas_bar[idx], idx  # descending noise level


def encode_prompt(params, cfg, cond_tokens, uncond_tokens):
    """-> context (2, B, 77, width): the paper's '2x77x768' tensor."""
    cond = encode_text(params["text"], cfg, cond_tokens)
    uncond = encode_text(params["text"], cfg, uncond_tokens)
    return jnp.stack([uncond, cond])


def denoise_step(params, cfg, latent, ctx2, step_idx):
    """One guided DDIM step.  ctx2 (2,B,77,w); step_idx scalar int32.
    The schedule, the guidance combine and the DDIM update are the
    named scope ``guidance``; the two UNet runs are ``unet``."""
    with jax.named_scope("guidance"):
        alphas, t_idx = ddim_alphas(cfg)
        a_t = alphas[step_idx]
        a_prev = jnp.where(step_idx + 1 < cfg.n_total_iterations,
                           alphas[jnp.minimum(step_idx + 1,
                                              cfg.n_total_iterations - 1)],
                           jnp.float32(1.0))
        t = jnp.broadcast_to(t_idx[step_idx], (latent.shape[0],))
    eps_u = apply_unet(params["unet"], cfg, latent, t, ctx2[0])
    eps_c = apply_unet(params["unet"], cfg, latent, t, ctx2[1])
    with jax.named_scope("guidance"):
        eps = eps_u + cfg.guidance_scale * (eps_c - eps_u)
        x0 = (latent - jnp.sqrt(1.0 - a_t) * eps) / jnp.sqrt(a_t)
        return jnp.sqrt(a_prev) * x0 + jnp.sqrt(1.0 - a_prev) * eps


def denoise_range(params, cfg, latent, ctx2, start_iter: int, stop_iter: int):
    """Run denoising iterations [start_iter, stop_iter).

    This is the paper's split: cloud runs [0, n_cloud), device runs
    [n_cloud, n_total).  Bounds are static -> one executable per split
    group (the scheduler's n_step quantization bounds how many exist).
    """
    def body(i, lat):
        return denoise_step(params, cfg, lat, ctx2, start_iter + i)

    return jax.lax.fori_loop(0, stop_iter - start_iter, body, latent)


def generate(params, cfg, cond_tokens, uncond_tokens, key):
    """Full pipeline on one machine (the all-cloud / all-device baseline)."""
    B = cond_tokens.shape[0]
    ctx2 = encode_prompt(params, cfg, cond_tokens, uncond_tokens)
    latent = jax.random.normal(
        key, (B, cfg.latent_channels, cfg.latent_size, cfg.latent_size))
    latent = denoise_range(params, cfg, latent, ctx2, 0,
                           cfg.n_total_iterations)
    return apply_vae_decoder(params["vae"], cfg, latent)


def split_payload(cfg, batch: int = 1) -> List[Tuple[str, int]]:
    """(split name, transfer bytes) for each split point — paper Table 2.

    latent fp32 + context fp16 for mid-diffusion splits; only the latent
    fp32 for 'denoising{n_total}' (context no longer needed).
    """
    latent_bytes = batch * cfg.latent_channels * cfg.latent_size ** 2 * 4
    ctx_bytes = 2 * batch * cfg.text_len * cfg.text_width * 2   # fp16
    out = [("denoising0", ctx_bytes)]
    for i in range(cfg.split_stride, cfg.n_total_iterations, cfg.split_stride):
        out.append((f"denoising{i}", latent_bytes + ctx_bytes))
    out.append((f"denoising{cfg.n_total_iterations}", latent_bytes))
    return out
