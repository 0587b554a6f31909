"""Plain reference of the sd-v1 cloud half, and its operation count.

Written from the architecture the configuration file states (CLIP-style
text encoder, the SD v1 UNet with spatial transformers, DDIM with
classifier-free guidance) in straightforward ``jax.numpy``.  It imports
nothing of the program: it reads the weights the benchmark made, by the
key names of their tree, and nothing else.

``Reference(sizes, schedule, precision=...)`` computes in float32 at
the given matmul precision; the check runs it at the precision the
configuration states (float32 at the TPU's default precision).
``Reference(..., dtype=jnp.bfloat16, precision=DEFAULT)`` is the
control: the same arithmetic one precision step below, bfloat16
throughout.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
#: the configuration file's ``matmul_precision`` -> JAX's
PRECISIONS = {"default": jax.lax.Precision.DEFAULT, "highest": HIGHEST}


class Reference:
    def __init__(self, sizes: dict, schedule: dict, *,
                 dtype=jnp.float32, precision=HIGHEST):
        self.s = sizes
        self.dtype = dtype
        self.prec = precision
        betas = np.linspace(schedule["beta_start"], schedule["beta_end"],
                            schedule["train_steps"], dtype=np.float64)
        abar = np.cumprod(1.0 - betas)
        n = sizes["n_total_iterations"]
        idx = np.linspace(schedule["train_steps"] - 1, 0, n).astype(np.int32)
        self.t_idx = idx
        self.alpha = abar[idx].astype(np.float32)
        self.alpha_next = np.append(abar[idx[1:]], 1.0).astype(np.float32)
        self.encode = jax.jit(self._encode)
        self.step = jax.jit(self._step)

    # -- primitives ---------------------------------------------------------
    def _mm(self, eq, a, b):
        return jnp.einsum(eq, a, b, precision=self.prec)

    def _conv(self, x, w, stride=1):
        return jax.lax.conv_general_dilated(
            x, w.astype(x.dtype), (stride, stride), "SAME",
            dimension_numbers=("NCHW", "OIHW", "NCHW"), precision=self.prec)

    @staticmethod
    def _ln(p, x, eps=1e-5):
        mu = jnp.mean(x, -1, keepdims=True)
        var = jnp.mean(jnp.square(x - mu), -1, keepdims=True)
        return ((x - mu) / jnp.sqrt(var + eps) * p["scale"].astype(x.dtype)
                + p["bias"].astype(x.dtype))

    @staticmethod
    def _gn(p, x, groups=32, eps=1e-5):
        B, C, H, W = x.shape
        g = min(groups, C)
        while C % g:
            g -= 1
        xg = x.reshape(B, g, C // g, H, W)
        mu = jnp.mean(xg, axis=(2, 3, 4), keepdims=True)
        var = jnp.mean(jnp.square(xg - mu), axis=(2, 3, 4), keepdims=True)
        xg = ((xg - mu) / jnp.sqrt(var + eps)).reshape(B, C, H, W)
        return (xg * p["scale"].astype(x.dtype)[:, None, None]
                + p["bias"].astype(x.dtype)[:, None, None])

    def _attend(self, q, k, v, heads, causal):
        B, Sq, D = q.shape
        hd = D // heads
        q = q.reshape(B, Sq, heads, hd)
        k = k.reshape(B, k.shape[1], heads, hd)
        v = v.reshape(B, v.shape[1], heads, hd)
        s = self._mm("bqhd,bkhd->bhqk", q, k) / math.sqrt(hd)
        if causal:
            s = jnp.where(jnp.tril(jnp.ones((Sq, k.shape[1]), bool)), s,
                          jnp.asarray(-1e30, s.dtype) if s.dtype == jnp.float32
                          else jnp.asarray(-3e38, s.dtype))
        s = s - jnp.max(s, -1, keepdims=True)
        e = jnp.exp(s)
        p = e / jnp.sum(e, -1, keepdims=True)
        return self._mm("bhqk,bkhd->bqhd", p, v).reshape(B, Sq, D)

    @staticmethod
    def _gelu(x):       # tanh form
        return 0.5 * x * (1.0 + jnp.tanh(
            math.sqrt(2.0 / math.pi) * (x + 0.044715 * x ** 3)))

    @staticmethod
    def _silu(x):
        return x / (1.0 + jnp.exp(-x))

    def _w(self, a):
        return a.astype(self.dtype)

    # -- text encoder ---------------------------------------------------------
    def _encode(self, p, tokens):
        """tokens (B, text_len) -> context (B, text_len, text_width)."""
        x = self._w(p["tok"])[tokens] + self._w(p["pos"])[None, :tokens.shape[1]]
        for lp in p["layers"]:
            h = self._ln(lp["ln1"], x)
            q, k, v = jnp.split(self._mm("bsd,de->bse", h, self._w(lp["wqkv"])),
                                3, -1)
            x = x + self._mm("bsd,de->bse",
                             self._attend(q, k, v, self.s["text_heads"], True),
                             self._w(lp["wo"]))
            h = self._ln(lp["ln2"], x)
            x = x + self._mm("bsf,fd->bsd", self._gelu(
                self._mm("bsd,df->bsf", h, self._w(lp["w1"]))), self._w(lp["w2"]))
        return self._ln(p["ln_f"], x)

    # -- UNet -------------------------------------------------------------------
    def _res(self, p, x, temb):
        h = self._conv(self._silu(self._gn(p["gn1"], x)), p["conv1"])
        h = h + self._mm("bt,tc->bc", self._silu(temb),
                         self._w(p["t_proj"]))[:, :, None, None]
        h = self._conv(self._silu(self._gn(p["gn2"], h)), p["conv2"])
        return h + (self._conv(x, p["skip"]) if "skip" in p else x)

    def _xattn(self, p, x, ctx):
        heads = self.s["unet_heads"]
        B, C, H, W = x.shape
        h = self._conv(self._gn(p["gn"], x), p["proj_in"])
        seq = h.reshape(B, C, H * W).transpose(0, 2, 1)
        t = self._ln(p["ln1"], seq)
        k, v = jnp.split(self._mm("bsc,ce->bse", t, self._w(p["wkv1"])), 2, -1)
        q = self._mm("bsc,ce->bse", t, self._w(p["wq1"]))
        seq = seq + self._mm("bsc,ce->bse", self._attend(q, k, v, heads, False),
                             self._w(p["wo1"]))
        t = self._ln(p["ln2"], seq)
        k, v = jnp.split(self._mm("bsc,ce->bse", ctx, self._w(p["wkv2"])), 2, -1)
        q = self._mm("bsc,ce->bse", t, self._w(p["wq2"]))
        seq = seq + self._mm("bsc,ce->bse", self._attend(q, k, v, heads, False),
                             self._w(p["wo2"]))
        t = self._ln(p["ln3"], seq)
        seq = seq + self._mm("bsf,fc->bsc", self._gelu(
            self._mm("bsc,cf->bsf", t, self._w(p["w1"]))), self._w(p["w2"]))
        h = seq.transpose(0, 2, 1).reshape(B, C, H, W)
        return x + self._conv(h, p["proj_out"])

    def _unet(self, p, latent, t, ctx):
        base = self.s["unet_base"]
        half = base // 2
        freqs = jnp.exp(-math.log(10000.0) * jnp.arange(half, dtype=jnp.float32)
                        / half)
        args = t[:, None].astype(jnp.float32) * freqs[None]
        temb = jnp.concatenate([jnp.cos(args), jnp.sin(args)], -1).astype(
            self.dtype)
        temb = self._mm("bt,te->be", self._silu(
            self._mm("bt,te->be", temb, self._w(p["t_w1"]))), self._w(p["t_w2"]))
        x = self._conv(latent, p["conv_in"])
        skips = [x]
        for lvl in p["downs"]:
            for blk in lvl["blocks"]:
                x = self._res(blk["res"], x, temb)
                if "attn" in blk:
                    x = self._xattn(blk["attn"], x, ctx)
                skips.append(x)
            if "down" in lvl:
                x = self._conv(x, lvl["down"], stride=2)
                skips.append(x)
        x = self._res(p["mid1"], x, temb)
        x = self._xattn(p["mid_attn"], x, ctx)
        x = self._res(p["mid2"], x, temb)
        for lvl in p["ups"]:
            for blk in lvl["blocks"]:
                x = jnp.concatenate([x, skips.pop()], axis=1)
                x = self._res(blk["res"], x, temb)
                if "attn" in blk:
                    x = self._xattn(blk["attn"], x, ctx)
            if "up" in lvl:
                x = jnp.repeat(jnp.repeat(x, 2, axis=2), 2, axis=3)
                x = self._conv(x, lvl["up"])
        return self._conv(self._silu(self._gn(p["gn_out"], x)), p["conv_out"])

    def _step(self, p, latent, ctx2, step):
        """One guided DDIM step at index ``step`` (a traced int32)."""
        a_t = jnp.asarray(self.alpha)[step].astype(self.dtype)
        a_n = jnp.asarray(self.alpha_next)[step].astype(self.dtype)
        t = jnp.broadcast_to(jnp.asarray(self.t_idx)[step], (latent.shape[0],))
        lat = latent.astype(self.dtype)
        eps_u = self._unet(p, lat, t, ctx2[0].astype(self.dtype))
        eps_c = self._unet(p, lat, t, ctx2[1].astype(self.dtype))
        g = jnp.asarray(self.s["guidance_scale"], self.dtype)
        eps = eps_u + g * (eps_c - eps_u)
        x0 = (lat - jnp.sqrt(1.0 - a_t) * eps) / jnp.sqrt(a_t)
        return jnp.sqrt(a_n) * x0 + jnp.sqrt(1.0 - a_n) * eps

    def cloud_half(self, params, cond, uncond, latent, n_cloud):
        """(latent after DDIM steps [0, n_cloud), context (2, B, L, W)),
        as float32 numpy."""
        ctx2 = jnp.stack([self.encode(params["text"], uncond),
                          self.encode(params["text"], cond)])
        lat = latent
        for i in range(n_cloud):
            lat = self.step(params["unet"], lat, ctx2, jnp.int32(i))
        return (np.asarray(lat.astype(jnp.float32)),
                np.asarray(ctx2.astype(jnp.float32)))


# ----------------------------------------------------------------------------
# Operation count: multiply-adds of every convolution, projection and
# attention product, times two; norms, activations and the DDIM update are
# left out (under 1% of the total at these widths).
# ----------------------------------------------------------------------------
def _conv_flops(hw, c_in, c_out, k):
    return 2 * hw * c_in * c_out * k * k


def _xattn_flops(s, hw, c):
    L, W = s["text_len"], s["text_width"]
    f = 2 * _conv_flops(hw, c, c, 1)              # proj_in, proj_out
    f += 2 * hw * c * 3 * c                         # q, k, v of self-attention
    f += 2 * 2 * hw * hw * c                        # scores and weighted sum
    f += 2 * hw * c * c                             # self-attention out
    f += 2 * hw * c * c + 2 * L * W * 2 * c         # cross q; cross k, v
    f += 2 * 2 * hw * L * c                         # cross scores, sum
    f += 2 * hw * c * c                             # cross out
    f += 2 * 2 * hw * c * 4 * c                     # MLP
    return f


def _res_flops(hw, c_in, c_out, t_dim):
    f = _conv_flops(hw, c_in, c_out, 3) + _conv_flops(hw, c_out, c_out, 3)
    f += 2 * t_dim * c_out
    if c_in != c_out:
        f += _conv_flops(hw, c_in, c_out, 1)
    return f


def unet_flops(s: dict) -> int:
    """Operations of one UNet evaluation at batch 1."""
    base, side = s["unet_base"], s["latent_size"]
    t_dim = 4 * base
    chans = [base * m for m in s["unet_mults"]]
    attn = set(s["unet_attn_levels"])
    f = 2 * base * t_dim + 2 * t_dim * t_dim
    hw = side * side
    f += _conv_flops(hw, s["latent_channels"], base, 3)
    skips, c_prev = [base], base
    for lvl, c in enumerate(chans):
        hw = (side >> lvl) ** 2
        for _ in range(s["unet_res_blocks"]):
            f += _res_flops(hw, c_prev, c, t_dim)
            if lvl in attn:
                f += _xattn_flops(s, hw, c)
            c_prev = c
            skips.append(c)
        if lvl < len(chans) - 1:
            f += _conv_flops((side >> (lvl + 1)) ** 2, c, c, 3)
            skips.append(c)
    hw = (side >> (len(chans) - 1)) ** 2
    f += 2 * _res_flops(hw, c_prev, c_prev, t_dim) + _xattn_flops(s, hw, c_prev)
    for lvl in reversed(range(len(chans))):
        c = chans[lvl]
        hw = (side >> lvl) ** 2
        for _ in range(s["unet_res_blocks"] + 1):
            f += _res_flops(hw, c_prev + skips.pop(), c, t_dim)
            if lvl in attn:
                f += _xattn_flops(s, hw, c)
            c_prev = c
        if lvl > 0:
            f += _conv_flops((side >> (lvl - 1)) ** 2, c, c, 3)
    f += _conv_flops(side * side, base, s["latent_channels"], 3)
    return f


def text_flops(s: dict) -> int:
    """Operations of encoding one prompt."""
    L, d = s["text_len"], s["text_width"]
    per = 2 * L * d * 3 * d + 2 * 2 * L * L * d + 2 * L * d * d \
        + 2 * 2 * L * d * 4 * d
    return s["text_layers"] * per


def group_flops(s: dict, n_cloud: int, batch: int) -> int:
    """Operations of one served group: both prompts of each request encoded,
    and n_cloud guided steps of two UNet evaluations each."""
    return batch * (2 * text_flops(s) + 2 * n_cloud * unet_flops(s))
