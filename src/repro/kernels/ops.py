"""jit'd public wrappers around the Pallas kernels.

Responsibilities:
  * backend dispatch — ``interpret=True`` off-TPU (CPU validation mode),
    compiled Pallas on TPU;
  * hardware alignment — pad head_dim to a multiple of 128 (MXU lanes) and
    sequence to the block size, then slice back;
  * layout adaptation — models use (B, S, H, D); kernels use (B*H, S, D)
    with head minor.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import decode_attention as _dec
from repro.kernels import flash_attention as _fa
from repro.kernels import int8_quant as _q8
from repro.kernels import rglru_scan as _lru
from repro.kernels import ssd_scan as _ssd


def on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def _interpret() -> bool:
    return not on_tpu()


def _pad_to(x, axis: int, multiple: int):
    size = x.shape[axis]
    pad = (-size) % multiple
    if pad == 0:
        return x, size
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths), size


# --------------------------------------------------------------------------
# Flash attention (prefill): model layout (B, S, H, D)
# --------------------------------------------------------------------------
@functools.partial(jax.jit, static_argnames=("causal", "window", "bq", "bk"))
def flash_attention(q, k, v, *, causal=True, window=0, bq=512, bk=512):
    B, Sq, Hq, D = q.shape
    _, Skv, Hkv, _ = k.shape
    scale = D ** -0.5
    qf = q.transpose(0, 2, 1, 3).reshape(B * Hq, Sq, D)
    kf = k.transpose(0, 2, 1, 3).reshape(B * Hkv, Skv, D)
    vf = v.transpose(0, 2, 1, 3).reshape(B * Hkv, Skv, D)
    qf, _ = _pad_to(qf, 2, 128)
    kf, _ = _pad_to(kf, 2, 128)
    vf, _ = _pad_to(vf, 2, 128)
    qf, sq0 = _pad_to(qf, 1, min(bq, max(128, Sq)))
    kf, sk0 = _pad_to(kf, 1, min(bk, max(128, Skv)))
    vf, _ = _pad_to(vf, 1, min(bk, max(128, Skv)))
    o = _fa.flash_attention(
        qf, kf, vf, causal=causal, window=window, kv_len=sk0,
        softmax_scale=scale, bq=min(bq, qf.shape[1]), bk=min(bk, kf.shape[1]),
        interpret=_interpret())
    o = o[:, :Sq, :D]
    return o.reshape(B, Hq, Sq, D).transpose(0, 2, 1, 3)


# --------------------------------------------------------------------------
# Decode attention: model layout q (B, 1, Hq, D), cache (B, Skv, Hkv, D)
# --------------------------------------------------------------------------
@functools.partial(jax.jit, static_argnames=("bk",))
def decode_attention(q, k, v, lengths, *, bk=512):
    """lengths (B,) int32 — valid KV length per sequence."""
    B, one, Hq, D = q.shape
    _, Skv, Hkv, _ = k.shape
    G = Hq // Hkv
    scale = D ** -0.5
    qf = q[:, 0].reshape(B, Hkv, G, D).reshape(B * Hkv, G, D)
    kf = k.transpose(0, 2, 1, 3).reshape(B * Hkv, Skv, D)
    vf = v.transpose(0, 2, 1, 3).reshape(B * Hkv, Skv, D)
    qf, _ = _pad_to(qf, 2, 128)
    kf, _ = _pad_to(kf, 2, 128)
    vf, _ = _pad_to(vf, 2, 128)
    kf, sk0 = _pad_to(kf, 1, min(bk, max(128, Skv)))
    vf, _ = _pad_to(vf, 1, min(bk, max(128, Skv)))
    lens = jnp.repeat(lengths[:, None], Hkv, axis=1).reshape(B * Hkv, 1)
    lens = jnp.minimum(lens, sk0).astype(jnp.int32)
    o = _dec.decode_attention(qf, kf, vf, lens, softmax_scale=scale,
                              bk=min(bk, kf.shape[1]), interpret=_interpret())
    o = o[:, :, :D].reshape(B, Hkv * G, D)
    return o[:, None]  # (B, 1, Hq, D)


# --------------------------------------------------------------------------
# RG-LRU scan: (B, S, W) fp32 — drop-in for models.rglru.lru_scan_ref
# --------------------------------------------------------------------------
@functools.partial(jax.jit, static_argnames=("bt", "bc"))
def rglru_scan(a, b, h0=None, *, bt=256, bc=512):
    B, S, W = a.shape
    if h0 is None:
        h0 = jnp.zeros((B, W), jnp.float32)
    a_p, s0 = _pad_to(a, 1, min(bt, max(8, S)))
    b_p, _ = _pad_to(b, 1, min(bt, max(8, S)))
    a_p, w0 = _pad_to(a_p, 2, min(bc, max(128, W)))
    b_p, _ = _pad_to(b_p, 2, min(bc, max(128, W)))
    h0_p, _ = _pad_to(h0, 1, min(bc, max(128, W)))
    out = _lru.rglru_scan(a_p, b_p, h0_p, bt=min(bt, a_p.shape[1]),
                          bc=min(bc, a_p.shape[2]), interpret=_interpret())
    return out[:, :S, :W]


# --------------------------------------------------------------------------
# SSD scan — drop-in for models.ssd.ssd_chunked_ref
# --------------------------------------------------------------------------
@functools.partial(jax.jit, static_argnames=("chunk_size",))
def ssd_scan(x, dt, A, Bm, Cm, *, chunk_size=128, init_state=None):
    return _ssd.ssd_scan(x, dt, A, Bm, Cm, chunk_size=chunk_size,
                         init_state=init_state, interpret=_interpret())


# --------------------------------------------------------------------------
# int8 boundary quantization
# --------------------------------------------------------------------------
@functools.partial(jax.jit, static_argnames=("br",))
def int8_quantize(x, *, br=256):
    T, d = x.shape
    x_p, t0 = _pad_to(x, 0, min(br, max(8, T)))
    q, s = _q8.int8_quantize(x_p, br=min(br, x_p.shape[0]),
                             interpret=_interpret())
    return q[:T], s[:T]


int8_dequantize = _q8.int8_dequantize


def kernel_registry():
    """kernel_fn overrides for models.transformer (TPU path, forward only:
    none of these kernels has a backward)."""
    return {"rglru": rglru_scan, "ssd": ssd_scan, "attn": flash_attention}
