"""Attention: GQA/MQA/MHA, causal / sliding-window / cross, chunked softmax.

Two execution paths with identical math:
  * ``attention_einsum`` — plain einsum; fine for short sequences and decode.
  * ``attention_chunked`` — lax.scan over KV chunks with an online softmax;
    never materializes the (Sq, Skv) score matrix.  This is the memory-safe
    path for 32k prefill and the pure-JAX mirror of the Pallas flash kernel
    (``repro.kernels.flash_attention``).

Shapes: q (B, Sq, Hq, D); k, v (B, Skv, Hkv, D) with Hq % Hkv == 0.
Positions are explicit so that decode (Sq=1, arbitrary offset) and ring/SWA
caches reuse the same masking logic.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

NEG_INF = -1e30


def _mask(q_pos, kv_pos, *, causal: bool, window: int, kv_valid=None):
    """Boolean mask (..., Sq, Skv): True = attend."""
    m = jnp.ones(q_pos.shape[:-1] + (q_pos.shape[-1], kv_pos.shape[-1]), bool)
    qp = q_pos[..., :, None]
    kp = kv_pos[..., None, :]
    if causal:
        m &= kp <= qp
    if window:
        m &= kp > qp - window
    if kv_valid is not None:
        m &= kv_valid[..., None, :]
    return m


def _gqa_scores(q, k):
    """q (B,Sq,Hkv,G,D) x k (B,Skv,Hkv,D) -> (B,Hkv,G,Sq,Skv) in fp32."""
    return jnp.einsum("bqhgd,bkhd->bhgqk", q, k, preferred_element_type=jnp.float32)


def attention_einsum(q, k, v, *, q_positions, kv_positions, causal=True,
                     window=0, kv_valid=None, softmax_scale=None):
    B, Sq, Hq, D = q.shape
    Hkv = k.shape[2]
    G = Hq // Hkv
    scale = softmax_scale if softmax_scale is not None else D ** -0.5
    qg = q.reshape(B, Sq, Hkv, G, D)
    s = _gqa_scores(qg, k) * scale                       # (B,Hkv,G,Sq,Skv)
    mask = _mask(q_positions, kv_positions, causal=causal, window=window,
                 kv_valid=kv_valid)                      # (B?,Sq,Skv)
    mask = mask[..., None, None, :, :] if mask.ndim == 2 else mask[:, None, None]
    s = jnp.where(mask, s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhgqk,bkhd->bqhgd", p.astype(v.dtype), v)
    return o.reshape(B, Sq, Hq, D)


def attention_chunked(q, k, v, *, q_positions, kv_positions, causal=True,
                      window=0, kv_valid=None, softmax_scale=None,
                      chunk_size=1024):
    """Online-softmax attention, scanning over KV chunks (flash-style)."""
    B, Sq, Hq, D = q.shape
    Skv, Hkv = k.shape[1], k.shape[2]
    G = Hq // Hkv
    scale = softmax_scale if softmax_scale is not None else D ** -0.5
    chunk = min(chunk_size, Skv)
    n_chunks = -(-Skv // chunk)
    pad = n_chunks * chunk - Skv
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
        kv_positions = jnp.pad(kv_positions, ((0, pad),), constant_values=-1)
        valid_pad = jnp.arange(n_chunks * chunk) < Skv
        kv_valid = valid_pad if kv_valid is None else jnp.pad(kv_valid, ((0, 0), (0, pad))) & valid_pad

    qg = q.reshape(B, Sq, Hkv, G, D)
    k_chunks = k.reshape(B, n_chunks, chunk, Hkv, D).transpose(1, 0, 2, 3, 4)
    v_chunks = v.reshape(B, n_chunks, chunk, Hkv, D).transpose(1, 0, 2, 3, 4)
    kp_chunks = kv_positions.reshape(n_chunks, chunk)
    if kv_valid is None:
        kvv_chunks = jnp.ones((n_chunks, 1, chunk), bool)
    elif kv_valid.ndim == 1:
        kvv_chunks = kv_valid.reshape(n_chunks, 1, chunk)
    else:
        kvv_chunks = kv_valid.reshape(B, n_chunks, chunk).transpose(1, 0, 2)

    def step(carry, xs):
        m_prev, l_prev, acc = carry
        kc, vc, kpc, kvc = xs
        s = _gqa_scores(qg, kc) * scale                  # (B,Hkv,G,Sq,chunk)
        msk = _mask(q_positions, kpc, causal=causal, window=window)
        msk = msk & kvc[..., None, :]
        s = jnp.where(msk[:, None, None] if msk.ndim == 3 else msk[None, None, None], s, NEG_INF)
        m_cur = jnp.max(s, axis=-1)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new[..., None])
        corr = jnp.exp(m_prev - m_new)
        l_new = l_prev * corr + jnp.sum(p, axis=-1)
        acc = acc * corr[..., None] + jnp.einsum(
            "bhgqk,bkhd->bhgqd", p, vc.astype(jnp.float32))
        return (m_new, l_new, acc), None

    m0 = jnp.full((B, Hkv, G, Sq), NEG_INF, jnp.float32)
    l0 = jnp.zeros((B, Hkv, G, Sq), jnp.float32)
    a0 = jnp.zeros((B, Hkv, G, Sq, D), jnp.float32)
    qpb = jnp.broadcast_to(q_positions, (B, Sq)) if q_positions.ndim == 1 else q_positions
    (m, l, acc), _ = jax.lax.scan(step, (m0, l0, a0),
                                  (k_chunks, v_chunks, kp_chunks, kvv_chunks))
    del m, qpb
    o = acc / jnp.maximum(l[..., None], 1e-30)
    return o.transpose(0, 3, 1, 2, 4).reshape(B, Sq, Hq, D).astype(q.dtype)


def attend(q, k, v, *, q_positions, kv_positions, causal=True, window=0,
           kv_valid=None, chunked=None, chunk_size=1024):
    """Dispatch: chunked for long KV (memory-safe), einsum otherwise."""
    if chunked is None:
        chunked = k.shape[1] > 2048 and q.shape[1] > 1
    fn = attention_chunked if chunked else attention_einsum
    kwargs = dict(q_positions=q_positions, kv_positions=kv_positions,
                  causal=causal, window=window, kv_valid=kv_valid)
    if chunked:
        kwargs["chunk_size"] = chunk_size
    return fn(q, k, v, **kwargs)


# ==========================================================================
# Flash self-attention with a custom VJP (memory-efficient backward).
#
# lax.scan's automatic backward saves every per-chunk residual — for a
# (B, H, Sq, chunk) fp32 score tensor that is chunks x 2.4 GB of saved
# state per layer, which blows the 16 GB/chip HBM budget at 4k train.
# The flash backward recomputes scores per kv-chunk from (q, k, v, o, lse),
# so the live set stays O(one chunk).  Positions are implicit arange(S)
# (training self-attention); decode/cross paths don't differentiate.
# ==========================================================================
def _flash_fwd_scan(q, k, v, causal, window, chunk):
    with jax.named_scope("flash_attention"):
        return _flash_fwd_scan_impl(q, k, v, causal, window, chunk)


def _flash_fwd_scan_impl(q, k, v, causal, window, chunk):
    B, Sq, Hq, D = q.shape
    Skv, Hkv = k.shape[1], k.shape[2]
    G = Hq // Hkv
    scale = D ** -0.5
    n_chunks = Skv // chunk
    qg = q.reshape(B, Sq, Hkv, G, D)
    k_chunks = k.reshape(B, n_chunks, chunk, Hkv, D).transpose(1, 0, 2, 3, 4)
    v_chunks = v.reshape(B, n_chunks, chunk, Hkv, D).transpose(1, 0, 2, 3, 4)
    q_pos = jnp.arange(Sq)

    def step(carry, xs):
        m_prev, l_prev, acc = carry
        ci, kc, vc = xs
        s = _gqa_scores(qg, kc) * scale                # (B,Hkv,G,Sq,chunk)
        kp = ci * chunk + jnp.arange(chunk)
        msk = _mask(q_pos, kp, causal=causal, window=window)
        s = jnp.where(msk[None, None, None], s, NEG_INF)
        m_cur = jnp.max(s, axis=-1)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new[..., None])
        corr = jnp.exp(m_prev - m_new)
        l_new = l_prev * corr + jnp.sum(p, axis=-1)
        acc = acc * corr[..., None] + jnp.einsum(
            "bhgqk,bkhd->bhgqd", p, vc.astype(jnp.float32))
        return (m_new, l_new, acc), None

    m0 = jnp.full((B, Hkv, G, Sq), NEG_INF, jnp.float32)
    l0 = jnp.zeros((B, Hkv, G, Sq), jnp.float32)
    a0 = jnp.zeros((B, Hkv, G, Sq, D), jnp.float32)
    (m, l, acc), _ = jax.lax.scan(
        step, (m0, l0, a0),
        (jnp.arange(n_chunks), k_chunks, v_chunks))
    l = jnp.maximum(l, 1e-30)
    o = (acc / l[..., None])
    lse = m + jnp.log(l)                               # (B,Hkv,G,Sq)
    out = o.transpose(0, 3, 1, 2, 4).reshape(B, Sq, Hq, D).astype(q.dtype)
    return out, (o, lse)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def flash_self_attention(q, k, v, causal=True, window=0, chunk_size=1024):
    """q (B,Sq,Hq,D); k,v (B,Skv,Hkv,D); positions implicit arange."""
    chunk = min(chunk_size, k.shape[1])
    assert k.shape[1] % chunk == 0
    out, _ = _flash_fwd_scan(q, k, v, causal, window, chunk)
    return out


def _flash_fwd_rule(q, k, v, causal, window, chunk_size):
    chunk = min(chunk_size, k.shape[1])
    out, (o, lse) = _flash_fwd_scan(q, k, v, causal, window, chunk)
    return out, (q, k, v, o, lse)


def _flash_bwd_rule(causal, window, chunk_size, res, dout):
    with jax.named_scope("flash_attention_bwd"):
        return _flash_bwd_impl(causal, window, chunk_size, res, dout)


def _flash_bwd_impl(causal, window, chunk_size, res, dout):
    q, k, v, o, lse = res
    B, Sq, Hq, D = q.shape
    Skv, Hkv = k.shape[1], k.shape[2]
    G = Hq // Hkv
    scale = D ** -0.5
    chunk = min(chunk_size, Skv)
    n_chunks = Skv // chunk
    qg = q.reshape(B, Sq, Hkv, G, D).astype(jnp.float32)
    dog = dout.reshape(B, Sq, Hkv, G, D).astype(jnp.float32)
    dog = dog.transpose(0, 2, 3, 1, 4)                 # (B,Hkv,G,Sq,D)
    delta = jnp.sum(dog * o, axis=-1)                  # (B,Hkv,G,Sq)
    k_chunks = k.reshape(B, n_chunks, chunk, Hkv, D).transpose(1, 0, 2, 3, 4)
    v_chunks = v.reshape(B, n_chunks, chunk, Hkv, D).transpose(1, 0, 2, 3, 4)
    q_pos = jnp.arange(Sq)

    def step(dq_acc, xs):
        ci, kc, vc = xs
        kcf = kc.astype(jnp.float32)
        vcf = vc.astype(jnp.float32)
        s = jnp.einsum("bqhgd,bkhd->bhgqk", qg, kcf) * scale
        kp = ci * chunk + jnp.arange(chunk)
        msk = _mask(q_pos, kp, causal=causal, window=window)
        s = jnp.where(msk[None, None, None], s, NEG_INF)
        p = jnp.exp(s - lse[..., None])                # (B,Hkv,G,Sq,chunk)
        dv_c = jnp.einsum("bhgqk,bhgqd->bkhd", p, dog)
        dp = jnp.einsum("bhgqd,bkhd->bhgqk", dog, vcf)
        ds = p * (dp - delta[..., None]) * scale
        dq_acc = dq_acc + jnp.einsum("bhgqk,bkhd->bqhgd", ds, kcf)
        dk_c = jnp.einsum("bhgqk,bqhgd->bkhd", ds, qg)
        return dq_acc, (dk_c, dv_c)

    dq0 = jnp.zeros((B, Sq, Hkv, G, D), jnp.float32)
    dq, (dk_c, dv_c) = jax.lax.scan(
        step, dq0, (jnp.arange(n_chunks), k_chunks, v_chunks))
    dq = dq.reshape(B, Sq, Hq, D).astype(q.dtype)
    dk = dk_c.transpose(1, 0, 2, 3, 4).reshape(B, Skv, Hkv, D).astype(k.dtype)
    dv = dv_c.transpose(1, 0, 2, 3, 4).reshape(B, Skv, Hkv, D).astype(v.dtype)
    return dq, dk, dv


flash_self_attention.defvjp(_flash_fwd_rule, _flash_bwd_rule)


#: Self-attention over at least this many tokens takes the flash path.
FLASH_MIN_LEN = 2048
#: The (q, kv) block pair of the Pallas flash kernel on the LM's
#: forward-only path: the fastest of those measured on a v5e at
#: h2o-danube-1.8b's shapes (GQA 32/8, head_dim 80, 8192 tokens, batch 1
#: and 2).
FLASH_BLOCKS = (512, 512)


def takes_flash(seq_len: int, chunk_size: int = 1024,
                flash_min_len: int = FLASH_MIN_LEN) -> bool:
    """Whether ``self_attention`` over ``seq_len`` tokens takes the flash
    path (the kernel hook or the scan) rather than the einsum."""
    return (seq_len >= flash_min_len
            and seq_len % min(chunk_size, seq_len) == 0)


def self_attention(q, k, v, *, causal=True, window=0, chunk_size=1024,
                   flash_min_len: int = FLASH_MIN_LEN, kernel=None):
    """Training/prefill self-attention dispatch: flash for long sequences,
    einsum for short.

    The flash path is ``kernel`` where a forward-only caller passes one
    (``kernels.ops.flash_attention`` on the TPU: scores stay in VMEM and
    tiles outside the causal window are skipped), else the jnp scan with
    its custom VJP (memory-efficient backward), which training needs."""
    S = q.shape[1]
    if takes_flash(S, chunk_size, flash_min_len):
        if kernel is not None:
            with jax.named_scope("flash_attention"):
                return kernel(q, k, v, causal=causal, window=window,
                              bq=FLASH_BLOCKS[0], bk=FLASH_BLOCKS[1])
        return flash_self_attention(q, k, v, causal, window, chunk_size)
    pos = jnp.arange(S)
    return attention_einsum(q, k, v, q_positions=pos, kv_positions=pos,
                            causal=causal, window=window)


# --------------------------------------------------------------------------
# KV caches
# --------------------------------------------------------------------------
def init_kv_cache(batch: int, max_len: int, n_kv: int, head_dim: int, dtype,
                  quantized: bool = False):
    if quantized:
        # int8 per-(position, head)-row symmetric quantization: halves the
        # dominant decode HBM term (cache reads) at <0.5% logit error.
        return {
            "k": jnp.zeros((batch, max_len, n_kv, head_dim), jnp.int8),
            "v": jnp.zeros((batch, max_len, n_kv, head_dim), jnp.int8),
            "k_scale": jnp.zeros((batch, max_len, n_kv, 1), jnp.float32),
            "v_scale": jnp.zeros((batch, max_len, n_kv, 1), jnp.float32),
        }
    return {
        "k": jnp.zeros((batch, max_len, n_kv, head_dim), dtype),
        "v": jnp.zeros((batch, max_len, n_kv, head_dim), dtype),
    }


def _quantize_rows(x):
    """x (..., D) -> (int8 values, f32 scales (..., 1))."""
    s = jnp.maximum(jnp.max(jnp.abs(x.astype(jnp.float32)), axis=-1,
                            keepdims=True) / 127.0, 1e-12)
    q = jnp.clip(jnp.round(x.astype(jnp.float32) / s), -127, 127)
    return q.astype(jnp.int8), s


def dequantize_cache(cache):
    """-> (k, v) as fp32 (from int8+scales or passthrough)."""
    if "k_scale" in cache:
        k = cache["k"].astype(jnp.float32) * cache["k_scale"]
        v = cache["v"].astype(jnp.float32) * cache["v_scale"]
        return k, v
    return cache["k"], cache["v"]


def _maybe_quantize_new(cache, k_new, v_new):
    if "k_scale" in cache:
        kq, ks = _quantize_rows(k_new)
        vq, vs = _quantize_rows(v_new)
        return (kq, ks), (vq, vs)
    return (k_new, None), (v_new, None)


def _write(cache, slot, kq, ks, vq, vs):
    out = dict(cache)
    out["k"] = jax.lax.dynamic_update_slice_in_dim(cache["k"], kq, slot,
                                                   axis=1)
    out["v"] = jax.lax.dynamic_update_slice_in_dim(cache["v"], vq, slot,
                                                   axis=1)
    if ks is not None:
        out["k_scale"] = jax.lax.dynamic_update_slice_in_dim(
            cache["k_scale"], ks, slot, axis=1)
        out["v_scale"] = jax.lax.dynamic_update_slice_in_dim(
            cache["v_scale"], vs, slot, axis=1)
    return out


def cache_update_ring(cache, k_new, v_new, position):
    """Write one step into a ring buffer of length W (SWA / local attention).

    position: scalar int32 — the *global* position of the new token.
    Returns updated cache; slot = position % W.
    """
    W = cache["k"].shape[1]
    slot = jnp.mod(position, W)
    (kq, ks), (vq, vs) = _maybe_quantize_new(cache, k_new, v_new)
    return _write(cache, slot, kq, ks, vq, vs)


def ring_positions(window: int, position):
    """Global position held in each ring slot at decode step `position`.

    Slot s holds global index: the latest p <= position with p % W == s.
    Slots not yet written (p < 0) are masked by validity.
    """
    slots = jnp.arange(window)
    cur_slot = jnp.mod(position, window)
    delta = jnp.mod(cur_slot - slots, window)
    pos = position - delta
    return pos, pos >= 0  # (positions, valid)


def cache_update_linear(cache, k_new, v_new, position):
    """Write one step into a full-length cache at index `position`."""
    (kq, ks), (vq, vs) = _maybe_quantize_new(cache, k_new, v_new)
    return _write(cache, position, kq, ks, vq, vs)


@functools.partial(jax.jit, static_argnums=())
def _noop(x):
    return x
