"""Flash attention (prefill) Pallas TPU kernel.

Online-softmax attention with explicit VMEM tiling:
  grid = (batch * q_heads, q_blocks, kv_blocks)   (kv innermost)
  q block   (1, bq, d)   VMEM
  k/v block (1, bk, d)   VMEM, indexed to the matching GQA kv head
  scratch   acc (bq, d) f32, m (bq, 128) f32, l (bq, 128) f32 — persist
            across the kv grid dimension (canonical TPU flash pattern).

Causal and sliding-window masks are applied per tile.  With either set
the kv grid axis spans only the widest band of kv blocks that one query
block can see (``_band``): step ``ki`` of query block ``qi`` visits kv
block ``lo(qi) + ki``, clamped to ``hi(qi)``.  A step past the band keeps
the resident k/v blocks (same block index, so no copy) and does no MXU
work (``pl.when``).  Without either the grid covers every kv block.
GQA is handled in the k/v index_map (kv_head = q_head // group), so no
materialized head repetition.

Precision: both dots take the input dtype as their operands and
accumulate in fp32 (bf16 inputs give single-pass bf16 MXU dots, fp32
inputs fp32 dots); the running max, the row sums and the accumulator are
fp32, and the probabilities are cast to the operand dtype only for the
PV dot.  Tiles need no mask when nothing is causal, windowed or padded.

Hardware alignment: bq/bk default 512/512; d must be padded to a multiple
of 128 by the ops.py wrapper (MXU lane width).  On a v5e, non-causal
attention over 4096 tokens at head_dim 40 (padded) took 0.60 ms per
8-head image at (512, 1024) with (bq, 128) statistics, 0.92 ms with
(bq,) vectors.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
#: lane width of a TPU vector register: the running max and row sums are
#: kept as (bq, LANES) columns with every lane equal, a layout the
#: row reductions and broadcasts take without relayout
LANES = 128


def _widen(col, n: int):
    """A lane-replicated (rows, LANES) column at width ``n``."""
    if n % LANES == 0:
        return jnp.tile(col, (1, n // LANES))
    return col[:, :1]


def _band(qi, *, bq: int, bk: int, n_kv: int, causal: bool, window: int):
    """First and last kv block that query block ``qi`` sees: none left of
    its first query's window, none above its last query's diagonal.
    Takes a Python int (the grid's size) or a traced grid index."""
    static = isinstance(qi, int)
    maximum, minimum = (max, min) if static else (jnp.maximum, jnp.minimum)
    q_start = qi * bq
    lo = maximum(q_start - window + 1, 0) // bk if window else 0
    hi = minimum((q_start + bq - 1) // bk, n_kv - 1) if causal else n_kv - 1
    return lo, hi


def _kernel(q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref, *,
            bq: int, bk: int, n_kv: int, n_steps: int, causal: bool,
            window: int, kv_len: int, scale: float, group: int):
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    # tile bounds in token coordinates
    q_start = qi * bq
    if causal or window:
        # the band holds every tile that meets the mask; steps past it
        # (query blocks whose band is narrower than the grid) compute nothing
        lo, hi = _band(qi, bq=bq, bk=bk, n_kv=n_kv, causal=causal,
                       window=window)
        k_start = (lo + ki) * bk
        run = lo + ki <= hi
    else:
        k_start = ki * bk
        run = jnp.bool_(True)

    @pl.when(run)
    def _compute():
        q = q_ref[0]                                         # (bq, d)
        k = k_ref[0]                                         # (bk, d)
        v = v_ref[0]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if causal or window or kv_len < n_kv * bk:
            q_pos = q_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
            k_pos = k_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
            mask = k_pos < kv_len
            if causal:
                mask = jnp.logical_and(mask, k_pos <= q_pos)
            if window:
                mask = jnp.logical_and(mask, k_pos > q_pos - window)
            s = jnp.where(mask, s, NEG_INF)
        m_prev = m_ref[...]                                  # (bq, LANES)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - _widen(m_new, bk))
        corr = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * corr + jnp.sum(p, axis=1, keepdims=True)
        acc_ref[...] = (acc_ref[...] * _widen(corr, acc_ref.shape[1])
                        + jax.lax.dot_general(
                            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32))
        m_ref[...] = m_new

    @pl.when(ki == n_steps - 1)
    def _finalize():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0] = (acc_ref[...] / _widen(l, acc_ref.shape[1])
                    ).astype(o_ref.dtype)


def flash_attention(q, k, v, *, causal: bool = True, window: int = 0,
                    kv_len: int | None = None, softmax_scale=None,
                    bq: int = 512, bk: int = 512, interpret: bool = False):
    """q (BHq, Sq, d); k, v (BHkv, Skv, d); BHq = B*Hq with Hq % Hkv == 0.

    Layout note: callers fold (batch, head) into the leading dim with head
    minor, i.e. index = b * H + h, so the GQA index map is
    kv_index = (bh // Hq) * Hkv + (bh % Hq) // group.
    """
    BHq, Sq, d = q.shape
    BHkv, Skv, _ = k.shape
    assert BHq % BHkv == 0
    group_total = BHq // BHkv  # Hq // Hkv
    scale = softmax_scale if softmax_scale is not None else d ** -0.5
    bq = min(bq, Sq)
    bk = min(bk, Skv)
    assert Sq % bq == 0 and Skv % bk == 0, (Sq, bq, Skv, bk)
    n_q, n_kv = Sq // bq, Skv // bk
    kv_len = Skv if kv_len is None else kv_len

    if causal or window:
        band = functools.partial(_band, bq=bq, bk=bk, n_kv=n_kv,
                                 causal=causal, window=window)
        n_steps = max(hi - lo + 1 for lo, hi in map(band, range(n_q)))

        def kv_index(bh, qi, ki):
            lo, hi = band(qi)
            return (bh // group_total, jnp.minimum(lo + ki, hi), 0)
    else:
        n_steps = n_kv

        def kv_index(bh, qi, ki):
            return (bh // group_total, ki, 0)

    kernel = functools.partial(
        _kernel, bq=bq, bk=bk, n_kv=n_kv, n_steps=n_steps, causal=causal,
        window=window, kv_len=kv_len, scale=scale, group=group_total)

    return pl.pallas_call(
        kernel,
        grid=(BHq, n_q, n_steps),
        in_specs=[
            pl.BlockSpec((1, bq, d), lambda bh, qi, ki: (bh, qi, 0)),
            pl.BlockSpec((1, bk, d), kv_index),
            pl.BlockSpec((1, bk, d), kv_index),
        ],
        out_specs=pl.BlockSpec((1, bq, d), lambda bh, qi, ki: (bh, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((BHq, Sq, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, d), jnp.float32),
            pltpu.VMEM((bq, LANES), jnp.float32),
            pltpu.VMEM((bq, LANES), jnp.float32),
        ],
        interpret=interpret,
    )(q, k, v)
