"""Per-kernel validation: shape/dtype sweeps, assert_allclose vs ref.py."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.kernels import ops, ref

rng = np.random.default_rng(42)


def _n(*shape, dtype=np.float32, gen=rng):
    return jnp.asarray(gen.normal(size=shape), dtype)


#: Cases with whole kv tiles outside the kernel's band at both ends.  They
#: draw from a generator of their own, so the module's generator gives the
#: tests after ``test_flash_attention`` the inputs it gave them before.
BAND_CASES = [
    # h2o-danube-1.8b's head ratio and head_dim: 4 of 8 kv blocks per
    # query block
    (1, 1024, 1024, 8, 2, 80, True, 384),
    (1, 512, 512, 4, 2, 64, True, 0),       # causal only: steps past the diagonal
    (1, 512, 512, 2, 1, 64, False, 200),    # window only: no diagonal
]
band_rng = np.random.default_rng(16)


@pytest.mark.parametrize("B,Sq,Skv,Hq,Hkv,D,causal,win", [
    (2, 256, 256, 4, 2, 64, True, 0),
    (1, 128, 384, 8, 8, 128, True, 0),
    (2, 256, 256, 4, 1, 80, True, 64),      # MQA + window + padded head_dim
    (1, 128, 128, 2, 2, 128, False, 0),     # non-causal (cross-attn)
    (1, 512, 512, 3, 3, 64, True, 128),     # odd heads
    (1, 256, 256, 2, 2, 40, False, 0),      # diffusion self-attn, padded d
] + BAND_CASES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention(B, Sq, Skv, Hq, Hkv, D, causal, win, dtype):
    band = (B, Sq, Skv, Hq, Hkv, D, causal, win) in BAND_CASES
    gen = band_rng if band else rng
    q = _n(B, Sq, Hq, D, dtype=dtype, gen=gen)
    k = _n(B, Skv, Hkv, D, dtype=dtype, gen=gen)
    v = _n(B, Skv, Hkv, D, dtype=dtype, gen=gen)
    o = ops.flash_attention(q, k, v, causal=causal, window=win, bq=128,
                            bk=128)
    qf = q.transpose(0, 2, 1, 3).reshape(B * Hq, Sq, D)
    kf = k.transpose(0, 2, 1, 3).reshape(B * Hkv, Skv, D)
    vf = v.transpose(0, 2, 1, 3).reshape(B * Hkv, Skv, D)
    want = ref.flash_attention_ref(qf, kf, vf, causal=causal, window=win)
    want = want.reshape(B, Hq, Sq, D).transpose(0, 2, 1, 3)
    tol = 5e-6 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(o, np.float32),
                               np.asarray(want, np.float32), atol=tol,
                               rtol=tol)


@pytest.mark.parametrize("B,Skv,Hq,Hkv,D", [
    (4, 512, 8, 2, 64), (2, 384, 4, 4, 128), (3, 512, 16, 1, 80),
])
def test_decode_attention(B, Skv, Hq, Hkv, D):
    q = _n(B, 1, Hq, D)
    k = _n(B, Skv, Hkv, D)
    v = _n(B, Skv, Hkv, D)
    lens = jnp.asarray(rng.integers(1, Skv, size=B), jnp.int32)
    o = ops.decode_attention(q, k, v, lens, bk=128)
    G = Hq // Hkv
    qf = q[:, 0].reshape(B, Hkv, G, D).reshape(B * Hkv, G, D)
    kf = k.transpose(0, 2, 1, 3).reshape(B * Hkv, Skv, D)
    vf = v.transpose(0, 2, 1, 3).reshape(B * Hkv, Skv, D)
    lf = jnp.repeat(lens[:, None], Hkv, 1).reshape(B * Hkv, 1)
    want = ref.decode_attention_ref(qf, kf, vf, lf).reshape(B, Hq, D)[:, None]
    np.testing.assert_allclose(np.asarray(o), np.asarray(want), atol=5e-6)


@pytest.mark.parametrize("B,S,W", [(2, 128, 256), (1, 512, 128), (3, 96, 200)])
def test_rglru_scan(B, S, W):
    a = jnp.asarray(rng.uniform(0.8, 0.999, size=(B, S, W)), jnp.float32)
    b = _n(B, S, W)
    h0 = _n(B, W)
    got = ops.rglru_scan(a, b, h0)
    want = ref.rglru_scan_ref(a, b, h0)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)


@pytest.mark.parametrize("b,S,H,P,G,N,Q", [
    (1, 256, 4, 64, 1, 128, 128), (2, 128, 8, 64, 2, 64, 64),
    (1, 512, 2, 32, 1, 16, 128),
])
def test_ssd_scan(b, S, H, P, G, N, Q):
    x = _n(b, S, H, P)
    dt = jnp.asarray(rng.uniform(0.001, 0.1, size=(b, S, H)), jnp.float32)
    A = -jnp.asarray(rng.uniform(0.5, 2, size=(H,)), jnp.float32)
    Bm, Cm = _n(b, S, G, N), _n(b, S, G, N)
    st = _n(b, H, P, N)
    y, f = ops.ssd_scan(x, dt, A, Bm, Cm, chunk_size=Q, init_state=st)
    yr, fr = ref.ssd_scan_ref(x, dt, A, Bm, Cm, chunk_size=Q, init_state=st)
    np.testing.assert_allclose(np.asarray(y), np.asarray(yr), atol=2e-4)
    np.testing.assert_allclose(np.asarray(f), np.asarray(fr), atol=2e-5)


@pytest.mark.parametrize("T,d", [(100, 333), (256, 64), (7, 1024)])
def test_int8_quantize(T, d):
    x = _n(T, d)
    q, s = ops.int8_quantize(x)
    qr, sr = ref.int8_quantize_ref(x)
    assert int(jnp.max(jnp.abs(q.astype(jnp.int32) - qr.astype(jnp.int32)))) == 0
    np.testing.assert_allclose(np.asarray(s), np.asarray(sr), rtol=1e-6)
    # round-trip error bounded by scale/2 per element
    back = ops.int8_dequantize(q, s)
    err = jnp.max(jnp.abs(back - x))
    assert float(err) <= float(jnp.max(s)) * 0.5 + 1e-6


def test_flash_custom_vjp_grads():
    B, S, Hq, Hkv, D = 2, 256, 4, 2, 64
    from repro.models import attention as at
    q, k, v = _n(B, S, Hq, D), _n(B, S, Hkv, D), _n(B, S, Hkv, D)
    pos = jnp.arange(S)

    def ref_loss(q, k, v):
        o = at.attention_einsum(q, k, v, q_positions=pos, kv_positions=pos,
                                causal=True, window=0)
        return jnp.sum(jnp.tanh(o))

    def flash_loss(q, k, v):
        return jnp.sum(jnp.tanh(at.flash_self_attention(q, k, v, True, 0, 64)))

    r, gr = jax.value_and_grad(ref_loss, argnums=(0, 1, 2))(q, k, v)
    f, gf = jax.value_and_grad(flash_loss, argnums=(0, 1, 2))(q, k, v)
    # The loss is a sum over B*S*Hq*D = 131072 fp32 values with |sum| ~1e3;
    # the chunked online softmax accumulates in a different order than the
    # one-shot softmax, so the two sums differ by O(|sum| * eps * sqrt(N))
    # ~ 1e-4 — a relative comparison is the meaningful one here.
    assert abs(float(r - f)) < 1e-6 * max(1.0, abs(float(r)))
    for a, b in zip(gr, gf):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-5)


def test_self_attention_without_kernel_keeps_scan_vjp():
    """With no kernel hook (what training passes, the registry being for
    forward-only callers) the flash-length dispatch differentiates
    through the scan's custom VJP and matches the einsum's gradients."""
    B, S, Hq, Hkv, D = 1, 256, 4, 2, 64
    from repro.models import attention as at
    gen = np.random.default_rng(17)
    q = _n(B, S, Hq, D, gen=gen)
    k, v = _n(B, S, Hkv, D, gen=gen), _n(B, S, Hkv, D, gen=gen)
    pos = jnp.arange(S)

    def ref_loss(q, k, v):
        o = at.attention_einsum(q, k, v, q_positions=pos, kv_positions=pos,
                                causal=True, window=96)
        return jnp.sum(jnp.tanh(o))

    def dispatch_loss(q, k, v):
        return jnp.sum(jnp.tanh(at.self_attention(
            q, k, v, causal=True, window=96, chunk_size=64,
            flash_min_len=S, kernel=None)))

    assert "custom_vjp_call" in str(jax.make_jaxpr(dispatch_loss)(q, k, v))
    r, gr = jax.value_and_grad(ref_loss, argnums=(0, 1, 2))(q, k, v)
    f, gf = jax.value_and_grad(dispatch_loss, argnums=(0, 1, 2))(q, k, v)
    # a sum of 65536 fp32 values in two orders: eps * sqrt(N) = 3e-5
    np.testing.assert_allclose(float(f), float(r), rtol=3e-5)
    for a, b in zip(gr, gf):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-5)


@pytest.mark.parametrize("T,br", [(509, 256), (1, 8), (130, 64)])
def test_int8_quantize_raw_kernel_ragged_rows(T, br):
    """Regression: the raw Pallas kernel used to ``assert T % br == 0``
    (a crash at any prime T); it now zero-pads to the block grid and
    trims, and pad rows never contaminate the real per-row scales."""
    from repro.kernels import int8_quant as q8
    x = _n(T, 64)
    q, s = q8.int8_quantize(jnp.asarray(x), br=br, interpret=True)
    assert q.shape == (T, 64) and s.shape == (T, 1)
    qr, sr = ref.int8_quantize_ref(jnp.asarray(x))
    assert int(jnp.max(jnp.abs(q.astype(jnp.int32)
                               - qr.astype(jnp.int32)))) == 0
    np.testing.assert_allclose(np.asarray(s), np.asarray(sr), rtol=1e-6)


@given(st.integers(1, 300), st.integers(1, 96), st.integers(0, 5))
@settings(max_examples=25, deadline=None)
def test_int8_quantize_roundtrip_bound_property(T, d, seed):
    """Per-row symmetric int8: |x - deq| <= scale/2 per element, at ANY
    row count (the ragged-grid path included)."""
    rng = np.random.default_rng(seed)
    x = jnp.asarray((rng.standard_normal((T, d)) * 7).astype(np.float32))
    q, s = ops.int8_quantize(x)
    back = ops.int8_dequantize(q, s)
    assert bool(jnp.all(jnp.abs(back - x) <= s * 0.5 + 1e-6))
