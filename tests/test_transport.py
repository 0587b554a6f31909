"""Transport property tests: serialization round trip, quantization error
bounds, lossy channel accounting, transmission-model shape (paper Fig 4)."""
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st
from hypothesis.extra import numpy as hnp

from repro.core.transport import (
    LOCAL_LINK,
    WAN_LINK,
    deserialize,
    lossy_transfer,
    pack_boundary,
    pack_boundary_wire,
    rowwise_dequantize_int8,
    rowwise_quantize_int8,
    serialize,
    transmission_time,
    unpack_boundary,
)

arrays_st = st.sampled_from(
    [np.float32, np.float16, np.int32, np.uint8]).flatmap(
    lambda dt: hnp.arrays(
        dtype=dt,
        shape=hnp.array_shapes(min_dims=1, max_dims=4, max_side=16),
        elements={"allow_nan": False},   # NaN != NaN breaks array_equal
    ))


@given(st.dictionaries(st.text(st.characters(categories=("Ll",)),
                               min_size=1, max_size=8),
                       arrays_st, min_size=1, max_size=4),
       st.booleans())
@settings(max_examples=50, deadline=None)
def test_serialize_roundtrip(tree, compress):
    data = serialize(tree, compress=compress)
    out = deserialize(data)
    assert set(out) == set(tree)
    for k in tree:
        np.testing.assert_array_equal(out[k], tree[k])


def test_boundary_pack_modes():
    rng = np.random.default_rng(0)
    lat = rng.standard_normal((4, 64, 64)).astype(np.float32)
    ctx = rng.standard_normal((2, 77, 768)).astype(np.float32)
    paper = pack_boundary(lat, ctx)
    int8 = pack_boundary_wire(lat, ctx, "int8")
    # paper Table 2: ~296 KB; the int8 wire ~4x smaller on the fp32 part
    assert abs(len(paper) - 296 * 1024) < 4096
    assert len(int8) < len(paper) / 2
    l1, c1 = unpack_boundary(paper)
    np.testing.assert_allclose(l1, lat, atol=1e-6)
    np.testing.assert_allclose(c1, ctx, atol=2e-3)  # fp16 context
    l2, c2 = unpack_boundary(int8)
    assert np.max(np.abs(l2 - lat)) < 0.05  # int8 graceful degradation
    assert np.corrcoef(l2.ravel(), lat.ravel())[0, 1] > 0.999


@given(st.floats(0.0, 0.5), st.integers(0, 3))
@settings(max_examples=30, deadline=None)
def test_lossy_transfer_fraction(p, seed):
    x = np.ones((4096,), np.float32)
    y, lost = lossy_transfer(x, p, seed=seed)
    assert 0.0 <= lost <= 1.0
    np.testing.assert_allclose(np.mean(y == 0.0), lost)


@given(st.integers(1, 10_000_000), st.integers(1, 10_000_000))
@settings(max_examples=100, deadline=None)
def test_transmission_monotone(a, b):
    lo, hi = min(a, b), max(a, b)
    for link in (LOCAL_LINK, WAN_LINK):
        assert transmission_time(hi, link) >= transmission_time(lo, link)


def test_fig4_crossover():
    """LAN wins small transfers (RTT), WAN wins large (bandwidth)."""
    small = 500
    large = 16_000_000
    assert (transmission_time(small, LOCAL_LINK)
            < transmission_time(small, WAN_LINK))
    assert (transmission_time(large, WAN_LINK)
            < transmission_time(large, LOCAL_LINK))


# --------------------------------------------------------------------------
# Serialization edge cases (deterministic twins of the property above,
# pinned on the shapes that have historically broken codecs: empty and
# 0-d tensors, both compression modes)
# --------------------------------------------------------------------------
@pytest.mark.parametrize("compress", [False, True])
def test_serialize_empty_and_0d(compress):
    tree = {"empty": np.zeros((0, 3), np.float32),
            "scalar": np.array(3.25, np.float32),
            "i0d": np.array(-7, np.int32),
            "dense": np.arange(6, dtype=np.float16).reshape(2, 3)}
    out = deserialize(serialize(tree, compress=compress))
    assert set(out) == set(tree)
    for k, v in tree.items():
        assert out[k].dtype == v.dtype and out[k].shape == v.shape
        np.testing.assert_array_equal(out[k], v)


@given(hnp.arrays(np.float32,
                  hnp.array_shapes(min_dims=2, max_dims=2, max_side=48),
                  elements=st.floats(-50, 50, width=32)))
@settings(max_examples=60, deadline=None)
def test_rowwise_int8_error_bound_property(x):
    """Per-row symmetric int8 (the wire-format / Pallas-kernel scheme):
    |x - deq| <= scale/2 per element, each row under ITS OWN scale."""
    q, s = rowwise_quantize_int8(x)
    back = rowwise_dequantize_int8(q, s)
    assert np.all(np.abs(back - x) <= s * 0.5 + 1e-6)


def test_compress_tree_int8_error_monotone_in_magnitude():
    """The distributed gradient compressor's reported MSE grows with
    leaf magnitude: int8 step size is max|leaf|/127, so scaling a leaf
    by c scales the error by ~c^2.  Monotonicity is what the
    error-feedback loop relies on."""
    from repro.distributed.compression import compress_tree_int8
    rng = np.random.default_rng(5)
    base = rng.standard_normal((64, 64)).astype(np.float32)
    errs = []
    for scale in (0.1, 1.0, 10.0, 100.0):
        _, err = compress_tree_int8({"g": base * scale})
        errs.append(float(err))
    assert all(b > a for a, b in zip(errs, errs[1:])), errs
    # and identical-magnitude trees report identical error
    _, e1 = compress_tree_int8({"g": base})
    _, e2 = compress_tree_int8({"g": -base})
    np.testing.assert_allclose(e1, e2, rtol=1e-6)


# --------------------------------------------------------------------------
# Lossy channel + boundary pack edge cases
# --------------------------------------------------------------------------
def test_lossy_transfer_extremes():
    x = np.linspace(-1, 1, 257, dtype=np.float32)
    y0, lost0 = lossy_transfer(x, 0.0, seed=1)
    np.testing.assert_array_equal(y0, x)       # drop_prob 0: identity
    assert lost0 == 0.0
    y1, lost1 = lossy_transfer(x, 1.0, seed=1)
    assert lost1 == 1.0                        # drop_prob 1: all zeros
    np.testing.assert_array_equal(y1, np.zeros_like(x))
    assert y1.dtype == x.dtype


@pytest.mark.parametrize("mode", ["paper", "int8"])
def test_pack_boundary_context_none(mode):
    lat = np.random.default_rng(2).standard_normal((4, 8, 8)) \
        .astype(np.float32)
    out_lat, out_ctx = unpack_boundary(
        pack_boundary(lat, None) if mode == "paper"
        else pack_boundary_wire(lat, None, mode))
    assert out_ctx is None
    assert out_lat.dtype == np.float32         # decode always lands fp32
    assert out_lat.shape == lat.shape
    tol = {"paper": 1e-6, "int8": 0.05}[mode]
    assert np.max(np.abs(out_lat - lat)) <= tol
