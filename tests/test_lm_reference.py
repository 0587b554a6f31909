"""The layer split of h2o-danube-1.8b against its plain reference.

At a small size on the CPU (``reduced_config`` widths, the published 24
layers, window 32) with seeded random weights, the payload that
``LayerSplitEngine`` ships after g layers matches the reference's hidden
states after g layers, and ``LayerSplitDevice.complete`` on it matches
the reference's full forward pass.  The reference is
``tests/lm_reference.py``: float32 at the highest matmul precision.
"""
import dataclasses
import importlib.util
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import h2o_danube_1_8b, reduced_config
from repro.core.transport import LOCAL_LINK
from repro.models import transformer as tr
from repro.serving.engine import LayerSplitDevice, LayerSplitEngine

sys.path.insert(0, str(Path(__file__).resolve().parent))
import lm_reference as ref  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
#: The program keeps its weights and activations in bfloat16 (unit
#: roundoff 2^-9) and rounds at every projection, norm and residual of
#: each layer; over 12-24 layers the hidden states and logits land
#: 1.1-2.3% from the float32 reference at these sizes.  The fp16
#: boundary holds bfloat16 values exactly.  Twice the largest reading.
TOL = 0.03


def rel(got, want) -> float:
    got = np.asarray(got, np.float64)
    want = np.asarray(want, np.float64)
    return float(np.linalg.norm(got - want) / np.linalg.norm(want))


def small_cfg(**kw):
    return dataclasses.replace(reduced_config("h2o-danube-1.8b"),
                               **dict(dict(num_layers=24), **kw))


@pytest.fixture(scope="module")
def model():
    cfg = small_cfg()
    params = tr.init_params(cfg, jax.random.PRNGKey(0))
    toks = np.asarray(jax.random.randint(jax.random.PRNGKey(1), (2, 128), 1,
                                         cfg.vocab_size))
    return cfg, params, toks


def split_outputs(params, cfg, toks, g):
    """(payload after g layers, the phone's last-position logits)."""
    payload, t_net = LayerSplitEngine(params, cfg, link=LOCAL_LINK).process(
        {"tokens": toks}, g)
    assert t_net > 0 and payload.dtype == np.float16
    logits = LayerSplitDevice(params, cfg).complete(payload, g)
    return payload, np.asarray(logits, np.float32)[:, -1, :cfg.vocab_size]


@pytest.mark.parametrize("g", [0, 12, 24])
def test_layer_split_matches_reference(model, g):
    """128 tokens against a window of 32: three quarters of the keys a
    causal mask allows are outside the window."""
    cfg, params, toks = model
    payload, logits = split_outputs(params, cfg, toks, g)
    want_h, want_logits = ref.forward(params, cfg, toks, g)
    assert rel(payload, want_h) < TOL
    assert rel(logits, want_logits) < TOL


def test_window_binds(model):
    """The reference without the window is far outside the tolerance, so
    a program that ignored it would fail the comparison above."""
    cfg, params, toks = model
    _, want = ref.forward(params, cfg, toks)
    _, full = ref.forward(params, dataclasses.replace(cfg, window=0), toks)
    assert rel(full, want) > 10 * TOL


def test_flash_path_matches_reference():
    """At 2048 tokens prefill attention takes the chunked flash scan
    (``attention.flash_self_attention``) with the window as a mask."""
    cfg = small_cfg(num_layers=2)
    params = tr.init_params(cfg, jax.random.PRNGKey(2))
    toks = np.asarray(jax.random.randint(jax.random.PRNGKey(3), (1, 2048), 1,
                                         cfg.vocab_size))
    payload, logits = split_outputs(params, cfg, toks, 1)
    want_h, want_logits = ref.forward(params, cfg, toks, 1)
    assert rel(payload, want_h) < TOL
    assert rel(logits, want_logits) < TOL


def test_flash_kernel_path_matches_reference():
    """At 2048 tokens ``run_layer_range`` given the kernel hook (the Pallas
    flash kernel, interpreted here) and without it (the scan) both match
    the reference, on a window that leaves whole kv blocks of the kernel
    outside every query block's band."""
    from repro.kernels import ops
    from repro.models.moe import LOCAL_CTX
    cfg = small_cfg(num_layers=2, window=700)
    params = tr.init_params(cfg, jax.random.PRNGKey(6))
    toks = np.asarray(jax.random.randint(jax.random.PRNGKey(7), (1, 2048), 1,
                                         cfg.vocab_size))

    def hidden(kernels):
        @jax.jit
        def run(params, toks):
            x = tr.embed_inputs(params, {"tokens": toks}, cfg)
            return tr.run_layer_range(
                params, x, cfg, LOCAL_CTX, start_group=0, stop_group=2,
                positions=jnp.arange(x.shape[1]), kernels=kernels)
        return np.asarray(run(params, jnp.asarray(toks)), np.float32)

    calls = []

    def counted(q, k, v, **kw):
        calls.append(kw)
        return ops.flash_attention(q, k, v, **kw)

    want, _ = ref.forward(params, cfg, toks, 2)
    kernel = hidden({"attn": counted})
    assert calls and all(kw["causal"] and kw["window"] == 700
                         for kw in calls)
    scan = hidden(None)
    assert rel(kernel, want) < TOL
    assert rel(scan, want) < TOL


def test_flash_sites():
    """``flash_sites`` counts the self-attention layers of a layer range
    that take the flash path: every attention layer of the range at 2048
    tokens or more, none below."""
    c = h2o_danube_1_8b.CONFIG
    assert tr.flash_sites(c, 8192, stop_group=18) == 18
    assert tr.flash_sites(c, 2048, start_group=18, stop_group=24) == 6
    assert tr.flash_sites(c, 2047, stop_group=18) == 0
    assert tr.flash_sites(c, 1024, stop_group=24) == 0
    # recurrent layers do not count; the tail's attention-free remainder
    # neither (38 layers of (rec, rec, attn): 12 groups and a tail of 2)
    g = reduced_config("recurrentgemma-9b")
    g = dataclasses.replace(g, num_layers=38)
    assert tr.flash_sites(g, 4096, stop_group=g.num_groups()) == 12
    # the engine states them only where it passes the kernel
    engine = LayerSplitEngine(None, c, link=LOCAL_LINK)
    assert engine._span_args(18, 2, 8192)["flash_sites"] == (
        18 if engine.kernels else 0)
    engine.kernels = {"attn": None}
    assert engine._span_args(18, 2, 8192) == {
        "stop_group": 18, "batch": 2, "tokens": 8192, "flash_sites": 18}


@pytest.mark.parametrize("eps", [1e-5, 1e-6])
def test_norm_eps_reaches_every_norm(monkeypatch, eps):
    """Every norm of the prefill, decode and split paths is given the
    configuration's ``norm_eps``."""
    cfg = small_cfg(num_layers=2, norm_eps=eps)
    params = tr.init_params(cfg, jax.random.PRNGKey(0))
    seen = []
    real = tr.apply_norm

    def spy(p, x, *args):
        seen.append(args)
        return real(p, x, *args)
    monkeypatch.setattr(tr, "apply_norm", spy)
    toks = jnp.ones((1, 8), jnp.int32)
    _, cache = tr.prefill(params, {"tokens": toks}, cfg, pad_to=9)
    tr.decode_step(params, toks[:, :1], cache, jnp.int32(8), cfg)
    payload, _ = LayerSplitEngine(params, cfg).process(
        {"tokens": np.ones((1, 8), np.int32)}, 1)
    LayerSplitDevice(params, cfg).complete(payload, 1)
    # traced once per scan body: prefill and decode 2 + final each, the
    # cloud's layers 2, the phone's 2 + final
    assert len(seen) == 3 + 3 + 2 + 3
    assert set(seen) == {(eps,)}


def test_norm_eps_changes_small_norm_output():
    """On inputs whose mean square is below eps the two published eps
    values give different hidden states, and the program follows the
    reference at its own."""
    cfg = small_cfg(num_layers=2)
    params = tr.init_params(cfg, jax.random.PRNGKey(0))
    params["embed"] = params["embed"] * 1e-3
    toks = np.asarray(jax.random.randint(jax.random.PRNGKey(1), (1, 64), 1,
                                         cfg.vocab_size))
    payload, _ = split_outputs(params, cfg, toks, 1)
    want, _ = ref.forward(params, cfg, toks, 1)
    other, _ = ref.forward(params, dataclasses.replace(cfg, norm_eps=1e-6),
                           toks, 1)
    assert rel(payload, want) < TOL
    assert rel(other, want) > 10 * TOL


def test_benchmark_reference_agrees():
    """The benchmark's copy of the reference (query blocks, weights
    upcast one layer at a time, no import of the program) gives the same
    hidden states and logits as this one, to float32 rounding."""
    spec = importlib.util.spec_from_file_location(
        "danube_ref_copy", ROOT / "bench" / "configs" / "danube-1.8b.ref.py")
    bref = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(bref)
    cfg = small_cfg()
    params = tr.init_params(cfg, jax.random.PRNGKey(4))
    toks = np.asarray(jax.random.randint(jax.random.PRNGKey(5), (1, 128), 1,
                                         cfg.vocab_size))
    sizes = {k: getattr(cfg, k) for k in (
        "num_layers", "d_model", "num_heads", "num_kv_heads", "head_dim",
        "d_ff", "vocab_size", "norm_eps", "rope_theta", "window")}
    copy = bref.Reference(sizes, q_block=32)
    for g in (0, 18):
        want_h, want_logits = ref.forward(params, cfg, toks, g)
        got_h, got_logits = copy.forward(params, toks[0], g)
        assert rel(got_h, want_h[0]) < 1e-5
        assert rel(got_logits, want_logits[0]) < 1e-5


def test_danube_config_published():
    """The values of the model's config.json (arXiv:2401.16818)."""
    c = h2o_danube_1_8b.CONFIG
    assert (c.num_layers, c.d_model, c.num_heads, c.num_kv_heads,
            c.resolved_head_dim(), c.d_ff, c.vocab_size) == (
        24, 2560, 32, 8, 80, 6912, 32000)
    assert (c.norm, c.norm_eps, c.rope_theta, c.max_seq_len) == (
        "rmsnorm", 1e-5, 1e4, 16384)
    assert (c.attention_kind, c.window) == ("swa", 4096)
    assert (c.activation, c.tie_embeddings, c.param_dtype) == (
        "swiglu", False, "bfloat16")
    assert reduced_config("h2o-danube-1.8b").norm_eps == 1e-5
