"""Per-architecture smoke tests (reduced configs) + decode consistency.

Every assigned architecture: instantiate a reduced same-family config,
run one forward/train step on CPU, assert output shapes + no NaNs; and
assert prefill+decode exactly matches the full-sequence forward.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCH_IDS, get_config, reduced_config
from repro.models import transformer as tr


def _batch(cfg, B=2, S=32, key=0):
    toks = jax.random.randint(jax.random.PRNGKey(key), (B, S), 0,
                              cfg.vocab_size)
    batch = {"tokens": toks,
             "labels": jnp.roll(toks, -1, axis=1),
             "mask": jnp.ones((B, S), jnp.int32)}
    if cfg.frontend is not None:
        P = cfg.frontend.num_positions
        batch["frontend"] = jax.random.normal(
            jax.random.PRNGKey(key + 1), (B, P, cfg.frontend.embed_dim))
        if not cfg.encoder_layers:
            batch["tokens"] = batch["tokens"][:, : S - P]
    return batch


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_smoke_train_step(arch):
    cfg = reduced_config(arch)
    params = tr.init_params(cfg, jax.random.PRNGKey(0))
    batch = _batch(cfg)
    loss, metrics = jax.jit(
        lambda p, b: tr.train_forward(p, b, cfg))(params, batch)
    assert loss.shape == ()
    assert bool(jnp.isfinite(loss)), f"{arch} loss not finite"
    # one optimizer step must keep params finite
    from repro.train.optimizer import AdamWConfig, apply_updates, init_opt_state
    grads = jax.grad(lambda p: tr.train_forward(p, batch, cfg)[0])(params)
    p2, _, m = apply_updates(AdamWConfig(), params, grads,
                             init_opt_state(params))
    assert bool(jnp.isfinite(m["grad_norm"]))
    for leaf in jax.tree_util.tree_leaves(p2):
        assert bool(jnp.all(jnp.isfinite(leaf.astype(jnp.float32))))


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_decode_matches_full_forward(arch):
    cfg = reduced_config(arch)
    if cfg.moe is not None:  # avoid batch-dependent capacity drops
        cfg = dataclasses.replace(
            cfg, moe=dataclasses.replace(cfg.moe, capacity_factor=16.0))
    params = tr.init_params(cfg, jax.random.PRNGKey(0))
    B, S, extra = 2, 16, 3
    toks = jax.random.randint(jax.random.PRNGKey(1), (B, S + extra), 0,
                              cfg.vocab_size)
    batch = {"tokens": toks[:, :S]}
    offset = 0
    if cfg.frontend is not None:
        P = cfg.frontend.num_positions
        batch["frontend"] = jax.random.normal(
            jax.random.PRNGKey(2), (B, P, cfg.frontend.embed_dim))
        if not cfg.encoder_layers:
            offset = P
    full = dict(batch)
    full["tokens"] = toks
    hidden, _, _ = tr.forward_hidden(params, full, cfg)
    want = tr.unembed(params, hidden[:, -1:], cfg)
    logits, cache = tr.prefill(params, batch, cfg, pad_to=offset + S + 8)
    pos = S + offset
    for t in range(extra):
        logits, cache = tr.decode_step(params, toks[:, S + t: S + t + 1],
                                       cache, jnp.int32(pos), cfg)
        pos += 1
    np.testing.assert_allclose(
        np.asarray(logits, np.float32), np.asarray(want, np.float32),
        atol=5e-2, rtol=5e-2)


def test_swa_ring_cache_matches_linear():
    """Decode beyond the window with a ring cache == full-length cache."""
    cfg = reduced_config("h2o-danube-1.8b")   # SWA window=32 reduced
    params = tr.init_params(cfg, jax.random.PRNGKey(0))
    B, T = 1, 48   # decode past the window
    toks = jax.random.randint(jax.random.PRNGKey(1), (B, T), 0,
                              cfg.vocab_size)
    ring = tr.init_decode_cache(cfg, B, cfg.window)      # ring (W slots)
    lin = tr.init_decode_cache(cfg, B, T)                # full length
    for t in range(T):
        lr, ring = tr.decode_step(params, toks[:, t:t+1], ring,
                                  jnp.int32(t), cfg)
        ll, lin = tr.decode_step(params, toks[:, t:t+1], lin,
                                 jnp.int32(t), cfg)
    np.testing.assert_allclose(np.asarray(lr, np.float32),
                               np.asarray(ll, np.float32), atol=1e-2,
                               rtol=1e-2)


def test_param_count_analytic_close_to_actual():
    from repro.models.common import count_params
    for arch in ("smollm-135m", "qwen2-7b", "mamba2-780m"):
        cfg = reduced_config(arch)
        params = tr.init_params(cfg, jax.random.PRNGKey(0))
        actual = count_params(params)
        # padded vocab inflates actual; analytic uses true vocab
        pad = (cfg.padded_vocab() - cfg.vocab_size) * cfg.d_model
        if not cfg.tie_embeddings:
            pad *= 2
        est = cfg.param_count()
        assert abs(actual - pad - est) / actual < 0.25, arch


def test_diffusion_named_scopes():
    """The compiled denoise loop and text encoder at the small size carry
    each named scope in their ``op_name`` metadata, at every level of
    the UNet: what the benchmark's trace reader puts device time down
    to."""
    import re

    from repro.configs import stable_diffusion_v1
    from repro.models import diffusion
    cfg = stable_diffusion_v1.reduced()
    params = jax.eval_shape(lambda k: diffusion.init_params(cfg, k),
                            jax.random.PRNGKey(0))
    lat = jax.ShapeDtypeStruct(
        (1, cfg.latent_channels, cfg.latent_size, cfg.latent_size),
        jnp.float32)
    ctx2 = jax.ShapeDtypeStruct((2, 1, cfg.text_len, cfg.text_width),
                                jnp.float32)
    toks = jax.ShapeDtypeStruct((1, cfg.text_len), jnp.int32)

    def op_names(fn, *args):
        hlo = jax.jit(fn).lower(*args).compile().as_text()
        return set(re.findall(r'op_name="([^"]*)"', hlo))

    unet = op_names(lambda p, x, c: diffusion.denoise_range(
        p, cfg, x, c, 0, 2), params, lat, ctx2)
    text = op_names(lambda p, t: diffusion.encode_text(p["text"], cfg, t),
                    params, toks)

    def has(names, path):
        return any(f"/{path}/" in n for n in names)
    L = len(cfg.unet_mults)
    levels = ([(f"down{i}", i) for i in range(L)] + [("mid", L - 1)]
              + [(f"up{i}", i) for i in reversed(range(L))])
    for level, i in levels:
        want = ["resblock"]
        if level == "mid" or i in cfg.unet_attn_levels:
            want += ["xattn_proj", "self_attn", "cross_attn", "mlp"]
        if (level.startswith("down") and i < L - 1) or (
                level.startswith("up") and i > 0):
            want.append("resample")
        for scope in want:
            assert has(unet, f"unet/{level}/{scope}"), (level, scope)
    for scope in ("unet/stem", "unet/head", "guidance"):
        assert has(unet, scope), scope
    assert has(text, "text_encoder")
    assert not has(unet, "text_encoder")


def test_diffusion_flash_self_attention_matches_einsum():
    """At the smallest shape the rule sends to the flash kernel (one
    image, 2 heads of head_dim 40, interpret mode here) the kernel path
    agrees with the einsum ``_mha`` on the same fp32 inputs to within the
    bf16 rounding of its operands; below the threshold it is ``_mha``."""
    from repro.models import diffusion
    S = diffusion.FLASH_MIN_TOKENS
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q, k, v = (jax.random.normal(kk, (1, S, 80), jnp.float32) for kk in ks)
    got = diffusion._self_attention(q, k, v, 2)
    want = diffusion._mha(q, k, v, 2)
    assert got.dtype == jnp.float32 and got.shape == want.shape
    err = float(jnp.linalg.norm(got - want) / jnp.linalg.norm(want))
    # q, k, v, the probabilities and the output are each rounded once to
    # bf16 (relative error up to 2^-9); the einsum path on the CPU rounds
    # none of them.  err > 0: the kernel path ran.
    assert 0 < err < 4 * 2.0 ** -9, err
    short = [a[:, : S // 2] for a in (q, k, v)]
    np.testing.assert_array_equal(diffusion._self_attention(*short, 2),
                                  diffusion._mha(*short, 2))


def test_diffusion_flash_sites(monkeypatch):
    """``flash_sites`` counts the self-attention layers of one UNet run
    that take the kernel: those of the 64x64 level of stable-diffusion-v1
    (2 down, 3 up) and none at the small size; with the threshold lowered
    to the small size's finest level it counts the calls a traced UNet
    makes."""
    from repro.configs import stable_diffusion_v1
    from repro.models import diffusion
    assert diffusion.flash_sites(stable_diffusion_v1.CONFIG) == 5
    cfg = stable_diffusion_v1.reduced()
    assert diffusion.flash_sites(cfg) == 0

    calls = []
    real = diffusion.ops.flash_attention

    def counted(*args, **kw):
        calls.append(args[0].shape)
        return real(*args, **kw)
    monkeypatch.setattr(diffusion, "FLASH_MIN_TOKENS", cfg.latent_size ** 2)
    monkeypatch.setattr(diffusion.ops, "flash_attention", counted)
    params = jax.eval_shape(lambda k: diffusion.init_params(cfg, k),
                            jax.random.PRNGKey(0))
    lat = jax.ShapeDtypeStruct(
        (1, cfg.latent_channels, cfg.latent_size, cfg.latent_size),
        jnp.float32)
    ctx = jax.ShapeDtypeStruct((1, cfg.text_len, cfg.text_width), jnp.float32)
    jax.eval_shape(lambda p, x, c: diffusion.apply_unet(
        p["unet"], cfg, x, jnp.zeros((1,), jnp.int32), c), params, lat, ctx)
    assert len(calls) == diffusion.flash_sites(cfg) == 3


def test_diffusion_flash_attention_scope():
    """The flash kernel's ops at a kernel-path shape (a narrow spatial
    transformer over a 64x64 map) carry ``self_attn/flash_attention`` in
    their op names, so the trace reader puts their time under
    ``self_attn``."""
    import re

    from repro.models import diffusion
    side = int(diffusion.FLASH_MIN_TOKENS ** 0.5)
    p = jax.eval_shape(lambda k: diffusion.init_xattn(k, 16, 8, 2),
                       jax.random.PRNGKey(0))
    x = jax.ShapeDtypeStruct((1, 16, side, side), jnp.float32)
    ctx = jax.ShapeDtypeStruct((1, 4, 8), jnp.float32)
    hlo = jax.jit(lambda p, x, c: diffusion.apply_xattn(p, x, c, 2)).lower(
        p, x, ctx).compile().as_text()
    names = set(re.findall(r'op_name="([^"]*)"', hlo))
    kernel = [n for n in names if "/flash_attention/" in n]
    assert kernel
    assert all("/self_attn/flash_attention/" in n for n in kernel), kernel


def test_lm_named_scopes():
    """The compiled cloud half of a layer split (``jit_cloud_layers``)
    carries ``self_attn`` and ``mlp`` in its ops' ``op_name`` metadata,
    the flash scan's ops under ``self_attn``: what the benchmark's trace
    reader puts the LM layers' device time down to."""
    import re

    from repro.core.transport import LOCAL_LINK
    from repro.serving.engine import LayerSplitEngine
    cfg = reduced_config("h2o-danube-1.8b")
    params = tr.init_params(cfg, jax.random.PRNGKey(0))
    engine = LayerSplitEngine(params, cfg, link=LOCAL_LINK)
    # 2048 tokens: prefill attention takes the chunked flash scan
    engine.process({"tokens": np.ones((1, 2048), np.int32)}, 1)
    (compiled,) = engine.cache.programs.values()
    hlo = compiled.as_text()
    assert hlo.split(",", 1)[0].split()[1] == "jit_cloud_layers"
    names = set(re.findall(r'op_name="([^"]*)"', hlo))
    assert any("/self_attn/flash_attention/" in n for n in names)
    assert any("/self_attn/" in n and "dot_general" in n for n in names)
    assert any("/mlp/" in n and "dot_general" in n for n in names)
