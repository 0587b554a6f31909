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
