"""Compile the served path's units at stable-diffusion-v1's and
h2o-danube-1.8b's published widths for one TPU v5e chip that is
described, not attached.

Nothing runs: these compiles catch what the chip's compiler refuses
(tiling, VMEM, memory) at no chip time.  This is the only test file that
describes a TPU; the description happens inside a fixture so that every
test worker collects the same tests and only the worker given this file
loads the TPU compiler.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import h2o_danube_1_8b
from repro.configs.stable_diffusion_v1 import CONFIG
from repro.kernels import ops
from repro.models import attention
from repro.models import diffusion as dif

LEVEL0 = CONFIG.unet_base                       # channels at the 64x64 level
T_DIM = 4 * CONFIG.unet_base


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc
    log_dir = os.environ.get("TPU_LOG_DIR")
    os.environ["TPU_LOG_DIR"] = "disabled"
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    finally:
        if log_dir is None:
            os.environ.pop("TPU_LOG_DIR", None)
        else:
            os.environ["TPU_LOG_DIR"] = log_dir
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without the chip: keep it out
    was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was_on)
    cc.reset_cache()


def _sds(tree, sharding):
    return jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding),
        tree)


def _compile(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    print(compiled.memory_analysis())
    return compiled


@pytest.mark.parametrize("shape", [
    (CONFIG.latent_channels, CONFIG.latent_size ** 2),   # kernel sees (8, 4096)
    (2, CONFIG.text_len * CONFIG.text_width),            # kernel sees (8, 59136)
], ids=["latent", "context"])
def test_int8_quantize_compiles_to_tpu_kernel(one_chip, shape, monkeypatch):
    # the wrapper asks jax.default_backend(), which is the CPU here: steer
    # it to the chip branch and compile a fresh jit of the same body
    monkeypatch.setattr(ops, "_interpret", lambda: False)
    x = jax.ShapeDtypeStruct(shape, jnp.float32, sharding=one_chip)
    compiled = _compile(ops.int8_quantize.__wrapped__, x)
    assert "tpu_custom_call" in compiled.as_text()


@pytest.fixture
def chip_kernels(monkeypatch):
    """Steer ``kernels.ops`` to its chip branch (it asks
    jax.default_backend(), the CPU here), with no jit traced earlier for
    the CPU reused, nor one traced here reused after."""
    monkeypatch.setattr(ops, "_interpret", lambda: False)
    jax.clear_caches()
    yield
    jax.clear_caches()


def test_level0_spatial_transformer_compiles(one_chip, chip_kernels):
    # 4096 tokens: the self-attention takes the flash kernel
    p = _sds(jax.eval_shape(
        lambda k: dif.init_xattn(k, LEVEL0, CONFIG.text_width,
                                 CONFIG.unet_heads),
        jax.random.PRNGKey(0)), one_chip)
    x = jax.ShapeDtypeStruct((1, LEVEL0, CONFIG.latent_size,
                              CONFIG.latent_size), jnp.float32,
                             sharding=one_chip)
    ctx = jax.ShapeDtypeStruct((1, CONFIG.text_len, CONFIG.text_width),
                               jnp.float32, sharding=one_chip)
    compiled = _compile(
        lambda p, x, c: dif.apply_xattn(p, x, c, CONFIG.unet_heads),
        p, x, ctx)
    assert compiled.memory_analysis().output_size_in_bytes >= 4 * x.size
    assert "tpu_custom_call" in compiled.as_text()


def test_level0_resblock_compiles(one_chip):
    p = _sds(jax.eval_shape(
        lambda k: dif.init_resblock(k, LEVEL0, LEVEL0, T_DIM),
        jax.random.PRNGKey(0)), one_chip)
    x = jax.ShapeDtypeStruct((1, LEVEL0, CONFIG.latent_size,
                              CONFIG.latent_size), jnp.float32,
                             sharding=one_chip)
    t_emb = jax.ShapeDtypeStruct((1, T_DIM), jnp.float32, sharding=one_chip)
    compiled = _compile(dif.apply_resblock, p, x, t_emb)
    assert compiled.memory_analysis().output_size_in_bytes >= 4 * x.size


def test_danube_prefill_attention_compiles(one_chip, chip_kernels):
    """The LM's forward-only self-attention at h2o-danube-1.8b's shapes
    (8192 tokens, batch 2, GQA 32/8, head_dim 80, causal window 4096)
    takes the flash kernel with its band of kv blocks."""
    c = h2o_danube_1_8b.CONFIG
    q = jax.ShapeDtypeStruct((2, 8192, c.num_heads, c.resolved_head_dim()),
                             jnp.bfloat16, sharding=one_chip)
    kv = jax.ShapeDtypeStruct((2, 8192, c.num_kv_heads,
                               c.resolved_head_dim()), jnp.bfloat16,
                              sharding=one_chip)
    compiled = _compile(
        lambda q, k, v: attention.self_attention(
            q, k, v, causal=True, window=c.window,
            kernel=ops.kernel_registry()["attn"]), q, kv, kv)
    assert "tpu_custom_call" in compiled.as_text()
