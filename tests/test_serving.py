"""End-to-end split-serving tests: split output == monolithic output."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import reduced_config, stable_diffusion_v1
from repro.core.cost_model import CostParams
from repro.core.segmentation import executable_count
from repro.core.telemetry import DeviceProfile
from repro.core.transport import LOCAL_LINK
from repro.models import diffusion
from repro.models import transformer as tr
from repro.serving.engine import (
    DiffusionDeviceSim,
    DiffusionSplitEngine,
    LayerSplitDevice,
    LayerSplitEngine,
    Request,
)


@pytest.fixture(scope="module")
def dmodel():
    cfg = stable_diffusion_v1.reduced()
    params = diffusion.init_params(cfg, jax.random.PRNGKey(0))
    return cfg, params


def test_diffusion_split_end_to_end(dmodel):
    """Cloud [0,n) + device [n,N) + VAE == all on one machine.

    The paper's Fig 9 claim: splitting does not change the output."""
    cfg, params = dmodel
    cost = CostParams(r_cloud=10.0, n_total=cfg.n_total_iterations,
                      n_step=cfg.split_stride, t_lim=5.0, k_decode=1.0)
    engine = DiffusionSplitEngine(params, cfg, cost, link=LOCAL_LINK)
    device = DiffusionDeviceSim(params, cfg)
    toks = np.zeros((1, cfg.text_len), np.int32)
    req = Request("r", DeviceProfile("d", 5.0), toks, toks)
    # baseline: everything on one machine with the same seed
    ctx2 = diffusion.encode_prompt(params, cfg, jnp.asarray(toks),
                                   jnp.asarray(toks))
    lat0 = jax.random.normal(jax.random.PRNGKey(0),
                             (1, cfg.latent_channels, cfg.latent_size,
                              cfg.latent_size))
    mono = diffusion.apply_vae_decoder(
        params["vae"], cfg,
        diffusion.denoise_range(params, cfg, lat0, ctx2, 0,
                                cfg.n_total_iterations))
    for n_cloud in (0, cfg.split_stride * 2, cfg.n_total_iterations):
        res = engine.process_group([req], n_cloud, seed=0)[0]
        img = device.complete(res)
        np.testing.assert_allclose(np.asarray(img), np.asarray(mono),
                                   atol=2e-2)  # fp16 context on the wire


def test_executable_cache_bounded_by_step_grid(dmodel):
    """The n_step quantization bounds the number of compiled programs —
    the paper's 'server does not handle diverse requests' claim."""
    cfg, params = dmodel
    cost = CostParams(r_cloud=50.0, n_total=cfg.n_total_iterations,
                      n_step=cfg.split_stride, t_lim=2.0, k_decode=1.0)
    engine = DiffusionSplitEngine(params, cfg, cost, link=LOCAL_LINK)
    device_rates = np.linspace(0.5, 8.0, 13)
    toks = np.zeros((1, cfg.text_len), np.int32)
    reqs = [Request(f"r{i}", DeviceProfile(f"d{i}", float(r)), toks, toks)
            for i, r in enumerate(device_rates)]
    engine.serve(reqs, seed=1)
    bound = executable_count(cfg.n_total_iterations, cfg.split_stride)
    assert engine.stats["executables"] <= bound
    assert engine.stats["requests"] == len(reqs)


def test_layer_split_matches_full_forward():
    cfg = reduced_config("qwen2-7b")
    params = tr.init_params(cfg, jax.random.PRNGKey(0))
    B, S = 2, 16
    toks = np.asarray(
        jax.random.randint(jax.random.PRNGKey(1), (B, S), 0,
                           cfg.vocab_size))
    batch = {"tokens": jnp.asarray(toks)}
    hidden, _, _ = tr.forward_hidden(params, batch, cfg)
    want = tr.unembed(params, hidden[:, -1:], cfg)
    engine = LayerSplitEngine(params, cfg, link=LOCAL_LINK)
    device = LayerSplitDevice(params, cfg)
    for g in (0, cfg.num_groups() // 2, cfg.num_groups()):
        payload, t_net = engine.process({"tokens": toks}, g)
        got = device.complete(payload, g)
        np.testing.assert_allclose(np.asarray(got, np.float32),
                                   np.asarray(want, np.float32),
                                   atol=0.15, rtol=0.1)  # fp16 boundary
        assert t_net > 0


def test_layer_split_payload_is_fp16_of_hidden():
    """The payload is the cloud layers' bf16 hidden states rounded once to
    fp16, bit for bit what a host-side bf16 -> fp32 -> fp16 conversion
    of the same states gives."""
    from repro.models.moe import LOCAL_CTX
    cfg = reduced_config("h2o-danube-1.8b")
    params = tr.init_params(cfg, jax.random.PRNGKey(0))
    toks = np.asarray(jax.random.randint(jax.random.PRNGKey(1), (2, 64), 0,
                                         cfg.vocab_size))
    payload, _ = LayerSplitEngine(params, cfg, link=LOCAL_LINK).process(
        {"tokens": toks}, 1)

    @jax.jit
    def hidden(params, toks):
        x = tr.embed_inputs(params, {"tokens": toks}, cfg)
        return tr.run_layer_range(params, x, cfg, LOCAL_CTX, start_group=0,
                                  stop_group=1,
                                  positions=jnp.arange(x.shape[1]))
    want = np.asarray(hidden(params, jnp.asarray(toks)), np.float32)
    assert payload.dtype == np.float16
    np.testing.assert_array_equal(payload.view(np.uint16),
                                  want.astype(np.float16).view(np.uint16))


def _engine_spans(trace_dir):
    """The ``repro.engine.*`` host events of the one profile under
    ``trace_dir``: (start ns, end ns, name, {arg: value}), in order."""
    from jax.profiler import ProfileData
    path = next(trace_dir.rglob("*.xplane.pb"))
    out = []
    for plane in ProfileData.from_file(str(path)).planes:
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith("repro.engine."):
                    out.append((ev.start_ns, ev.end_ns, ev.name,
                                dict(ev.stats)))
    return sorted(out, key=lambda e: (e[0], -e[1]))


def test_process_group_spans(dmodel, tmp_path):
    """A traced group shows the engine's host stages in order, each pack
    with its request; a compile span appears on a cache miss only.  The
    group and compile spans carry how many self-attention layers take the
    flash kernel (none at this size)."""
    cfg, params = dmodel
    cost = CostParams(r_cloud=10.0, n_total=cfg.n_total_iterations,
                      n_step=cfg.split_stride, t_lim=5.0, k_decode=1.0)
    engine = DiffusionSplitEngine(params, cfg, cost, link=LOCAL_LINK)
    toks = np.zeros((1, cfg.text_len), np.int32)
    reqs = [Request(f"r{i}", DeviceProfile(f"d{i}", 5.0), toks, toks)
            for i in range(2)]
    with jax.profiler.trace(str(tmp_path)):
        engine.process_group(reqs, 2, seed=0)        # compiles
        engine.process_group(reqs, 2, seed=1)        # cached
    spans = _engine_spans(tmp_path)
    groups = [s for s in spans if s[2] == "repro.engine.process_group"]
    assert len(groups) == 2
    for (g0, g1, _, args), compiles in zip(groups, (1, 0)):
        assert args == {"n_cloud": 2, "batch": 2, "flash_sites": 0,
                        "request_ids": "r0;r1"}
        inner = [s for s in spans if g0 <= s[0] and s[1] <= g1
                 and s[2] != "repro.engine.process_group"]
        assert [s[2].rsplit(".", 1)[1] for s in inner] == (
            ["encode_prompt"] + ["compile"] * compiles
            + ["denoise", "pull", "pack", "pack"])
        assert [s[3] for s in inner if s[2].endswith(".pack")] == [
            {"request_id": "r0"}, {"request_id": "r1"}]
        for s in inner:
            if s[2].endswith(".compile"):
                assert s[3] == {"n_cloud": 2, "batch": 2, "flash_sites": 0}
    assert engine.stats["cache_misses"] == 1
    assert engine.stats["cache_hits"] == 1


def test_layer_split_spans(tmp_path):
    """A traced ``LayerSplitEngine.process`` shows its host stages once
    each per call, in order, the outer one and the compile (on a cache
    miss only) with the split, batch, prompt length and the layers that
    take the flash kernel (none off the TPU, nor at this length)."""
    cfg = reduced_config("h2o-danube-1.8b")
    params = tr.init_params(cfg, jax.random.PRNGKey(0))
    engine = LayerSplitEngine(params, cfg, link=LOCAL_LINK)
    toks = np.ones((2, 16), np.int32)
    with jax.profiler.trace(str(tmp_path)):
        engine.process({"tokens": toks}, 1)          # compiles
        engine.process({"tokens": toks}, 1)          # cached
    spans = _engine_spans(tmp_path)
    calls = [s for s in spans if s[2] == "repro.engine.process_layers"]
    assert len(calls) == 2
    args = {"stop_group": 1, "batch": 2, "tokens": 16, "flash_sites": 0}
    for (c0, c1, _, got), compiles in zip(calls, (1, 0)):
        assert got == args
        inner = [s for s in spans if c0 <= s[0] and s[1] <= c1
                 and s[2] != "repro.engine.process_layers"]
        assert [s[2].rsplit(".", 1)[1] for s in inner] == (
            ["compile"] * compiles + ["cloud_layers", "pull", "pack"])
        for s in inner:
            assert s[3] == (args if s[2].endswith(".compile") else {})
    assert engine.stats["cache_misses"] == 1
    assert engine.stats["cache_hits"] == 1


def test_engine_programs_have_stable_names(dmodel):
    """Each engine's jitted program is named for what it runs, so the
    trace tells them apart."""
    cfg, params = dmodel
    cost = CostParams(r_cloud=10.0, n_total=cfg.n_total_iterations,
                      n_step=cfg.split_stride, t_lim=5.0, k_decode=1.0)
    engine = DiffusionSplitEngine(params, cfg, cost, link=LOCAL_LINK)
    device = DiffusionDeviceSim(params, cfg)
    toks = np.zeros((1, cfg.text_len), np.int32)
    res = engine.process_group(
        [Request("r", DeviceProfile("d", 5.0), toks, toks)],
        cfg.n_total_iterations - 1)[0]
    device.complete(res)
    lcfg = reduced_config("qwen2-7b")
    lparams = tr.init_params(lcfg, jax.random.PRNGKey(0))
    cloud = LayerSplitEngine(lparams, lcfg, link=LOCAL_LINK)
    phone = LayerSplitDevice(lparams, lcfg)
    payload, _ = cloud.process({"tokens": np.zeros((1, 8), np.int32)}, 1)
    phone.complete(payload, 1)

    def names(cache):
        return {c.as_text().split(",", 1)[0].split()[1]
                for c in cache.programs.values()}
    assert names(engine.cache) == {"jit_denoise_range"}
    assert names(device.cache) == {"jit_device_finish"}
    assert names(cloud.cache) == {"jit_cloud_layers"}
    assert names(phone.cache) == {"jit_device_layers"}
