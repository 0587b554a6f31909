#!/usr/bin/env python3
"""Chip smoke: stable-diffusion-v1 at its published widths through the
split-serving path on one TPU.

    python chip_smoke.py

One process, no arguments, no children.  Random weights from a seed.  The
planner assigns each request a split; ``DiffusionSplitEngine.process_group``
runs the cloud iterations of each ``(n_cloud, batch)`` group as one
compiled program and encodes the boundary (fp32, and int8 through the
compiled Pallas kernel); ``DiffusionDeviceSim.complete`` runs the rest
plus the VAE decode.  Groups:

  * full cloud, ``n_cloud=50``, batch 2 (two slow phones);
  * mid split, ``n_cloud=25``, batch 1, fp32 wire;
  * the same mid-split group with the int8 wire.

Checks (any failure exits non-zero):

  * every image is finite;
  * the fp32-wire split image equals ``diffusion.generate`` (the whole
    pipeline in one program) on the same chip and seed within
    ``SPLIT_ATOL``;
  * the int8 kernel agrees with ``transport.rowwise_quantize_int8``
    within 1 LSB on the boundary tensors;
  * the compiled ``ops.int8_quantize`` holds a ``tpu_custom_call``, i.e.
    the kernel is compiled for the chip and not interpreted;
  * the compiled denoise program holds the flash-attention kernel as a
    ``tpu_custom_call`` when ``diffusion.flash_sites`` counts any site.

Timings printed here are smoke timings of one run, not benchmark
numbers.  Without a TPU the script exits non-zero before any work.  The
last line of stdout is the JSON result.
"""
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.configs.stable_diffusion_v1 import CONFIG  # noqa: E402
from repro.core.telemetry import DeviceProfile  # noqa: E402
from repro.core.transport import (  # noqa: E402
    LOCAL_LINK,
    rowwise_quantize_int8,
    unpack_boundary,
)
from repro.kernels import ops  # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402
from repro.models import diffusion as dif  # noqa: E402
from repro.serving.engine import (  # noqa: E402
    DiffusionDeviceSim,
    DiffusionSplitEngine,
    Request,
)
from repro.serving.simulator import CALIBRATED  # noqa: E402

SEED = 0
MID_SPLIT = 25
#: At default precision a TPU dot or convolution rounds its fp32 operands
#: to bf16 (8 significant bits), so a product is off by up to 2^-8
#: relative, and the split's programs, compiled and fused apart from the
#: monolithic one, round different intermediates.  DDIM multiplies a
#: latent perturbation made at the first step by up to
#: sqrt(1 / alpha_bar_first) ~ 25 by the last, so the decoded image
#: (tanh, in [-1, 1]) may move by up to 25 * 2^-8.  (Under
#: ``jax.default_matmul_precision("float32")`` the two agreed to 2.3e-5
#: on a v5e, but those programs take about three times as long to
#: compile, which a cold run cannot afford.)
SPLIT_ATOL = 25 * 2.0 ** -8
#: Device rates (iterations/s) that the paper-calibrated planner maps to
#: a full-cloud split (slow phone) and to n_cloud=25 (fast tablet).
SLOW_R_DEV, FAST_R_DEV = 0.5, 3.8


def check(ok, what):
    if not ok:
        sys.exit(f"chip_smoke: FAILED: {what}")
    print(f"check ok: {what}")


def finite(x):
    return bool(jnp.all(jnp.isfinite(x)))


def run_group(engine, members, n_cloud, label):
    c0 = engine.stats["compile_seconds"]
    e0 = engine.stats["device_seconds"]
    results = engine.process_group(members, n_cloud, seed=SEED)
    print(f"group {label}: n_cloud={n_cloud} batch={len(members)} "
          f"wire={engine.wire}: compile "
          f"{engine.stats['compile_seconds'] - c0:.3f} s, execute "
          f"{engine.stats['device_seconds'] - e0:.6f} s, payload "
          f"{len(results[0].payload)} B/request")
    return results


def complete(device, result, label):
    c0 = device.stats["compile_seconds"]
    e0 = device.stats["device_seconds"]
    img = device.complete(result)
    print(f"device {label}: [{result.n_cloud}, "
          f"{CONFIG.n_total_iterations}) + VAE: compile "
          f"{device.stats['compile_seconds'] - c0:.3f} s, execute "
          f"{device.stats['device_seconds'] - e0:.6f} s, image "
          f"{tuple(img.shape)}")
    return img


def check_int8_kernel(x, name):
    rows = np.asarray(x, np.float32).reshape(x.shape[0], -1)
    q, s = ops.int8_quantize(jnp.asarray(rows))
    q_ref, s_ref = rowwise_quantize_int8(rows)
    lsb = int(np.max(np.abs(np.asarray(q, np.int32) - q_ref.astype(np.int32))))
    check(lsb <= 1 and np.allclose(np.asarray(s), s_ref, rtol=1e-6, atol=0),
          f"int8 kernel == numpy reference on the {name} {rows.shape} "
          f"(max |dq| = {lsb} LSB)")
    return rows


def main():
    backend = jax.default_backend()
    if backend != "tpu":
        sys.exit(f"chip_smoke: needs a TPU, but JAX's backend is "
                 f"{backend!r}; there is no CPU path")
    dev = jax.devices()[0]
    result = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices())}
    print(f"device: platform={result['platform']} kind={result['kind']} "
          f"count={result['count']}")
    cache = Path(enable_compile_cache())
    n_cached = len(list(cache.iterdir())) if cache.is_dir() else 0
    print(f"compile cache: {cache} ({n_cached} entries at start)")
    cfg = CONFIG
    print(f"config {cfg.name}: latent {cfg.latent_channels}x"
          f"{cfg.latent_size}x{cfg.latent_size}, unet_base {cfg.unet_base}, "
          f"context {cfg.text_len}x{cfg.text_width}, "
          f"{cfg.n_total_iterations} steps, {dif.flash_sites(cfg)} "
          f"self-attention layers per UNet run through the flash kernel")
    print("timings below are one smoke run, not benchmark numbers")

    t0 = time.perf_counter()
    params = jax.jit(dif.init_params, static_argnums=0)(
        cfg, jax.random.PRNGKey(SEED))
    jax.block_until_ready(params)
    n_bytes = sum(a.nbytes for a in jax.tree.leaves(params))
    print(f"init (jitted, compile included): "
          f"{time.perf_counter() - t0:.3f} s, {n_bytes} B of parameters")

    rng = np.random.default_rng(SEED)
    uncond = np.zeros((1, cfg.text_len), np.int32)

    def request(rid, r_dev):
        cond = rng.integers(1, cfg.text_vocab, (1, cfg.text_len), np.int32)
        return Request(rid, DeviceProfile(rid, r_dev, k_decode=2.0,
                                          rtt=LOCAL_LINK.rtt), cond, uncond)

    reqs = [request("slow0", SLOW_R_DEV), request("slow1", SLOW_R_DEV),
            request("fast0", FAST_R_DEV)]
    fp32 = DiffusionSplitEngine(params, cfg, CALIBRATED, link=LOCAL_LINK,
                                wire="fp32")
    int8 = DiffusionSplitEngine(params, cfg, CALIBRATED, link=LOCAL_LINK,
                                wire="int8")
    device = DiffusionDeviceSim(params, cfg)
    groups = {}
    for r in reqs:
        groups.setdefault(fp32.assign(r.device), []).append(r)
    print("planner: " + ", ".join(
        f"n_cloud={n}: {[r.request_id for r in m]}"
        for n, m in sorted(groups.items())))
    check(sorted(groups) == [MID_SPLIT, cfg.n_total_iterations],
          f"planner splits are {sorted(groups)}")

    full = run_group(fp32, groups[cfg.n_total_iterations],
                     cfg.n_total_iterations, "full-cloud")
    for res in full:
        check(finite(complete(device, res, res.request_id)),
              f"{res.request_id} image finite")

    mid = groups[MID_SPLIT]
    (mid_fp32,) = run_group(fp32, mid, MID_SPLIT, "mid-split fp32")
    img_split = complete(device, mid_fp32, "mid-split fp32")
    check(finite(img_split), "mid-split fp32 image finite")
    (mid_int8,) = run_group(int8, mid, MID_SPLIT, "mid-split int8")
    img_int8 = complete(device, mid_int8, "mid-split int8")
    check(finite(img_int8), "mid-split int8 image finite")
    print(f"int8 vs fp32 wire: max |image diff| = "
          f"{float(jnp.max(jnp.abs(img_int8 - img_split))):.6g}")

    denoise_hlo = fp32.cache.programs[(MID_SPLIT, len(mid))].as_text()
    check(("tpu_custom_call" in denoise_hlo) == (dif.flash_sites(cfg) > 0),
          f"compiled denoise program holds the flash kernel "
          f"({dif.flash_sites(cfg)} sites per UNet run)")

    lat, ctx = unpack_boundary(mid_fp32.payload)
    rows = check_int8_kernel(lat, "latent")
    check_int8_kernel(ctx, "context")
    hlo = ops.int8_quantize.lower(jnp.asarray(rows)).compile().as_text()
    check("tpu_custom_call" in hlo,
          "compiled ops.int8_quantize contains tpu_custom_call")

    t0 = time.perf_counter()
    img_mono = jax.jit(dif.generate, static_argnums=1)(
        params, cfg, jnp.asarray(mid[0].cond_tokens),
        jnp.asarray(mid[0].uncond_tokens), jax.random.PRNGKey(SEED))
    img_mono.block_until_ready()
    print(f"monolithic generate (compile included): "
          f"{time.perf_counter() - t0:.3f} s")
    check(finite(img_mono), "monolithic image finite")
    diff = float(jnp.max(jnp.abs(img_split - img_mono)))
    check(diff <= SPLIT_ATOL,
          f"split == monolithic: max |diff| = {diff:.6g} <= {SPLIT_ATOL:.6g}")

    peak = dev.memory_stats()["peak_bytes_in_use"]
    print(f"peak device bytes in use: {peak}")
    print(json.dumps({"ok": True, "device": result}))


if __name__ == "__main__":
    main()
