"""Split serving across BOTH granularities + §7 refinements.

1. Iteration split (diffusion) with the paper encoding vs the row-wise
   int8 wire and a lossy (UDP-style) channel — the paper's
   graceful-degradation claim, measured as image correlation.
2. Layer split (qwen2-class LM): cloud runs pattern groups [0, g), ships
   the fp16 hidden boundary, device finishes; verifies the logits match
   the monolithic forward at every split point.

    PYTHONPATH=src python examples/split_serving.py [--smoke]

Scheduling decisions come from the unified planner: the diffusion
engine's ``assign``/``plan`` delegate to ``repro.api.Planner``, and the
demo prints the decision's explain() trace for one device.
"""
import argparse

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import reduced_config, stable_diffusion_v1
from repro.core.cost_model import CostParams
from repro.core.telemetry import DeviceProfile
from repro.core.transport import LOCAL_LINK, lossy_transfer
from repro.models import diffusion
from repro.models import transformer as tr
from repro.serving.engine import (
    DiffusionDeviceSim,
    DiffusionSplitEngine,
    LayerSplitDevice,
    LayerSplitEngine,
    Request,
)


def diffusion_demo(smoke: bool = False):
    cfg = stable_diffusion_v1.reduced()
    params = diffusion.init_params(cfg, jax.random.PRNGKey(0))
    cost = CostParams(r_cloud=40.0, n_total=cfg.n_total_iterations,
                      n_step=cfg.split_stride, t_lim=3.0, k_decode=1.0)
    device = DiffusionDeviceSim(params, cfg)
    toks = np.zeros((1, cfg.text_len), np.int32)
    prof = DeviceProfile("dev", 2.0, rtt=0.05)
    req = Request("r", prof, toks, toks)
    n = cfg.split_stride * 2

    # the engine's scheduling surface IS the unified planner: one
    # request in, one explained decision out
    probe = DiffusionSplitEngine(params, cfg, cost, link=LOCAL_LINK)
    decision = probe.plan(prof)
    print("== planner decision for this device (engine.plan) ==")
    print(decision.explain())
    assert decision.n_final == probe.assign(prof)

    print("== diffusion iteration split ==")
    base_img = None
    wires = (None,) if smoke else (None, "int8")
    for wire in wires:
        eng = DiffusionSplitEngine(params, cfg, cost, link=LOCAL_LINK,
                                   wire=wire)
        res = eng.process_group([req], n, seed=0)[0]
        img = np.asarray(device.complete(res))
        if base_img is None:
            base_img = img
        corr = np.corrcoef(img.ravel(), base_img.ravel())[0, 1]
        print(f"  wire={wire or 'paper':6s} payload={len(res.payload):7d}B "
              f"corr_vs_paper={corr:.4f}")
    if smoke:
        return
    # lossy channel: drop 5% of packets of the latent, zero-fill
    eng = DiffusionSplitEngine(params, cfg, cost, link=LOCAL_LINK)
    res = eng.process_group([req], n, seed=0)[0]
    from repro.core.transport import unpack_boundary, pack_boundary
    lat, ctx = unpack_boundary(res.payload)
    lat_lossy, lost = lossy_transfer(lat, drop_prob=0.05, seed=1)
    res.payload = pack_boundary(lat_lossy, ctx)
    img = np.asarray(device.complete(res))
    corr = np.corrcoef(img.ravel(), base_img.ravel())[0, 1]
    print(f"  lossy(5% pkts, {lost*100:.1f}% elems lost) corr={corr:.4f} "
          "(graceful degradation, paper §7)")


def layer_split_demo(smoke: bool = False):
    print("== LM layer split (qwen2-class) ==")
    cfg = reduced_config("qwen2-7b")
    params = tr.init_params(cfg, jax.random.PRNGKey(0))
    toks = np.asarray(jax.random.randint(jax.random.PRNGKey(1), (2, 16), 0,
                                         cfg.vocab_size))
    hidden, _, _ = tr.forward_hidden(params, {"tokens": jnp.asarray(toks)},
                                     cfg)
    want = np.asarray(tr.unembed(params, hidden[:, -1:], cfg), np.float32)
    engine = LayerSplitEngine(params, cfg, link=LOCAL_LINK)
    device = LayerSplitDevice(params, cfg)
    stride = cfg.num_groups() if smoke else max(1, cfg.num_groups() // 4)
    for g in range(0, cfg.num_groups() + 1, stride):
        payload, t_net = engine.process({"tokens": toks}, g)
        got = np.asarray(device.complete(payload, g), np.float32)
        err = np.max(np.abs(got - want))
        print(f"  split at group {g:2d}/{cfg.num_groups()}: boundary="
              f"{payload.nbytes}B t_net={t_net*1e3:.2f}ms "
              f"max_logit_err={err:.3f}")


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="reduced CI run: one wire format, fewer splits")
    args = ap.parse_args()
    diffusion_demo(smoke=args.smoke)
    layer_split_demo(smoke=args.smoke)
