"""Serving launcher: the paper's split-serving engine behind a CLI.

`python -m repro.launch.serve --requests 4` serves stable-diffusion-v1 at
its published widths (``configs/stable_diffusion_v1.CONFIG``) to the
paper's device fleet under the paper's calibrated planner constants
(§5.4, ``serving.simulator.CALIBRATED``).  It schedules each request
(minimum cloud iterations for its SLA, quantized to the n_step grid),
runs the batched cloud segments, encodes each boundary for the link and
completes every job on the simulated device.  ``--reduced`` serves the
tiny same-family config instead, for CPU runs.
"""
import argparse
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import stable_diffusion_v1
from repro.core.transport import LOCAL_LINK, WAN_LINK
from repro.launch.compile_cache import enable_compile_cache
from repro.models import diffusion
from repro.serving.engine import (
    DiffusionDeviceSim,
    DiffusionSplitEngine,
    Request,
)
from repro.serving.simulator import CALIBRATED, table4_fleet


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--requests", type=int, default=16)
    ap.add_argument("--t-lim", type=float, default=CALIBRATED.t_lim)
    ap.add_argument("--r-cloud", type=float, default=CALIBRATED.r_cloud)
    ap.add_argument("--wan", action="store_true")
    ap.add_argument("--int8-transport", action="store_true",
                    help="encode boundaries as per-row int8 through the "
                         "Pallas kernel (wire format 'int8')")
    ap.add_argument("--reduced", action="store_true",
                    help="serve the tiny same-family config (CPU runs)")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    enable_compile_cache()
    cfg = (stable_diffusion_v1.reduced() if args.reduced
           else stable_diffusion_v1.CONFIG)
    # one compiled init program instead of one eager dispatch per op
    params = jax.jit(diffusion.init_params, static_argnums=0)(
        cfg, jax.random.PRNGKey(args.seed))
    cost = dataclasses.replace(
        CALIBRATED, r_cloud=args.r_cloud, t_lim=args.t_lim,
        n_total=cfg.n_total_iterations, n_step=cfg.split_stride)
    link = WAN_LINK if args.wan else LOCAL_LINK
    engine = DiffusionSplitEngine(
        params, cfg, cost, link=link,
        wire="int8" if args.int8_transport else None)
    device = DiffusionDeviceSim(params, cfg)
    fleet = table4_fleet(args.requests, seed=args.seed, params=cost,
                         rtt=link.rtt)
    toks = np.zeros((1, cfg.text_len), np.int32)
    reqs = [Request(d.device_id, d, toks, toks) for d in fleet]
    results = engine.serve(reqs, seed=args.seed)

    dev = jax.devices()[0]
    print(f"{cfg.name} on {dev.platform} ({dev.device_kind}, "
          f"{len(jax.devices())} device(s))")
    print(f"{'request':10s} {'r_dev':>6s} {'n_cloud':>8s} {'payload':>9s} "
          f"{'t_net':>8s}")
    for d in fleet:
        r = results[d.device_id]
        img = device.complete(r)
        if not bool(jnp.all(jnp.isfinite(img))):
            raise RuntimeError(f"{d.device_id}: non-finite image")
        print(f"{d.device_id:10s} {d.r_dev:6.2f} {r.n_cloud:8d} "
              f"{len(r.payload):8d}B {r.transfer_seconds*1e3:7.2f}ms")
    print(f"\nengine stats ({dev.platform}): {engine.stats}")
    print(f"distinct executables (bounded by n_total/n_step + 1 = "
          f"{cfg.n_total_iterations // cfg.split_stride + 1}): "
          f"{engine.stats['executables']}")


if __name__ == "__main__":
    main()
