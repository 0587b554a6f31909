"""End-to-end behaviour tests for the paper's system.

The paper's pipeline: telemetry -> scheduler -> split execution ->
transport -> device completion, plus the SLA controller and the GPU
allocator, exercised together on the reduced diffusion model.
"""
import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import stable_diffusion_v1
from repro.core.cost_model import CostParams, e2e_latency
from repro.core.scheduler import VariableIterationScheduler, allocate_gpus
from repro.core.sla import AdaptiveSLAController, SLAPolicy
from repro.core.telemetry import ClientRegistry, DeviceProfile, generate_fleet
from repro.core.transport import LOCAL_LINK
from repro.models import diffusion
from repro.serving.engine import (
    DiffusionDeviceSim,
    DiffusionSplitEngine,
    Request,
)


def test_full_pipeline_telemetry_to_image():
    """Register clients -> schedule -> split-execute -> complete on device;
    slower devices must get MORE cloud iterations, every image finite."""
    cfg = stable_diffusion_v1.reduced()
    params = diffusion.init_params(cfg, jax.random.PRNGKey(0))
    cost = CostParams(r_cloud=40.0, n_total=cfg.n_total_iterations,
                      n_step=cfg.split_stride, t_lim=3.0, k_decode=1.0)
    reg = ClientRegistry()
    for i, r in enumerate((0.5, 2.0, 8.0)):
        reg.register(DeviceProfile(f"d{i}", r_dev=r, rtt=0.05))
    # telemetry updates shift the estimate
    reg.report_rtt("d0", 0.2)
    reg.report_rate("d0", 0.4)
    engine = DiffusionSplitEngine(params, cfg, cost, link=LOCAL_LINK)
    device = DiffusionDeviceSim(params, cfg)
    toks = np.zeros((1, cfg.text_len), np.int32)
    reqs = [Request(p.device_id, p, toks, toks)
            for p in reg.all_profiles()]
    results = engine.serve(reqs, seed=0)
    n = {rid: res.n_cloud for rid, res in results.items()}
    assert n["d0"] >= n["d1"] >= n["d2"]     # slower -> more cloud work
    for res in results.values():
        img = device.complete(res)
        assert bool(jnp.all(jnp.isfinite(img)))


def test_scheduler_gpu_allocator_pipeline():
    p = CostParams(r_cloud=62.5, n_total=50, n_step=5, t_lim=8.5,
                   k_decode=2.0)
    fleet = generate_fleet(200, 2.25, 0.28, seed=3, rtt=0.3, k_decode=2.0)
    summ = VariableIterationScheduler(p).summarize(fleet)
    plan = allocate_gpus(summ, p, n_gpus=64, horizon_s=60.0)
    assert plan.gpus_needed >= 1
    assert 0 <= sum(plan.fractions.values()) <= 1 + 1e-9
    # paper §4.5: when demand collapses, GPUs are released
    tiny = VariableIterationScheduler(p).summarize(fleet[:5])
    plan2 = allocate_gpus(tiny, p, n_gpus=64, horizon_s=60.0)
    assert plan2.release_gpus


def test_adaptive_sla_controller():
    pol = SLAPolicy(t_lim=8.0, t_floor=2.0, t_ceil=30.0)
    ctrl = AdaptiveSLAController(pol)
    t1 = ctrl.update(utilization=0.95)      # overloaded -> relax
    assert t1 > 8.0
    for _ in range(50):
        ctrl.update(utilization=0.1)        # idle -> tighten
    assert pol.t_lim < t1
    assert pol.t_lim >= pol.t_floor


def test_sla_relaxation_reduces_cloud_work():
    """Relaxing the SLA must reduce total cloud GPU time (the §7 knob)."""
    fleet = generate_fleet(100, 2.25, 0.28, seed=1, rtt=0.3, k_decode=2.0)
    times = []
    for t_lim in (6.0, 8.5, 12.0, 20.0):
        p = CostParams(r_cloud=62.5, n_total=50, n_step=5, t_lim=t_lim,
                       k_decode=2.0)
        times.append(
            VariableIterationScheduler(p).summarize(fleet).total_gpu_time)
    assert times == sorted(times, reverse=True)


def test_quantized_transport_end_to_end():
    """§7 refinement: the row-wise int8 wire still reconstructs images
    (graceful degradation) at under half the paper encoding's bytes."""
    cfg = stable_diffusion_v1.reduced()
    params = diffusion.init_params(cfg, jax.random.PRNGKey(0))
    cost = CostParams(r_cloud=40.0, n_total=cfg.n_total_iterations,
                      n_step=cfg.split_stride, t_lim=3.0, k_decode=1.0)
    toks = np.zeros((1, cfg.text_len), np.int32)
    req = Request("r", DeviceProfile("d", 2.0, rtt=0.05), toks, toks)
    paper_e = DiffusionSplitEngine(params, cfg, cost, link=LOCAL_LINK)
    int8_e = DiffusionSplitEngine(params, cfg, cost, link=LOCAL_LINK,
                                  wire="int8")
    device = DiffusionDeviceSim(params, cfg)
    n = cfg.split_stride * 2
    r_paper = paper_e.process_group([req], n, seed=0)[0]
    r_int8 = int8_e.process_group([req], n, seed=0)[0]
    assert len(r_int8.payload) < 0.5 * len(r_paper.payload)
    img_a = np.asarray(device.complete(r_paper))
    img_b = np.asarray(device.complete(r_int8))
    assert np.corrcoef(img_a.ravel(), img_b.ravel())[0, 1] > 0.98
