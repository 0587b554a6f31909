"""Engine-in-the-loop trace replay (serving.replay; docs/engine_replay.md).

Covers the PR-6 acceptance criteria:
  * trace round-trip: write -> read -> every recorded plan/replan
    decision re-derives EXACTLY from the header's planner config
    (verify_decisions), including adaptive-SLA t_lim drift and
    preemption replans;
  * tracing is write-only: a traced run keeps the PR-2/PR-3 golden
    trace bit-identical to the untraced default;
  * replay determinism: same trace + same seed -> identical counters;
  * engine replay: compile count == distinct scaled (n_final, batch)
    keys == the engine's own executable counter, under the §4.3 bound;
  * the engine accounting bugfixes: compile time out of device_seconds,
    cache hit/miss counters in every engine's ProgramCache,
    PlanCache-backed assign(), and the unified stats schema across both
    engines.

Engine-executing tests use the reduced config on CPU and assert only
deterministic counters — never wall-clock seconds (beyond sign).
"""
import hashlib
import json
import math

import numpy as np
import pytest

import jax

from repro.configs import reduced_config, stable_diffusion_v1
from repro.core.cost_model import CostParams
from repro.core.planner import TRACE_FIELDS, PlanRequest, Planner
from repro.core.telemetry import DeviceProfile
from repro.core.transport import LOCAL_LINK
from repro.models import diffusion
from repro.models.transformer import init_params as lm_init
from repro.serving.engine import (
    ENGINE_STATS_KEYS,
    DiffusionDeviceSim,
    DiffusionSplitEngine,
    LayerSplitDevice,
    LayerSplitEngine,
    Request,
)
from repro.serving.fleet_sim import SimConfig, run_fleet_sim
from repro.serving.replay import (
    TRACE_VERSION,
    TraceWriter,
    read_trace,
    replay_through_engine,
    scale_n,
    scaled_group_key,
    verify_decisions,
)
from repro.serving.simulator import CALIBRATED, table4_capacity

GOLDEN = dict(policy="variable+batching", rate=12.0, duration=40.0,
              seed=7, gpus_init=10, max_gpus=32, metrics_interval_s=10.0)
SMALL = dict(policy="variable+batching", rate=8.0, duration=15.0,
             seed=7, gpus_init=10, max_gpus=32)


def _digest(res):
    sig = hashlib.sha256()
    for c in res.completed:
        sig.update(f"{c.request_id}:{c.completion:.9f}:{c.batched:d};"
                   .encode())
    return (res.n_arrivals, len(res.completed), res.violations,
            round(res.total_gpu_seconds, 9), sig.hexdigest()[:16])


# --------------------------------------------------------------------------
# Trace recording + round-trip
# --------------------------------------------------------------------------
def test_trace_round_trip_and_verify(tmp_path):
    path = str(tmp_path / "trace.jsonl")
    res = run_fleet_sim(SimConfig(trace_out=path, **SMALL))
    trace = read_trace(path)
    assert trace.header["version"] == TRACE_VERSION
    assert trace.header["sim"]["seed"] == SMALL["seed"]
    # every arrival became a plan record; dispatches carry member ids
    assert len(trace.plans()) == res.n_arrivals
    assert trace.dispatches()
    for rec in trace.dispatches():
        assert rec["batch"] == len(rec["members"]) >= 1
        assert rec["n_final"] > 0
        assert set(TRACE_FIELDS) >= {"n_final", "t_lim"}
    for rec in trace.plans():
        assert set(rec["decision"]) == set(TRACE_FIELDS)
    # the core contract: every decision re-derives exactly from the
    # header config + recorded inputs
    report = verify_decisions(trace)
    assert report.n_plans == res.n_arrivals
    assert report.ok, report.to_json()


def test_tracing_keeps_golden_trace_bit_identical(tmp_path):
    """trace_out is a write-only sink: the traced run's event dynamics
    are the PR-2/PR-3 golden trace, digit for digit."""
    base = run_fleet_sim(SimConfig(**GOLDEN))
    traced = run_fleet_sim(SimConfig(
        trace_out=str(tmp_path / "t.jsonl"), **GOLDEN))
    d = _digest(traced)
    assert d == _digest(base)
    # and the untraced digest is the pinned golden anchor itself
    assert d == (490, 490, 0, 249.312, "af766f3924e39378")


def test_trace_determinism(tmp_path):
    """Same config -> byte-identical trace files (modulo nothing)."""
    p1, p2 = str(tmp_path / "a.jsonl"), str(tmp_path / "b.jsonl")
    run_fleet_sim(SimConfig(trace_out=p1, **SMALL))
    run_fleet_sim(SimConfig(trace_out=p2, **SMALL))
    with open(p1) as f1, open(p2) as f2:
        assert f1.read() == f2.read()


def test_trace_with_preemption_replans(tmp_path):
    """Scripted spot reclaims produce preempt + replan records, and the
    replan decisions re-derive exactly through replan_preempted."""
    path = str(tmp_path / "p.jsonl")
    cap = table4_capacity(base_count=4, spot_count=8, base_max=8,
                          spot_max=16)
    res = run_fleet_sim(SimConfig(
        policy="variable+batching", rate=10.0, duration=30.0, seed=7,
        capacity=cap, dispatch="edf", trace_out=path,
        preempt_trace=[(10.0, "spot", 4), (18.0, "spot", 3)]))
    trace = read_trace(path)
    assert trace.preempts()
    assert sum(p["k"] for p in trace.preempts()) == 7
    assert res.replans > 0
    assert len(trace.replans()) == res.replans
    for rec in trace.replans():
        assert rec["n_done"] >= 0
        assert rec["decision"]["t_lim"] == rec["time_left"]
    report = verify_decisions(trace)
    assert report.n_replans == res.replans
    assert report.ok, report.to_json()


def test_trace_with_adaptive_sla_verifies(tmp_path):
    """t_lim drifts mid-run under the §7 controller; each plan record
    carries the t_lim it was decided under and the verifier tracks the
    drift through set_t_lim."""
    path = str(tmp_path / "sla.jsonl")
    res = run_fleet_sim(SimConfig(
        policy="variable+batching", rate=25.0, duration=30.0, seed=3,
        gpus_init=4, max_gpus=6, adaptive_sla=True, trace_out=path))
    trace = read_trace(path)
    t_lims = {rec["decision"]["t_lim"] for rec in trace.plans()}
    assert len(t_lims) > 1, "workload did not drift t_lim; retune"
    assert res.final_t_lim != CALIBRATED.t_lim
    report = verify_decisions(trace)
    assert report.ok, report.to_json()


def test_verify_catches_tampering(tmp_path):
    """A doctored decision field must be reported, not absorbed."""
    path = str(tmp_path / "t.jsonl")
    run_fleet_sim(SimConfig(trace_out=path, **SMALL))
    lines = open(path).read().splitlines()
    for i, line in enumerate(lines):
        rec = json.loads(line)
        if rec["kind"] == "plan":
            rec["decision"]["n_final"] += 5
            lines[i] = json.dumps(rec)
            break
    with open(path, "w") as f:
        f.write("\n".join(lines) + "\n")
    report = verify_decisions(read_trace(path))
    assert not report.ok
    assert any(m["field"] == "n_final" for m in report.mismatches)


def test_reader_rejects_malformed(tmp_path):
    bad = tmp_path / "bad.jsonl"
    bad.write_text('{"kind": "plan", "t": 0}\n')      # no header
    with pytest.raises(ValueError):
        read_trace(str(bad))
    bad.write_text('{"kind": "header", "version": 99}\n')
    with pytest.raises(ValueError):
        read_trace(str(bad))
    bad.write_text('{"kind": "header", "version": %d}\n'
                   '{"kind": "nonsense"}\n' % TRACE_VERSION)
    with pytest.raises(ValueError):
        read_trace(str(bad))


def test_writer_counts_records(tmp_path):
    w = TraceWriter(str(tmp_path / "w.jsonl"), {"params": {}}, {})
    w.preempt(1.0, "spot", 2, 3)
    w.close()
    assert w.n_records == 2          # header + preempt
    with pytest.raises(AssertionError):
        w.write({"kind": "preempt"})


# --------------------------------------------------------------------------
# Grid scaling
# --------------------------------------------------------------------------
def test_scale_n_maps_sim_grid_onto_engine_grid():
    """Sim grid 50/5 -> reduced engine grid 10/2: scale by the iteration
    ratio, round UP to the engine stride, clamp at n_total; many-to-one
    at small n by design."""
    expect = {5: 2, 10: 2, 15: 4, 20: 4, 25: 6, 30: 6, 35: 8, 40: 8,
              45: 10, 50: 10}
    for n_final, n_scaled in expect.items():
        assert scale_n(n_final, 50, 10, 2) == n_scaled
    assert scale_n(0, 50, 10, 2) == 0
    assert scale_n(-3, 50, 10, 2) == 0
    rec = {"n_final": 35, "batch": 4}
    assert scaled_group_key(rec, 50, 10, 2) == (8, 4)


# --------------------------------------------------------------------------
# Engine-in-the-loop replay (real compiled programs; CPU-sized)
# --------------------------------------------------------------------------
@pytest.fixture(scope="module")
def traced_small(tmp_path_factory):
    path = str(tmp_path_factory.mktemp("replay") / "small.jsonl")
    run_fleet_sim(SimConfig(trace_out=path, **SMALL))
    return read_trace(path)


def test_replay_compile_count_is_distinct_scaled_keys(traced_small):
    trace = traced_small
    report = replay_through_engine(trace, max_records=8)
    cfg = stable_diffusion_v1.reduced()
    sim_n_total = int(trace.header["planner"]["params"]["n_total"])
    keys = {scaled_group_key(r, sim_n_total, cfg.n_total_iterations,
                             cfg.split_stride)
            for r in trace.dispatches()[:8]}
    # modeled (pure arithmetic) == measured (the engine's own counter)
    assert report.modeled_executables == len(keys)
    assert report.measured_executables == len(keys)
    assert report.measured_cache_misses == len(keys)
    assert report.measured_cache_hits == 8 - len(keys)
    assert report.modeled_cache_hits == report.measured_cache_hits
    assert report.measured_hit_rate == report.modeled_hit_rate
    # §4.3: the whole stream compiles within the quantization bound
    # (per batch size; 8 records here use at most the solo+batch pair)
    assert report.executable_bound == cfg.n_total_iterations \
        // cfg.split_stride + 1
    assert report.executed == 8
    assert report.skipped == len(trace.dispatches()) - 8
    # accounting: compile time exists, is NOT inside device_seconds, and
    # both are positive; every request shipped real bytes
    assert report.compile_seconds > 0
    assert report.device_seconds > 0
    assert report.bytes_shipped > 0
    assert report.requests == sum(r["batch"]
                                  for r in trace.dispatches()[:8])
    # reconciliation: a calibration ratio was fitted and every group got
    # a finite deviation measure
    assert report.calibration_ratio > 0
    assert all(math.isfinite(g.rel_dev) for g in report.groups)
    assert report.groups_total == len(keys)


def test_replay_determinism(traced_small):
    """Same trace + same seed -> identical counters and payload bytes
    (wall-clock fields excluded, obviously)."""
    r1 = replay_through_engine(traced_small, max_records=4)
    r2 = replay_through_engine(traced_small, max_records=4)
    for field in ("modeled_executables", "measured_executables",
                  "measured_cache_hits", "measured_cache_misses",
                  "bytes_shipped", "requests", "executed",
                  "device_only"):
        assert getattr(r1, field) == getattr(r2, field), field
    assert [g.measured_bytes for g in r1.groups] \
        == [g.measured_bytes for g in r2.groups]


# --------------------------------------------------------------------------
# Engine accounting bugfixes
# --------------------------------------------------------------------------
@pytest.fixture(scope="module")
def dmodel():
    cfg = stable_diffusion_v1.reduced()
    params = diffusion.init_params(cfg, jax.random.PRNGKey(0))
    return cfg, params


def _mk_requests(cfg, n, start=0):
    toks = np.zeros((1, cfg.text_len), np.int32)
    return [Request(f"q{start + i}", DeviceProfile(f"q{start + i}", 1.0),
                    toks, toks) for i in range(n)]


def _diffusion_engine(cfg, params):
    cost = CostParams(r_cloud=10.0, n_total=cfg.n_total_iterations,
                      n_step=cfg.split_stride, t_lim=5.0, k_decode=1.0)
    return DiffusionSplitEngine(params, cfg, cost, link=LOCAL_LINK)


def _lm():
    lcfg = reduced_config("qwen2-7b")
    return lcfg, lm_init(lcfg, jax.random.PRNGKey(0))


def _diffusion_cloud(cfg, params):
    eng = _diffusion_engine(cfg, params)
    return eng, lambda i: eng.process_group(_mk_requests(cfg, 1, start=i),
                                            n_cloud=4)


def _diffusion_device(cfg, params):
    (res,) = _diffusion_engine(cfg, params).process_group(
        _mk_requests(cfg, 1), n_cloud=4)
    device = DiffusionDeviceSim(params, cfg)
    return device, lambda i: device.complete(res)


def _layer_cloud(cfg, params):
    lcfg, lparams = _lm()
    eng = LayerSplitEngine(lparams, lcfg, link=LOCAL_LINK)
    batch = {"tokens": np.zeros((1, 8), np.int32)}
    return eng, lambda i: eng.process(batch, stop_group=1)


def _layer_device(cfg, params):
    lcfg, lparams = _lm()
    payload, _ = LayerSplitEngine(lparams, lcfg, link=LOCAL_LINK).process(
        {"tokens": np.zeros((1, 8), np.int32)}, stop_group=1)
    device = LayerSplitDevice(lparams, lcfg)
    return device, lambda i: device.complete(payload, 1)


@pytest.mark.parametrize("make", [_diffusion_cloud, _diffusion_device,
                                  _layer_cloud, _layer_device],
                         ids=["DiffusionSplitEngine", "DiffusionDeviceSim",
                              "LayerSplitEngine", "LayerSplitDevice"])
def test_compile_time_split_from_device_seconds(dmodel, make):
    """Every engine's program cache: a miss charges compile_seconds, NOT
    device_seconds; a repeat key is a hit and compiles nothing more."""
    eng, call = make(*dmodel)
    call(0)
    s = eng.stats
    assert s["executables"] == s["cache_misses"] == 1
    assert s["cache_hits"] == 0
    assert s["compile_seconds"] > 0
    assert s["device_seconds"] > 0
    assert s["requests"] == 1
    compile_after_first = s["compile_seconds"]
    # warm path: same key -> a hit, no new compile time
    call(1)
    assert s["cache_hits"] == 1
    assert s["cache_misses"] == s["executables"] == 1
    assert s["compile_seconds"] == compile_after_first
    assert s["requests"] == 2
    # steady-state execution is far cheaper than compilation here; the
    # old accounting (compile inside the timed call) made request 1 look
    # ~10x slower than request 2 — now both timings are same-scale
    assert s["compile_seconds"] > s["device_seconds"]


def test_engine_assign_uses_plan_cache(dmodel):
    """assign() goes through the planner's memoized hot path: repeat
    profiles hit the PlanCache, values stay identical to the audited
    plan(), and set_t_lim invalidates (epoch rules)."""
    cfg, _ = dmodel
    cost = CostParams(r_cloud=31.25, n_total=50, n_step=5, t_lim=10.0,
                      k_decode=1.0)
    # assign() never touches params/jax, so an empty params dict is fine
    eng = DiffusionSplitEngine({}, cfg, cost, link=LOCAL_LINK)
    cache = eng.planner.cache
    assert cache is not None, "engine planner must carry a PlanCache"
    profs = [DeviceProfile(f"d{i}", r_dev=1.0 + 0.5 * (i % 3))
             for i in range(12)]
    n_cached = [eng.assign(p) for p in profs]
    assert cache.misses == 3                  # 3 distinct r_dev values
    assert cache.hits == 9
    # cached == uncached == audited, value for value
    uncached = Planner(cost, policy="variable",
                       solve_c_batch=cost.c_batch, cache=False)
    for p, nf in zip(profs, n_cached):
        assert uncached.plan_profile(p).n_final == nf
        assert eng.plan(p).n_final == nf      # audited path agrees
    # epoch invalidation: an SLA change must re-solve, not serve stale
    hits_before = cache.hits
    eng.planner.set_t_lim(3.0)
    n_tight = eng.assign(profs[0])
    assert cache.misses == 4
    assert cache.hits == hits_before
    assert n_tight != n_cached[0]             # tighter SLA, bigger split


def test_unified_stats_schema(dmodel):
    """Both engines (and both device sims) report the same stats keys —
    the replay reconciler reads either."""
    cfg, params = dmodel
    d_eng = _diffusion_engine(cfg, params)
    lcfg, lparams = _lm()
    l_eng = LayerSplitEngine(lparams, lcfg, link=LOCAL_LINK)
    assert set(d_eng.stats) == set(l_eng.stats) == set(ENGINE_STATS_KEYS)
    assert set(DiffusionDeviceSim(params, cfg).stats) \
        == set(LayerSplitDevice(lparams, lcfg).stats) \
        == set(ENGINE_STATS_KEYS)
