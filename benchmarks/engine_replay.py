"""Engine-in-the-loop replay benchmark: simulation claims vs the system.

Records a fleet-sim decision trace (the PR-5 golden-trace workload),
verifies every recorded plan/replan decision re-derives exactly from
the trace header's planner config, then executes the trace's dispatch
records through a REAL ``DiffusionSplitEngine`` executable cache on the
reduced stable-diffusion config and reconciles:

  * modeled vs MEASURED executable count and cache hit rate (the §4.3
    quantization claim: a whole fleet's dispatch stream compiles at
    most ``n_total/n_step + 1`` programs),
  * modeled GPU-seconds vs measured device seconds per group — a single
    calibration ratio (CPU engine vs the modeled A100-class rate) plus
    per-group relative deviation with a tolerance report; compile time is
    accounted separately (``stats["compile_seconds"]``, the PR-6
    engine bugfix) so the comparison is steady-state execution,
  * modeled vs measured boundary payload bytes (wire-format overhead
    over the paper's Table-2 payload table).

The full run adds a preemption cell (scripted reclaim trace) so
``replan_preempted`` records are verified and replayed too.  Results
land in ``BENCH_fleet_sim.json["engine_replay"]``.

    PYTHONPATH=src python -m benchmarks.engine_replay            # full
    PYTHONPATH=src python -m benchmarks.engine_replay --smoke    # CI
"""
import argparse
import json
import os
import tempfile
import time

from repro.serving.fleet_sim import SimConfig, run_fleet_sim
from repro.serving.replay import (
    read_trace,
    replay_through_engine,
    verify_decisions,
)

#: the PR-5 golden-trace workload (tests/test_fleet_sim.py) — tracing it
#: must not perturb it, so this cell doubles as the bit-identity anchor
FULL_CELL = dict(seed=7, rate=12.0, duration=40.0, gpus_init=10,
                 max_gpus=32, metrics_interval_s=10.0)
SMOKE_CELL = dict(seed=7, rate=8.0, duration=15.0, gpus_init=10,
                  max_gpus=32, metrics_interval_s=10.0)
#: scripted spot reclaims (deterministic, unlike the Poisson hazard)
#: against the 2-class base+spot pool — exercises replan_preempted
#: records end to end
PREEMPT_CELL = dict(seed=7, rate=10.0, duration=30.0, dispatch="edf",
                    preempt_trace=[[10.0, "spot", 4], [18.0, "spot", 3]])


def _preempt_capacity():
    from repro.serving.simulator import table4_capacity
    return table4_capacity(base_count=4, spot_count=8, base_max=8,
                           spot_max=16)


def _cell(sim_kwargs, max_records, tolerance=0.75, keep_groups=True,
          capacity=None):
    """Trace -> verify -> engine replay for one sim config; returns the
    JSON cell (decision verification must be clean — a mismatch means
    the trace is not a faithful replay log, and the bench refuses to
    reconcile numbers against it).  ``capacity`` is passed to SimConfig
    but kept out of the recorded cell (not JSON-serializable)."""
    with tempfile.TemporaryDirectory() as td:
        path = os.path.join(td, "trace.jsonl")
        t0 = time.perf_counter()
        res = run_fleet_sim(SimConfig(trace_out=path, capacity=capacity,
                                      **sim_kwargs))
        sim_wall = time.perf_counter() - t0
        trace = read_trace(path)
        decisions = verify_decisions(trace)
        if not decisions.ok:
            raise AssertionError(
                f"decision replay mismatches: {decisions.to_json()}")
        t0 = time.perf_counter()
        report = replay_through_engine(trace, max_records=max_records,
                                       tolerance=tolerance)
    replay_wall = time.perf_counter() - t0
    d = report.to_json()
    if not keep_groups:
        del d["groups"]
    return {
        "sim": {k: v for k, v in sim_kwargs.items()},
        "sim_wall_s": round(sim_wall, 3),
        "replay_wall_s": round(replay_wall, 3),
        "arrivals": res.n_arrivals,
        "trace_records": len(trace.records),
        "decisions": decisions.to_json(),
        "replay": d,
    }


def bench(smoke: bool = False):
    t0 = time.perf_counter()
    cells = {}
    if smoke:
        cells["smoke"] = _cell(SMOKE_CELL, max_records=12,
                               keep_groups=False)
    else:
        cells["golden"] = _cell(FULL_CELL, max_records=60)
        cells["preemption"] = _cell(PREEMPT_CELL, max_records=30,
                                    keep_groups=False,
                                    capacity=_preempt_capacity())
    return {
        "bench": "engine_replay",
        "smoke": smoke,
        "wall_s": round(time.perf_counter() - t0, 2),
        "cells": cells,
    }


def run():
    """benchmarks.run surface (smoke-sized)."""
    payload = bench(smoke=True)
    rows = []
    for label, cell in payload["cells"].items():
        r = cell["replay"]
        rows.append((
            f"fleet_sim/engine_replay/{label}",
            cell["replay_wall_s"] * 1e6,
            f"exec={r['measured_executables']}/{r['modeled_executables']} "
            f"hit={r['measured_hit_rate']:.3f} "
            f"max_dev={r['max_rel_dev']:.3f} "
            f"compile_s={r['compile_seconds']:.1f}"))
    return rows


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("out", nargs="?", default="BENCH_fleet_sim.json")
    ap.add_argument("--smoke", action="store_true",
                    help="one small cell, few replayed dispatches (CI)")
    args = ap.parse_args()

    payload = bench(smoke=args.smoke)
    existing = {}
    if os.path.exists(args.out):
        with open(args.out) as f:
            try:
                existing = json.load(f)
            except ValueError:
                existing = {}
    existing["engine_replay"] = payload
    with open(args.out, "w") as f:
        json.dump(existing, f, indent=1)

    print(f"wrote engine_replay cells to {args.out} "
          f"({payload['wall_s']}s)")
    for label, cell in payload["cells"].items():
        r = cell["replay"]
        d = cell["decisions"]
        print(f"{label}: {d['n_plans']} plans + {d['n_replans']} replans "
              f"verified, {r['executed']}/{r['n_dispatches']} dispatches "
              f"executed -> executables {r['measured_executables']} "
              f"(modeled {r['modeled_executables']}, "
              f"bound {r['executable_bound']}), "
              f"hit_rate {r['measured_hit_rate']:.3f} "
              f"(modeled {r['modeled_hit_rate']:.3f}), "
              f"device_s {r['device_seconds']:.2f} "
              f"(+{r['compile_seconds']:.2f}s compile), "
              f"max_rel_dev {r['max_rel_dev']:.3f} "
              f"(tol {r['tolerance']}), "
              f"bytes_overhead {r['bytes_overhead'] * 100:.1f}%")


if __name__ == "__main__":
    main()
