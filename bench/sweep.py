#!/usr/bin/env python3
"""Find the knee of an open-loop cell: the highest Poisson rate the
system sustains without a growing backlog.

    python3 bench/sweep.py --workload sd-v1.fleet-poisson --seed 11 \
        --seconds 40 --rates 0.4,0.55,0.7,0.85,1.0

One process: the weights and every program are built once, then each
rate gets a window of its own (stratified Poisson arrivals at that rate,
the cell's fleet and planner), drained before the next.  For each rate it
prints the offered and served rates, the chip's held share (summed group
wall time over the window), the backlog when the window closed, and
latency quantiles of the first and second half of the window: a backlog
that grows shows as a second half slower than the first.  The result
goes to stdout as one JSON line.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

os.environ.setdefault("TPU_LOG_DIR", "disabled")
sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

import numpy as np  # noqa: E402

from bench import harness  # noqa: E402


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", required=True)
    args = ap.parse_args(argv)
    rates = [float(r) for r in args.rates.split(",")]
    bench = harness.load_benchmark()
    cell = harness.find(bench["workloads"], args.workload, "workload")
    cfg_entry = harness.find(bench["configs"], cell["config"], "config")
    spec = json.loads((harness.ROOT / cfg_entry["file"]).read_text())
    traffic = json.loads((harness.BENCH / "traffic"
                          / f"{cell['traffic']}.json").read_text())
    sys.path.insert(0, str(harness.ROOT / "src"))
    import jax
    harness.enable_cache(jax)
    harness.device_info(jax, cell["chips"])
    from bench.traffic import derive, open_loop_schedule, prompt_tokens
    mod = harness.load_module(
        harness.ROOT / cfg_entry["file"].replace(".json", ".py"), "cfgmod")
    system = mod.System(spec, traffic, derive(args.seed, 0))
    scheds = {}
    for k, r in enumerate(rates):
        t = dict(traffic, arrivals={"process": "poisson", "rate": r})
        s = open_loop_schedule(t, derive(args.seed, 100 + k), args.seconds)
        for a in s:
            a.index += 10000 * (k + 1)
        scheds[r] = s
    keys = sorted(set().union(*(system.prepare(s, prompt_tokens)
                                for s in scheds.values())))
    system.build()
    t = time.perf_counter()
    system.warm(keys)
    harness.say(f"sweep: (n_final, batch) {keys}, warm-up {time.perf_counter() - t:.1f} s,"
                f" set-up {time.perf_counter() - T_START:.1f} s")
    out = []
    for r in rates:
        run = harness.Run(cell=cell, traffic=traffic, seconds=args.seconds,
                          batch_size=system.batch_size)
        harness.open_loop(system, scheds[r], run, args.seed,
                          lambda name: contextlib.nullcontext())
        lat = {i: run.done[i] - run.due[i] for i in run.due if i in run.done}
        first = [v for i, v in lat.items() if run.due[i] < args.seconds / 2]
        second = [v for i, v in lat.items() if run.due[i] >= args.seconds / 2]
        held = sum(g.end - g.start for g in run.groups)
        open_at_close = sum(1 for i in run.due
                            if run.done.get(i, 1e9) > args.seconds)
        row = {
            "rate": r, "arrivals": len(scheds[r]),
            "served_per_s": len(lat) / max(g.end for g in run.groups),
            "held_share": held / args.seconds,
            "open_at_close": open_at_close,
            "groups": len(run.groups),
            "mean_batch": float(np.mean([len(g.members) for g in run.groups])),
            "p50_s": float(np.percentile(list(lat.values()), 50)),
            "p90_s": float(np.percentile(list(lat.values()), 90)),
            "p90_first_half_s": float(np.percentile(first, 90)),
            "p90_second_half_s": float(np.percentile(second, 90)),
            "s_per_image": held / len(lat),
        }
        harness.say("sweep: " + json.dumps(row))
        out.append(row)
    print(json.dumps({"workload": args.workload, "seconds": args.seconds,
                      "rows": out}))


if __name__ == "__main__":
    main()
