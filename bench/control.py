#!/usr/bin/env python3
"""Readings for a cell's correctness limits, in one process.

    python3 bench/control.py --workload sd-v1.fleet-poisson \
        --seeds 101,102,...,112 --control-seeds 3 --seconds 12 \
        [--also highest]

For each seed the cell's system is built from that seed (weights and
prompts), driven for ``--seconds`` through the same loop as a benchmark
run, and its sampled answers are compared with the plain reference at
the configuration's precision: the program's reading.  For the first
``--control-seeds`` seeds the same sampled requests are also answered by
the control (the reference one precision step below the
configuration's, ``System.control_reference``) and compared with the
reference: the control's reading.  ``--also`` adds, on those seeds,
readings of both against the reference at another precision.  Programs are compiled once and read
from the cache for later seeds.  One JSON line per seed goes to stdout.
"""
import argparse
import contextlib
import json
import os
import sys
import time
from pathlib import Path

os.environ.setdefault("TPU_LOG_DIR", "disabled")
sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

import numpy as np  # noqa: E402

from bench import harness  # noqa: E402


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", type=int, default=3)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--also", default=None,
                    help="another precision to read against")
    ap.add_argument("--sample", type=int, default=None,
                    help="requests compared per seed (default: the "
                         "configuration's check sample)")
    args = ap.parse_args(argv)
    seeds = [int(s) for s in args.seeds.split(",")]
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
    mod = harness.load_module(
        harness.ROOT / cfg_entry["file"].replace(".json", ".py"), "cfgmod")
    refs = {}
    for k, seed in enumerate(seeds):
        row = readings(mod, spec, traffic, cell, seed, args.seconds,
                       k < args.control_seeds, refs, args.also,
                       args.sample or spec["check"]["sample"])
        print(json.dumps(row), flush=True)


def readings(mod, spec, traffic, cell, seed, seconds, with_control, refs,
             also=None, k=3):
    """One seed: the program's reading and, if asked, the control's, on
    the same ``k`` sampled requests, worst over them and each one's
    (``<who>_each``: latent and context relative errors).  ``refs``
    keeps the compiled references."""
    from bench.traffic import derive, open_loop_schedule, prompt_tokens
    quiet = lambda name: contextlib.nullcontext()   # noqa: E731
    t = time.perf_counter()
    system = mod.System(spec, traffic, derive(seed, 0))
    run = harness.Run(cell=cell, traffic=traffic, seconds=seconds,
                      batch_size=system.batch_size)
    schedule = open_loop_schedule(traffic, seed, seconds)
    system.prepare(schedule, prompt_tokens)
    system.build()
    served = harness.open_loop(system, schedule, run, seed, quiet)
    samples = system.sample(served, np.random.default_rng(derive(seed, 7)),
                            k)
    system.release()
    also = also if with_control else None
    if "ref" not in refs:
        refs["ref"] = system.reference()
    if also and also not in refs:
        refs[also] = system.reference(also)
    if with_control and "control" not in refs:
        refs["control"] = system.control_reference()
    got = {"program": system.served_answers(samples)}
    if with_control:
        got["control"] = system.answers(samples, refs["control"])
    row = {"seed": seed, "served": len(served),
           "n_cloud": [s.n_cloud for s in samples],
           "rows": [[s.batch, s.row] for s in samples]}
    for ref_name in ["ref"] + ([also] if also else []):
        want = system.answers(samples, refs[ref_name])
        for who, answers in got.items():
            key = who if ref_name == "ref" else f"{who}_vs_{ref_name}"
            row[key] = system.compare(answers, want)
            row[key + "_each"] = [list(system.compare([g], [w]).values())
                                  for g, w in zip(answers, want)]
    row["seconds"] = time.perf_counter() - t
    return row


if __name__ == "__main__":
    main()
