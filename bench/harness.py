"""The benchmark harness: one cell, one seed, one run.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

It reads ``BENCHMARK.json`` for the cell's configuration and traffic
mix, loads ``bench/configs/<config>.py`` (the system under test and its
check) and ``bench/traffic/<mix>.json`` (parameters for
``bench/traffic.py``), sets up, measures for ``--seconds``, checks the
served answers against the plain reference, and prints one JSON line.
With ``--trace 1`` the window is traced and the cell's per-layer metrics
(``bench/metrics/<metric>.py``) are read from it; otherwise its
end-to-end metrics are printed.
"""
from __future__ import annotations

import argparse
import dataclasses
import importlib.util
import json
import math
import shutil
import sys
import time
from pathlib import Path
from typing import Any, Dict, List, Optional

import numpy as np

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
CACHE = ROOT / ".bench_cache"
#: longest a request due in the window may wait, after it, to be served
DRAIN_S = 60.0


def load_module(path: Path, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


def load_benchmark() -> dict:
    return json.loads((ROOT / "BENCHMARK.json").read_text())


def find(entries: List[dict], name: str, what: str) -> dict:
    for e in entries:
        if e["name"] == name:
            return e
    raise SystemExit(f"bench: no {what} named {name!r} in BENCHMARK.json")


def say(*args):
    print(*args, file=sys.stderr, flush=True)


# --------------------------------------------------------------------------
# Records of one run
# --------------------------------------------------------------------------
@dataclasses.dataclass
class Group:
    members: List[int]
    n_cloud: int
    start: float                  # seconds after the window opened
    end: float
    flops: int
    ok: bool = True


@dataclasses.dataclass
class Run:
    """Everything a metric reader may read."""
    cell: dict
    traffic: dict
    seconds: float
    setup_s: float = math.nan
    due: Dict[int, float] = dataclasses.field(default_factory=dict)
    done: Dict[int, float] = dataclasses.field(default_factory=dict)
    planned: Dict[int, int] = dataclasses.field(default_factory=dict)
    groups: List[Group] = dataclasses.field(default_factory=list)
    batch_size: int = 1
    backlog: int = 0
    late_s: float = 0.0           # how late the generator ran, at most
    peak_flops: float = math.nan
    trace: Any = None             # tracing.Trace of the window, traced runs


# --------------------------------------------------------------------------
# Open loop: arrivals on a schedule, the program plans and serves them
# --------------------------------------------------------------------------
def open_loop(system, schedule, run: Run, seed: int, annotate) -> list:
    """Serve ``schedule`` (arrivals due in [0, seconds)) open loop.

    Each arrival is planned by the program when it comes due.  One not
    admitted to a batching window runs alone; one admitted joins the
    window of its ``n_final``, which is dispatched when it holds
    ``batch_size`` requests or its tightest member's ``max_wait`` has
    passed.  Ready groups run one at a time, earliest due member first.
    Arrivals due in the window are drained after it closes."""
    from bench.traffic import derive
    B = system.batch_size
    pending = list(schedule)              # ascending due times
    windows: Dict[int, dict] = {}         # n_final -> {"members", "deadline"}
    ready: List[tuple] = []               # (first due, n_final, members)
    served = []
    t0 = time.perf_counter()
    clock = lambda: time.perf_counter() - t0    # noqa: E731
    i = 0
    while i < len(pending) or windows or ready:
        now = clock()
        if now > run.seconds + DRAIN_S:
            break
        while i < len(pending) and pending[i].due <= now:
            a = pending[i]
            i += 1
            run.late_s = max(run.late_s, now - a.due)
            run.due[a.index] = a.due
            with annotate("bench.plan"):
                n, admit, max_wait = system.plan(a)
            run.planned[a.index] = n
            if n <= 0:
                run.done[a.index] = clock()
                continue
            if not admit or B <= 1:
                ready.append((a.due, n, [a.index]))
                continue
            w = windows.setdefault(n, {"members": [], "deadline": math.inf,
                                       "first": a.due})
            w["members"].append(a.index)
            w["deadline"] = min(w["deadline"], a.due + max_wait)
            if len(w["members"]) >= B:
                ready.append((w["first"], n, w["members"]))
                del windows[n]
        now = clock()
        for n in [n for n, w in windows.items() if w["deadline"] <= now]:
            w = windows.pop(n)
            ready.append((w["first"], n, w["members"]))
        if ready:
            ready.sort(key=lambda g: g[0])
            _, n, members = ready.pop(0)
            gseed = derive(seed, 5, len(run.groups))
            start = clock()
            ok = True
            try:
                with annotate("bench.process_group"):
                    out = system.run_group(members, n, gseed)
            except Exception as e:            # counted, not fatal
                say(f"bench: group n={n} {members} raised {e!r}")
                out, ok = [], False
            end = clock()
            run.groups.append(Group(members, n, start, end,
                                    system.group_flops(n, len(members)), ok))
            for m in members:
                run.done[m] = end
            served.extend(out)
            continue
        nxt = [w["deadline"] for w in windows.values()]
        if i < len(pending):
            nxt.append(pending[i].due)
        if not nxt:
            break
        wait = min(nxt) - clock()
        if wait > 0:
            with annotate("bench.wait"):
                time.sleep(min(wait, 0.05))
    run.backlog = sum(1 for a in schedule if a.index not in run.done)
    return served


# --------------------------------------------------------------------------
# The run
# --------------------------------------------------------------------------
def parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def enable_cache(jax):
    """JAX's persistent cache at a fixed path in the checkout, with no
    size cap, so that every program of a cell is kept for the next run."""
    path = CACHE / "jax"
    path.mkdir(parents=True, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", str(path))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    jax.config.update("jax_compilation_cache_max_size", -1)
    return path


def device_info(jax, chips: int) -> dict:
    devs = jax.devices()
    if devs[0].platform == "cpu":
        raise SystemExit("bench: JAX found no accelerator; there is no CPU "
                         "path")
    if len(devs) < chips:
        raise SystemExit(f"bench: the cell needs {chips} chips, JAX has "
                         f"{len(devs)}")
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def memory_peak(jax) -> Optional[int]:
    peaks = []
    for d in jax.devices():
        stats = d.memory_stats() or {}
        if "peak_bytes_in_use" in stats:
            peaks.append(int(stats["peak_bytes_in_use"]))
    return max(peaks) if peaks else None


def metric_names(bench: dict, cell: str, kind: str) -> List[dict]:
    return [m for m in bench[kind]
            if "workloads" not in m or cell in m["workloads"]]


def read_metrics(entries: List[dict], run: Run) -> Dict[str, dict]:
    out = {}
    for m in entries:
        if m["name"] == "setup_s":
            value = run.setup_s
        else:
            mod = load_module(BENCH / "metrics" / f"{m['name']}.py",
                              "bench_metric_" + m["name"].replace(".", "_")
                              .replace("-", "_"))
            value = mod.read(run)
        if value is None:
            say(f"bench: metric {m['name']} found nothing to read")
            continue
        out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def main(argv=None, t_start: Optional[float] = None,
         system_hook=None, sizes: Optional[dict] = None,
         traffic_over: Optional[dict] = None,
         benchmark: Optional[dict] = None) -> int:
    """Run one cell.  ``system_hook`` and ``sizes`` are for tests on the
    CPU: the hook may break the system under test after it is built,
    ``sizes`` replaces configuration sizes and ``traffic_over`` traffic
    parameters.  With a hook the device check
    is skipped."""
    t_start = t_start if t_start is not None else time.perf_counter()
    args = parse(argv)
    bench = benchmark or load_benchmark()
    cell = find(bench["workloads"], args.workload, "workload")
    cfg_entry = find(bench["configs"], cell["config"], "config")
    spec = json.loads((ROOT / cfg_entry["file"]).read_text())
    traffic = json.loads(
        (BENCH / "traffic" / f"{cell['traffic']}.json").read_text())
    if not (ROOT / "src" / "repro").is_dir():
        raise SystemExit("bench: the program (src/repro) is not in this "
                         "checkout")
    sys.path.insert(0, str(ROOT / "src"))
    sys.path.insert(0, str(ROOT))

    import jax
    if system_hook is None:
        enable_cache(jax)
        device = device_info(jax, cell["chips"])
    else:
        d = jax.devices()[0]
        device = {"platform": d.platform, "kind": d.device_kind,
                  "count": len(jax.devices())}
    if sizes:
        spec = dict(spec, sizes=dict(spec["sizes"], **sizes))
    if traffic_over:
        traffic = dict(traffic, **traffic_over)
    from bench import tracing
    from bench.peaks import peak
    from bench.traffic import derive, open_loop_schedule, prompt_tokens

    mod = load_module(ROOT / cfg_entry["file"].replace(".json", ".py"),
                      "bench_config_" + cell["config"].replace("-", "_")
                      .replace(".", "_"))
    system = mod.System(spec, traffic, derive(args.seed, 0))
    run = Run(cell=cell, traffic=traffic, seconds=args.seconds,
              batch_size=getattr(system, "batch_size", 1),
              peak_flops=(peak(device["kind"], device["platform"])["flops"]
                          if system_hook is None else math.nan))
    say(f"bench: {cell['name']} seed {args.seed} on {device}")

    # -- set-up: inputs, weights, every program the window uses ---------------
    schedule = open_loop_schedule(traffic, args.seed, args.seconds)
    keys = system.prepare(schedule, prompt_tokens)
    say(f"bench: {len(schedule)} arrivals, (n_final, batch) {keys}")
    t = time.perf_counter()
    system.build()
    say(f"bench: weights {time.perf_counter() - t:.3f} s")
    if system_hook is not None:
        system_hook(system)
    t = time.perf_counter()
    stats = system.warm(keys)
    say(f"bench: warm-up {time.perf_counter() - t:.3f} s, engine {stats}")
    annotate = tracing.annotator(args.trace)
    trace_dir = CACHE / "trace"
    if args.trace:
        shutil.rmtree(trace_dir, ignore_errors=True)
        jax.profiler.start_trace(str(trace_dir))
    run.setup_s = time.perf_counter() - t_start
    say(f"bench: setup_s {run.setup_s:.3f}")

    # -- the window --------------------------------------------------------
    t_window = time.perf_counter()
    with annotate("bench.window"):
        served = open_loop(system, schedule, run, args.seed, annotate)
    wall = time.perf_counter() - t_window
    if args.trace:
        jax.profiler.stop_trace()
    mem = memory_peak(jax)

    # -- after the window: failures, the check, the metrics --------------------
    attempted = sum(len(g.members) for g in run.groups)
    failed = sum(len(g.members) for g in run.groups if not g.ok)
    failed += sum(1 for s in served if not system.finite(s))
    say(f"bench: {attempted} requests served, {run.backlog} left when "
        f"the drain ended, generator late by at most {run.late_s:.4f} s, "
        f"{len(run.groups)} groups, window+drain {wall:.3f} s")
    if args.trace:
        t = time.perf_counter()
        run.trace = tracing.load(trace_dir, window_s=wall,
                                 first=system.first_program)
        say(f"bench: trace read in {time.perf_counter() - t:.3f} s, "
            f"device clock moved by {run.trace.shift!r} s")
    entries = metric_names(bench, cell["name"],
                           "per_layer" if args.trace else "end_to_end")
    metrics = read_metrics(entries, run)
    for name, m in metrics.items():
        say(f"bench: {name} = {m['value']!r} {m['unit']}")

    t = time.perf_counter()
    system.release()
    rng = np.random.default_rng(derive(args.seed, 7))
    checks = system.check_run(served, rng)
    say(f"bench: reference check {time.perf_counter() - t:.3f} s")
    # the numbers with a limit in the configuration are compared; any
    # other reading is printed for the record
    limits = spec["limits"]
    compared = {n: v for n, v in checks.items() if n in limits}
    ok = failed == 0 and attempted > 0 and len(compared) == len(limits)
    for name, value in compared.items():
        ok = ok and value <= limits[name]
    result = {"correct": bool(ok), "attempted": attempted, "failed": failed,
              "metrics": metrics,
              "device": dict(device, memory_peak_bytes=mem)}
    if args.trace:
        result["device"]["busy_s"] = run.trace.busy_s
        result["device"]["window_s"] = run.trace.window_s
        result["breakdown"] = run.trace.breakdown()
    result["checks"] = {n: {"value": v, "limit": limits[n]}
                        for n, v in compared.items()}
    result["checks"]["failed_requests"] = {"value": failed, "limit": 0}
    for n, v in checks.items():
        if n not in limits:
            say(f"reading {n}: {v!r} (not compared)")
    for n, v in compared.items():
        say(f"check {n}: {v!r} limit {limits[n]!r} "
            f"{'ok' if v <= limits[n] else 'FAILED'}")
    say(f"check failed_requests: {failed} limit 0")
    print(json.dumps(result), flush=True)
    return 0
