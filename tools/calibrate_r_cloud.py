#!/usr/bin/env python3
"""Calibrate the roofline r_cloud estimates against measured step times.

The dry-run loop (``repro.launch.dryrun``) emits per-hardware serving
rates (``r_cloud_est``) derived from the analytic roofline; those rates
drive the per-class capacity model (``CloudCapacity.from_roofline``)
but were never validated against real hardware — the open ROADMAP Perf
item.  This tool closes the loop:

  1. read dryrun.jsonl records (one per arch x cell x mesh),
  2. take a MEASURED step time for each record from caller-supplied
     timings (``--step-time`` for a single record, ``--timings-json`` for
     a batch — numbers taken on the chip, e.g. from a profiler),
  3. emit each record back out with a ``calibration_ratio`` column
     (measured rate / roofline-estimated rate for ``--hw``; 1.0 means
     the roofline was exact, < 1 means hardware is slower than the
     model) and a ``r_cloud_measured`` value,
  4. optionally rebuild the capacity artifact from the CALIBRATED rates
     (``--capacity-out``): every class rate is scaled by the measured
     ratio, replacing hand calibration.

Examples:
    # one record, measured at 21.5 ms/step on the chip
    python tools/calibrate_r_cloud.py --dryrun dryrun.jsonl \
        --arch qwen2-7b --cell decode_32k --step-time 0.0215 \
        --out dryrun.jsonl --capacity-out capacity.json
"""
import argparse
import json
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent / "src"))


def load_records(path):
    records = []
    with open(path) as f:
        for line in f:
            line = line.strip()
            if line:
                records.append(json.loads(line))
    return records


def calibrate_record(rec, step_time_s, hw="v5e"):
    """Attach the measured-vs-roofline calibration columns to one
    dry-run record (returns the record; no-op when it carries no
    estimate for ``hw``)."""
    est = (rec.get("r_cloud_est") or {}).get(hw)
    if not est or step_time_s <= 0:
        return rec
    measured_rate = 1.0 / step_time_s
    rec["step_time_measured_s"] = step_time_s
    rec["r_cloud_measured"] = round(measured_rate, 4)
    rec["calibration_hw"] = hw
    rec["calibration_ratio"] = round(measured_rate / est, 4)
    return rec


def apply_timings(records, timings, hw="v5e"):
    """``timings``: {(arch, cell): step_seconds}.  Calibrates every
    matching record; returns the number calibrated."""
    n = 0
    for rec in records:
        key = (rec.get("arch"), rec.get("cell"))
        if key in timings:
            calibrate_record(rec, timings[key], hw=hw)
            n += "calibration_ratio" in rec and 1 or 0
    return n


def calibrated_capacity(records, counts=None, cell=None,
                        count_per_class=8):
    """``CloudCapacity.from_roofline`` over records whose estimates are
    SCALED by their measured calibration ratio — the roofline rates the
    fleet model consumes, anchored to real step times.  Records without
    a ratio contribute their raw estimates (ratio 1.0)."""
    from repro.core.capacity import CloudCapacity
    scaled = []
    for rec in records:
        est = rec.get("r_cloud_est")
        if not est:
            continue
        ratio = rec.get("calibration_ratio", 1.0)
        r2 = dict(rec)
        r2["r_cloud_est"] = {k: v * ratio for k, v in est.items()}
        scaled.append(r2)
    if not scaled:
        raise ValueError("no r_cloud_est records to calibrate")
    if counts is None:
        hw_names = sorted({h for r in scaled for h in r["r_cloud_est"]})
        counts = {h: count_per_class for h in hw_names}
    return CloudCapacity.from_roofline(scaled, counts=counts, cell=cell)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--dryrun", default="dryrun.jsonl",
                    help="dry-run records to calibrate (jsonl)")
    ap.add_argument("--arch", default=None)
    ap.add_argument("--cell", default=None)
    ap.add_argument("--hw", default="v5e",
                    help="hardware class whose roofline estimate the "
                         "measurement is compared against")
    ap.add_argument("--step-time", type=float, default=None,
                    help="measured seconds/step for the --arch/--cell "
                         "records (offline calibration)")
    ap.add_argument("--timings-json", default=None,
                    help='JSON file {"arch/cell": seconds, ...}')
    ap.add_argument("--out", default=None,
                    help="write calibrated records here (jsonl; default "
                         "overwrite --dryrun in place)")
    ap.add_argument("--capacity-out", default=None,
                    help="write the calibration-scaled CloudCapacity "
                         "JSON artifact")
    args = ap.parse_args()

    records = load_records(args.dryrun)
    match = [r for r in records
             if (args.arch is None or r.get("arch") == args.arch)
             and (args.cell is None or r.get("cell") == args.cell)
             and r.get("r_cloud_est")]
    if not match:
        raise SystemExit(f"no records with r_cloud_est match "
                         f"--arch={args.arch} --cell={args.cell}")

    timings = {}
    if args.timings_json:
        with open(args.timings_json) as f:
            for key, sec in json.load(f).items():
                arch, _, cell = key.partition("/")
                timings[(arch, cell)] = float(sec)
    n = 0
    for rec in match:
        key = (rec.get("arch"), rec.get("cell"))
        if args.step_time is not None:
            sec = args.step_time
        elif key in timings:
            sec = timings[key]
        else:
            continue
        calibrate_record(rec, sec, hw=args.hw)
        if "calibration_ratio" in rec:
            n += 1
            print(f"{rec['arch']}/{rec['cell']} ({rec.get('mesh')}): "
                  f"measured {sec * 1e3:.2f} ms/step, roofline est "
                  f"{1.0 / rec['r_cloud_est'][args.hw] * 1e3:.2f} ms -> "
                  f"calibration_ratio={rec['calibration_ratio']}")
    if not n:
        raise SystemExit("nothing calibrated: pass --step-time or "
                         "--timings-json")

    out = args.out or args.dryrun
    with open(out, "w") as f:
        for rec in records:
            f.write(json.dumps(rec) + "\n")
    print(f"wrote {len(records)} records ({n} calibrated) to {out}")

    if args.capacity_out:
        cap = calibrated_capacity(match, cell=args.cell)
        with open(args.capacity_out, "w") as f:
            json.dump(cap.to_json(), f, indent=1)
        print(f"wrote {len(cap)} calibrated GPU classes to "
              f"{args.capacity_out}")


if __name__ == "__main__":
    main()
