"""tools/calibrate_r_cloud.py — the roofline-vs-measured calibration
hook."""
import importlib.util
import json
import subprocess
import sys
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parent.parent


def _load_tool():
    spec = importlib.util.spec_from_file_location(
        "calibrate_r_cloud", REPO / "tools" / "calibrate_r_cloud.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _records():
    return [
        {"arch": "sd", "cell": "decode", "mesh": "16x16",
         "r_cloud_est": {"v5e": 50.0, "h100": 100.0, "a100": 60.0}},
        {"arch": "sd", "cell": "train_4k", "mesh": "16x16",
         "r_cloud_est": {"v5e": 2.0, "h100": 4.0}},
        {"arch": "sd", "cell": "decode", "mesh": "16x16",
         "status": "SKIP"},                       # no estimate: untouched
    ]


def test_calibrate_record_emits_ratio_column():
    tool = _load_tool()
    rec = _records()[0]
    # measured 25 ms/step = 40 steps/s vs the 50 steps/s v5e estimate
    out = tool.calibrate_record(rec, 0.025, hw="v5e")
    assert out["calibration_ratio"] == pytest.approx(40.0 / 50.0)
    assert out["r_cloud_measured"] == pytest.approx(40.0)
    assert out["calibration_hw"] == "v5e"
    assert out["step_time_measured_s"] == 0.025
    # a record without the estimate is a no-op
    bare = tool.calibrate_record({"arch": "x"}, 0.025)
    assert "calibration_ratio" not in bare


def test_apply_timings_matches_by_arch_cell():
    tool = _load_tool()
    records = _records()
    n = tool.apply_timings(records, {("sd", "decode"): 0.02}, hw="v5e")
    assert n == 1
    assert records[0]["calibration_ratio"] == pytest.approx(1.0)
    assert "calibration_ratio" not in records[1]


def test_calibrated_capacity_scales_class_rates():
    tool = _load_tool()
    records = _records()[:1]
    baseline = tool.calibrated_capacity([dict(records[0])])
    tool.calibrate_record(records[0], 1.0 / 25.0, hw="v5e")  # ratio 0.5
    scaled = tool.calibrated_capacity(records)
    for cls in scaled:
        assert cls.r_cloud == pytest.approx(baseline[cls.name].r_cloud
                                            * 0.5)
    with pytest.raises(ValueError):
        tool.calibrated_capacity([{"no": "estimates"}])


def test_cli_round_trip(tmp_path):
    """End-to-end offline invocation: jsonl in, calibration_ratio
    column + capacity artifact out."""
    dryrun = tmp_path / "dryrun.jsonl"
    with open(dryrun, "w") as f:
        for rec in _records():
            f.write(json.dumps(rec) + "\n")
    out = tmp_path / "calibrated.jsonl"
    cap_out = tmp_path / "capacity.json"
    proc = subprocess.run(
        [sys.executable, str(REPO / "tools" / "calibrate_r_cloud.py"),
         "--dryrun", str(dryrun), "--arch", "sd", "--cell", "decode",
         "--step-time", "0.025", "--out", str(out),
         "--capacity-out", str(cap_out)],
        capture_output=True, text=True)
    assert proc.returncode == 0, proc.stderr
    rows = [json.loads(line) for line in open(out)]
    assert len(rows) == 3                     # every record written back
    assert rows[0]["calibration_ratio"] == pytest.approx(0.8)
    assert "calibration_ratio" not in rows[1]  # cell filter respected
    cap = json.load(open(cap_out))
    names = {c["name"] for c in cap}
    assert names == {"v5e", "h100", "a100"}
    # class rates carry the measured 0.8 scaling
    by_name = {c["name"]: c for c in cap}
    assert by_name["h100"]["r_cloud"] == pytest.approx(100.0 * 0.8)
