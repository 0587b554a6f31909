"""The control — the plain reference one precision step below what the
configuration states — fails the cell's limits, and the program passes
them, at a size a CPU test can hold.  The readings at the cell's own
size come from ``bench/control.py`` on the chip (PERF.md)."""
import json

from bench import harness
from bench.tests.test_faults import SD_SMALL, WORKLOAD


def load(sizes):
    bench = harness.load_benchmark()
    cell = harness.find(bench["workloads"], WORKLOAD, "workload")
    entry = harness.find(bench["configs"], cell["config"], "config")
    spec = json.loads((harness.ROOT / entry["file"]).read_text())
    spec = dict(spec, sizes=dict(spec["sizes"], **sizes))
    traffic = json.loads((harness.BENCH / "traffic"
                          / f"{cell['traffic']}.json").read_text())
    mod = harness.load_module(harness.ROOT / entry["file"].replace(
        ".json", ".py"), "cfg_" + cell["config"].replace("-", "_"))
    return mod, spec, traffic, cell


def test_control_fails_program_passes():
    from bench.control import readings
    mod, spec, traffic, cell = load(SD_SMALL)
    row = readings(mod, spec, traffic, cell, 2**31 + 5, 4.0, True, {})
    limits = spec["limits"]
    print(row)
    assert all(row["program"][k] <= limit for k, limit in limits.items()), row
    assert any(row["control"][k] > limit for k, limit in limits.items()), row
