"""The danube-1.8b cell on the CPU at a small size: the operation count
against a hand count, injected faults that must read ``correct: false``,
the control against the limits, and the traffic file.

    JAX_PLATFORMS=cpu python -m pytest -q bench/tests/test_danube.py
"""
import dataclasses
import importlib.util
import json
from pathlib import Path

import numpy as np
import pytest

from bench import harness

CONFIGS = Path(__file__).resolve().parents[1] / "configs"
WORKLOAD = "danube-1.8b.prefill-g18"
#: the published depth and split at the reduced widths
SMALL = dict(num_layers=24, d_model=64, num_heads=4, num_kv_heads=1,
             head_dim=16, d_ff=128, vocab_size=512, window=32)
#: 128-token prompts (the window of 32 binds), fast enough arrivals that
#: groups fill to two
TRAFFIC = {"prompt_tokens": 128,
           "arrivals": {"process": "poisson", "rate": 2.0}}


def load_ref():
    spec = importlib.util.spec_from_file_location(
        "danube_ref_flops", CONFIGS / "danube-1.8b.ref.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_flops_by_hand():
    ref = load_ref()
    s = dict(d_model=4, num_heads=2, num_kv_heads=1, head_dim=2, d_ff=8,
             window=3)
    # q 4x(2x2), k and v 4x(1x2), out (2x2)x4, gate and up 4x8, down 8x4
    per_token = 2 * (16 + 8 + 8 + 16 + 32 + 32 + 32)
    assert ref.matmul_flops_per_token(s) == per_token == 288
    # 5 queries see 1, 2, 3, 3, 3 keys; scores and sum over 2 heads of 2
    attn = 2 * 2 * 2 * 2 * (1 + 2 + 3 + 3 + 3)
    assert ref.attention_flops(s, 5) == attn == 192
    assert ref.group_flops(s, 2, 3, 5) == 3 * 2 * (5 * per_token + attn)
    assert ref.visible_keys(5, 0) == 15


def test_published_counts():
    """The counts at the cell's sizes, as PERF.md gives them."""
    ref = load_ref()
    s = json.loads((CONFIGS / "danube-1.8b.json").read_text())["sizes"]
    assert round(ref.matmul_flops_per_token(s) / 1e6, 1) == 138.9
    assert round(ref.attention_flops(s, 8192) / 8192 / 1e6, 1) == 31.5
    assert round(ref.group_flops(s, 18, 2, 8192) / 1e12, 1) == 50.3


def run_cell(capsys, hook):
    rc = harness.main(["--workload", WORKLOAD, "--seed", str(2**31 + 77),
                       "--seconds", "5", "--trace", "0"],
                      system_hook=hook, sizes=SMALL, traffic_over=TRAFFIC)
    assert rc == 0
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


def wrap(system, change):
    """Pass every group's batch and payload through ``change``."""
    engine = system.engine
    real = engine.process

    def broken(batch, stop_group):
        return change(real, batch, stop_group)
    engine.process = broken


def layer_short(system):
    """The payload leaves after 17 layers, not 18."""
    wrap(system, lambda real, batch, g: real(batch, g - 1))


def rows_swapped(system):
    """The first two rows of a group's payload change places."""
    def change(real, batch, g):
        payload, t = real(batch, g)
        if len(payload) > 1:
            payload = payload[[1, 0] + list(range(2, len(payload)))]
        return payload, t
    wrap(system, change)


def rebuilt(**kw):
    """The cloud half runs the configuration with ``kw`` changed."""
    def hook(system):
        from repro.serving.engine import LayerSplitEngine
        cfg = dataclasses.replace(system.cfg, **kw)
        system.engine = LayerSplitEngine(system.params, cfg, link=system.link)
    return hook


def sound(system):
    pass


@pytest.mark.parametrize("fault,correct", [
    (sound, True), (layer_short, False), (rebuilt(window=0), False),
    (rows_swapped, False), (rebuilt(norm_eps=1e-6), False)],
    ids=["sound", "17_layers", "no_window", "rows_swapped", "eps_1e-6"])
def test_danube_fault(capsys, fault, correct):
    out = run_cell(capsys, fault)
    assert out["attempted"] > 0
    assert out["correct"] is correct, out["checks"]


def test_control_fails_program_passes():
    from bench.control import readings
    bench = harness.load_benchmark()
    cell = harness.find(bench["workloads"], WORKLOAD, "workload")
    entry = harness.find(bench["configs"], cell["config"], "config")
    spec = json.loads((harness.ROOT / entry["file"]).read_text())
    spec = dict(spec, sizes=dict(spec["sizes"], **SMALL))
    traffic = json.loads((harness.BENCH / "traffic"
                          / f"{cell['traffic']}.json").read_text())
    traffic = dict(traffic, **TRAFFIC)
    mod = harness.load_module(harness.ROOT / entry["file"].replace(
        ".json", ".py"), "cfg_danube_control")
    row = readings(mod, spec, traffic, cell, 2**31 + 5, 4.0, True, {})
    limits = spec["limits"]
    assert all(row["program"][k] <= v for k, v in limits.items()), row
    assert any(row["control"][k] > v for k, v in limits.items()), row


def test_traffic_file_schedule():
    """``open_loop_schedule`` reads the cell's file: the same arrivals for
    every run seed, at the file's rate, with their own prompt seeds."""
    from bench.traffic import open_loop_schedule
    mix = json.loads((harness.BENCH / "traffic"
                      / "prefill-g18.json").read_text())
    a = open_loop_schedule(mix, 2**31 + 1, 51.0)
    b = open_loop_schedule(mix, 2**33 + 9, 51.0)
    assert len(a) == round(mix["arrivals"]["rate"] * 51.0)
    assert [x.due for x in a] == [x.due for x in b]
    assert all(0.0 <= x.due < 51.0 for x in a)
    assert len({x.token_seed for x in a}) == len(a)
    assert [x.token_seed for x in a] != [x.token_seed for x in b]
    assert mix["prompt_tokens"] == 8192
    assert mix["planner"] == {"split": 18, "batch_size": 2, "max_wait_s": 0.5}
    assert np.all(np.diff([x.due for x in a]) > 0)
