"""The trace reductions, on a small trace recorded on one TPU v5e by
``bench/record_trace.py``: three ``bench.process_group`` spans, each
around one run of a jitted chain of eight 2048x2048 matmuls, each
followed by a ``bench.wait`` span of 0.1 s of sleep, inside
``bench.window``."""
import importlib.util
from pathlib import Path
from types import SimpleNamespace

import pytest

from bench import tracing

BENCH = Path(__file__).resolve().parents[1]


@pytest.fixture(scope="module")
def raw():
    from jax.profiler import ProfileData
    return ProfileData.from_file(str(BENCH / "data"
                                     / "small_trace.xplane.pb"))


@pytest.fixture(scope="module")
def trace(raw):
    return tracing.from_profile(raw, first="jit_work")


def modules_from(raw):
    """The device's program events, read straight from the planes."""
    out = []
    for p in raw.planes:
        if p.name == "/device:TPU:0":
            for line in p.lines:
                if line.name == "XLA Modules":
                    out += [(e.start_ns * 1e-9, e.duration_ns * 1e-9, e.name)
                            for e in line.events]
    return out


def reader(name):
    spec = importlib.util.spec_from_file_location(
        name.replace(".", "_"), BENCH / "metrics" / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_spans_and_window(trace):
    spans = trace.spans_named("bench.process_group")
    assert len(spans) == 3
    assert len(trace.spans_named("bench.wait")) == 3
    assert 0.30 < trace.window_s < 0.32


def test_device_clock_aligned(raw, trace):
    # before the shift each program ends before its launching span begins;
    # after it, each lies inside its span
    mods = modules_from(raw)
    spans = trace.spans_named("bench.process_group")
    assert len(mods) == 3
    for (s, e, _), (m0, dur, name) in zip(spans, mods):
        assert name.startswith("jit_work")
        assert m0 + dur < s
        assert s <= m0 + trace.shift and m0 + trace.shift + dur <= e
    assert 0.0005 < trace.shift < 0.005


def test_busy_is_the_programs(raw, trace):
    mods = modules_from(raw)
    total = sum(d for _, d, _ in mods)
    assert trace.busy_s == pytest.approx(total, rel=1e-3)
    for (s, e, _), (_, dur, _) in zip(trace.spans_named("bench.process_group"),
                                      mods):
        assert trace.busy_in(s, e) == pytest.approx(dur, rel=1e-3)
        assert trace.module_time_in(s, e) == pytest.approx(dur, rel=1e-6)


def test_idle_gaps_labelled_by_host(trace):
    bd = trace.breakdown()
    gaps = dict(bd["idle_gaps"])
    assert gaps["bench.wait"] > 0.29
    assert sum(gaps.values()) == pytest.approx(trace.window_s - trace.busy_s,
                                               rel=1e-9)
    ops = bd["device_ops"]
    assert ops[0][0] == "convolution_tanh_fusion"
    assert ops[0][1] == pytest.approx(trace.busy_s, rel=0.01)


def test_self_times_nest():
    ev = [(0.0, 10.0, "%while.1 = while"), (1.0, 3.0, "%fusion.2 = f"),
          (4.0, 5.0, "%fusion.3 = f"), (11.0, 12.0, "%copy.4 = c")]
    got = dict()
    for name, t in tracing.self_times(ev):
        got[tracing.op_family(name)] = got.get(tracing.op_family(name), 0) + t
    assert got == {"while": 7.0, "fusion": 3.0, "copy": 1.0}


def test_align_back_to_back_spans():
    # device clock 1 ms early; each span runs encode, then denoise, then a
    # short pull program that ends just before the span does, and the next
    # span opens at once: the pull falls inside the next span's lookback
    lead, spans, mods = 0.001, [], []
    for k in range(5):
        s = 1.0 + 0.05 * k
        spans.append((s, s + 0.05, "bench.process_group"))
        for a, b, name in ((s + 0.0002, s + 0.002, "jit_encode_prompt(1)"),
                           (s + 0.002, s + 0.045, "jit_denoise(2)"),
                           (s + 0.046, s + 0.0495, "jit_pull(3)")):
            mods.append((a - lead, b - lead, name))
    modules = {"/device:TPU:0": mods}
    shift = tracing.align(modules, spans, first="jit_encode_prompt")
    assert shift == pytest.approx(lead - 0.0002, abs=1e-12)
    # without the name the previous span's pull would set the lead
    assert tracing.align(modules, spans) > 0.004


def test_readers_on_the_trace(trace):
    spans = trace.spans_named("bench.process_group")
    run = SimpleNamespace(trace=trace)
    host = reader("engine_host_s_per_group.poisson").read(run)
    want = sum((e - s) - trace.busy_in(s, e) for s, e, _ in spans) / 3
    assert host == pytest.approx(want) and 0.0005 < host < 0.002


def test_nothing_to_read_gives_nothing():
    run = SimpleNamespace(trace=None)
    assert reader("engine_host_s_per_group.poisson").read(run) is None
    assert reader("unet_mfu.poisson").read(
        SimpleNamespace(groups=[], peak_flops=197e12)) is None
    assert reader("denoise_s_per_image_step.poisson").read(run) is None
