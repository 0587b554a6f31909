"""The reader of the program's spans and named scopes
(``bench/program_trace.py``), on two traces recorded on one TPU v5e:
``small_trace.xplane.pb`` (``bench/record_trace.py``: a jitted matmul
chain, no scopes, no program spans) and ``scoped_trace.xplane.pb``
(``bench/record_scoped_trace.py``: the engine serving two groups at a
small size, batch 1 then batch 2)."""
import importlib.util
import shutil
from pathlib import Path
from types import SimpleNamespace

import pytest

from bench import program_trace, tracing
from bench.harness import Group
from bench.record_scoped_trace import GROUPS

BENCH = Path(__file__).resolve().parents[1]
SMALL = BENCH / "data" / "small_trace.xplane.pb"
SCOPED = BENCH / "data" / "scoped_trace.xplane.pb"
NEW = ("unet_resblock_s_per_image_step.poisson",
       "unet_self_attn_s_per_image_step.poisson",
       "unet_cross_attn_s_per_image_step.poisson",
       "unet_mlp_s_per_image_step.poisson",
       "engine_pull_s_per_group.poisson",
       "engine_pack_s_per_request.poisson")


def reader(name):
    spec = importlib.util.spec_from_file_location(
        name.replace(".", "_"), BENCH / "metrics" / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def profile_data(path):
    from jax.profiler import ProfileData
    return ProfileData.from_file(str(path))


def traced_run(path, first, tmp_path, monkeypatch, groups=()):
    """A run as the harness leaves it after a traced window, with
    ``path`` as the window's profile."""
    d = tmp_path / "trace"
    d.mkdir()
    shutil.copy(path, d / path.name)
    monkeypatch.setattr(program_trace, "TRACE_DIR", d)
    trace = tracing.from_profile(profile_data(path), first=first)
    return SimpleNamespace(trace=trace, groups=list(groups))


@pytest.fixture
def scoped(tmp_path, monkeypatch):
    groups = [Group([0] * b, n, 0.0, 0.0, 0) for n, b in GROUPS]
    return traced_run(SCOPED, "jit_encode_prompt", tmp_path, monkeypatch,
                      groups)


def test_decoder_maps_ops_to_scope_paths():
    prof = program_trace.parse(SMALL)
    fused = {k: v for k, v in prof.tf_op.items()
             if k[1].startswith("%convolution_tanh_fusion")}
    assert len(fused) == 8
    assert set(fused.values()) == {"jit(work)/dot_general:"}
    # keyed by the program the ops run in, as its events name it
    assert {pid for pid, _ in fused} == {9815723411115578950}
    assert {m[2] for v in tracing.from_profile(profile_data(SMALL))
            .modules.values() for m in v} == {"jit_work(9815723411115578950)"}
    assert prof.spans == []


def test_scope_of():
    p = "jit(denoise_range)/while/body/closed_call/unet/up1/{}/add:"
    assert program_trace.scope_of(p.format("self_attn")) == ("up1",
                                                             "self_attn")
    assert program_trace.scope_of(p.format("jit(silu)")) == ("up1",
                                                             "unscoped")
    assert program_trace.scope_of(
        "jit(encode_prompt)/text_encoder/dot_general:") == ("-",
                                                           "text_encoder")
    assert program_trace.scope_of(None) == ("-", "unscoped")


def test_spans_on_the_harness_clock():
    # the reader's own decoding of the host plane gives the same events
    # and times as JAX's reader
    want = sorted((ev.start_ns * 1e-9, ev.end_ns * 1e-9, ev.name)
                  for p in profile_data(SCOPED).planes
                  if p.name.startswith("/host:")
                  for line in p.lines for ev in line.events
                  if ev.name.startswith("repro."))
    got = [(s, e, n) for s, e, n, _ in program_trace.parse(SCOPED).spans]
    assert len(got) == len(want) > 0
    for g, w in zip(got, want):
        assert g[2] == w[2]
        assert g[0] == pytest.approx(w[0], abs=1e-9)
        assert g[1] == pytest.approx(w[1], abs=1e-9)


def test_scoped_trace_spans(scoped):
    r = program_trace.read(scoped)
    groups = scoped.trace.spans_named("bench.process_group")
    assert len(groups) == len(GROUPS)
    engine = [s for s in r.spans if s[2] == "repro.engine.process_group"]
    assert len(engine) == len(groups)
    for (s, e, _), (a, z, _, args), (n, b) in zip(groups, engine, GROUPS):
        assert s <= a and z <= e
        assert (args["n_cloud"], args["batch"]) == (n, b)
        inner = [x for x in r.spans if a <= x[0] and x[1] <= z
                 and x[2] != "repro.engine.process_group"]
        assert [x[2].rsplit(".", 1)[1] for x in inner] == (
            ["encode_prompt", "denoise", "pull"] + ["pack"] * b)
    assert len(r.span_lengths("repro.engine.pack")) == sum(
        b for _, b in GROUPS)
    assert r.span_lengths("repro.engine.compile") == []


def test_scoped_trace_scopes(scoped):
    r = program_trace.read(scoped)
    for scope in ("resblock", "self_attn", "cross_attn", "mlp",
                  "xattn_proj", "guidance"):
        assert r.denoise_scope_s.get(scope, 0.0) > 0, scope
    levels = {lv for lv, _ in r.by_scope}
    assert {"down0", "down1", "mid", "up1", "up0"} <= levels
    assert r.by_scope.get(("-", "text_encoder"), 0.0) > 0
    # at this size a guided step takes 76 us and the waits for the
    # compiler's async copies, which carry no scope, a tenth of it; at
    # sd-v1's size the traced cell reads 96.6% (PERF.md)
    assert r.scoped_share >= 0.8
    assert max(r.unscoped_ops, key=r.unscoped_ops.get) == "copy-done"
    # scoped and unscoped op self-time is the program's time on the chip
    assert sum(r.denoise_scope_s.values()) == pytest.approx(
        r.denoise_ops_s, rel=1e-9)
    assert r.denoise_ops_s == pytest.approx(r.denoise_module_s, rel=0.01)


def test_idle_put_down_to_spans(scoped):
    r = program_trace.read(scoped)
    t = scoped.trace
    union = next(iter(t.busy.values()))
    total = sum((e - s) - program_trace._covered(union, s, e)
                for s, e, _ in t.spans_named("bench.process_group"))
    assert sum(r.idle.values()) == pytest.approx(total, rel=1e-9)
    assert set(r.idle) <= {"bench.process_group"} | {
        x[2] for x in r.spans}


def test_readers_on_the_scoped_trace(scoped):
    r = program_trace.read(scoped)
    steps = sum(n * b for n, b in GROUPS)
    got = {name: reader(name).read(scoped) for name in NEW}
    assert all(v is not None and v > 0 for v in got.values()), got
    assert got["unet_self_attn_s_per_image_step.poisson"] == pytest.approx(
        r.denoise_scope_s["self_attn"] / steps)
    assert got["engine_pack_s_per_request.poisson"] == pytest.approx(
        sum(r.span_lengths("repro.engine.pack")) / sum(b for _, b in GROUPS))
    # the four sublayers are part of the denoise program's time
    denoise = reader("denoise_s_per_image_step.poisson").read(scoped)
    assert sum(got[n] for n in NEW[:4]) < denoise


def test_nothing_to_read_gives_nothing(tmp_path, monkeypatch):
    assert all(reader(n).read(SimpleNamespace(trace=None, groups=[]))
               is None for n in NEW)
    # a trace with neither named scopes nor program spans (the parent's)
    run = traced_run(SMALL, "jit_work", tmp_path, monkeypatch,
                     [Group([0], 3, 0.0, 0.0, 0)])
    assert all(reader(n).read(run) is None for n in NEW)
    # a traced run whose profile is gone
    monkeypatch.setattr(program_trace, "TRACE_DIR", tmp_path / "none")
    run.trace = tracing.from_profile(profile_data(SMALL), first="jit_work")
    assert all(reader(n).read(run) is None for n in NEW)


def test_self_times_match_the_harness():
    # random call trees: the vectorized self times equal the harness's
    # stack walk (tracing.self_times), op by op
    import numpy as np
    rng = np.random.default_rng(3)
    for _ in range(20):
        events = []

        def fill(a, b, depth):
            t = a
            while t < b:
                s = t + rng.uniform(0, 1)
                e = min(b, s + rng.uniform(0.5, 8))
                if s >= e:
                    break
                events.append((s, e, f"op{len(events)}"))
                if depth < 3 and rng.random() < 0.3:
                    fill(s, e, depth + 1)
                t = e
        fill(0.0, 100.0, 0)
        events.sort(key=lambda x: (x[0], -x[1]))
        starts = np.array([x[0] for x in events])
        ends = np.array([x[1] for x in events])
        got = program_trace._self_times(starts, ends)
        want = dict(tracing.self_times(events))
        assert got == pytest.approx([want[n] for _, _, n in events],
                                    abs=1e-9)


def test_same_name_in_two_programs(tmp_path, monkeypatch):
    # two programs number their operations apart, so one name can be a
    # self-attention op in one and an MLP op in the other: each event is
    # looked up under the program it ran in
    plane = "/device:TPU:0"
    trace = tracing.Trace(
        {plane: [(1.0, 1.5, "%fusion.1"), (3.0, 3.25, "%fusion.1")]},
        {plane: [(0.9, 1.6, "jit_denoise_range(11)"),
                 (2.9, 3.6, "jit_denoise_range(22)")]},
        [(0.5, 2.0, "bench.process_group"),
         (2.5, 4.0, "bench.process_group")], 5.0)
    path = "jit(denoise_range)/while/body/closed_call/unet/{}/x:"
    prof = program_trace.Profile(
        {(11, "%fusion.1"): path.format("down0/self_attn"),
         (22, "%fusion.1"): path.format("up0/mlp")}, [])
    (tmp_path / "t.xplane.pb").write_bytes(b"")
    monkeypatch.setattr(program_trace, "TRACE_DIR", tmp_path)
    monkeypatch.setattr(program_trace, "parse", lambda p: prof)
    r = program_trace.read(SimpleNamespace(trace=trace, groups=[]))
    assert r.denoise_scope_s == {"self_attn": 0.5, "mlp": 0.25}
    assert r.by_scope == {("down0", "self_attn"): 0.5, ("up0", "mlp"): 0.25}
    assert r.scoped_share == 1.0
