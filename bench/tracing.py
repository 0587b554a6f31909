"""Host spans and the device trace, reduced to intervals.

The harness wraps each call into a layer in a ``TraceAnnotation`` named
``bench.<what>`` (``bench.window`` around the whole measured window).
``load`` reads the profiler's ``.xplane.pb`` with JAX's own reader and
keeps three things: the device's operation intervals (the ``XLA Ops``
line of each ``/device:`` plane), its program intervals (``XLA
Modules``), and the host's ``bench.*`` spans, in seconds.

On a v5e the device's events reach the trace early against the host's
clock, by about a millisecond: in the small trace in ``bench/data`` each
program ends before the host span that launched it begins.  ``align``
measures that lead from the first program each launching span runs, and
the device's events are moved by it.
"""
from __future__ import annotations

import contextlib
import re
from pathlib import Path
from typing import Dict, List, Optional, Tuple

import numpy as np


def annotator(trace: int):
    if not trace:
        return lambda name: contextlib.nullcontext()
    import jax
    return jax.profiler.TraceAnnotation


def merge(intervals) -> np.ndarray:
    """Union of [start, end) intervals as a sorted (k, 2) array."""
    iv = sorted((s, e) for s, e in intervals if e > s)
    out: List[List[float]] = []
    for s, e in iv:
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return np.asarray(out, float).reshape(-1, 2)


def covered(union: np.ndarray, a: float, b: float) -> float:
    """Seconds of [a, b) that the merged ``union`` covers."""
    if not len(union) or b <= a:
        return 0.0
    s = np.clip(union[:, 0], a, b)
    e = np.clip(union[:, 1], a, b)
    return float(np.sum(e - s))


_SUFFIX = re.compile(r"[.:]\d+$")


def op_family(name: str) -> str:
    """``%fusion.123 = f32[...] fusion(...)`` -> ``fusion``: device
    operations grouped by the name XLA gave their kind."""
    head = name.split(" = ", 1)[0].strip().lstrip("%")
    return _SUFFIX.sub("", head)


def self_times(events) -> List[tuple]:
    """(name, seconds not covered by nested events) for events of one
    line, which nest like calls (a ``while`` holds its body's ops)."""
    out, stack = [], []       # stack of [start, end, name, child_time]
    for s, e, name in sorted(events, key=lambda x: (x[0], -x[1])):
        while stack and s >= stack[-1][1]:
            top = stack.pop()
            out.append((top[2], top[1] - top[0] - top[3]))
        if stack:
            stack[-1][3] += e - s
        stack.append([s, e, name, 0.0])
    out.extend((t[2], t[1] - t[0] - t[3]) for t in stack)
    return out


def align(modules, spans, first: Optional[str] = None,
          launch=("bench.process_group",), lookback: float = 0.01) -> float:
    """Seconds to add to device times so that no program starts before
    the host span that launched it: the largest lead, over launching
    spans, of the span's first program.  That is the first program whose
    name starts with ``first`` (any program where ``first`` is None) and
    which starts within ``lookback`` before the span or inside it.

    A program starts some time after its span opens, so each lead is the
    clock offset less that delay, and the largest lead is the nearest to
    the offset; a median would leave half of the first programs before
    their spans.  The name keeps out a previous span's last program,
    which can start inside the lookback when spans run back to back."""
    starts = np.sort([m[0] for v in modules.values() for m in v
                      if first is None or m[2].startswith(first)])
    lead = 0.0
    for s, e, name in spans:
        if name not in launch:
            continue
        i = np.searchsorted(starts, s - lookback)
        if i < len(starts) and starts[i] < e:
            lead = max(lead, s - starts[i])
    return lead


class Trace:
    def __init__(self, ops: Dict[str, List[tuple]],
                 modules: Dict[str, List[tuple]],
                 spans: List[tuple], window_s: float,
                 first: Optional[str] = None):
        self.shift = align(modules, spans, first)
        sh = lambda d: {k: [(a + self.shift, b + self.shift, n)  # noqa: E731
                            for a, b, n in v] for k, v in d.items()}
        #: device plane -> [(start, end, name)], on the host's clock
        self.ops = sh(ops)
        self.modules = sh(modules)
        #: host spans [(start, end, name)]
        self.spans = spans
        win = [s for s in spans if s[2] == "bench.window"]
        if win:
            self.t0, self.t1 = win[0][0], win[0][1]
        else:
            starts = [o[0] for v in self.ops.values() for o in v]
            self.t0 = min(starts) if starts else 0.0
            self.t1 = self.t0 + window_s
        self.window_s = self.t1 - self.t0
        self.busy = {d: merge((s, e) for s, e, _ in v)
                     for d, v in self.ops.items()}
        self.busy_s = (float(np.mean([covered(u, self.t0, self.t1)
                                      for u in self.busy.values()]))
                       if self.busy else 0.0)

    def spans_named(self, name: str) -> List[tuple]:
        return [s for s in self.spans if s[2] == name]

    def busy_in(self, a: float, b: float) -> float:
        """Device busy seconds inside [a, b), averaged over the chips."""
        if not self.busy:
            return 0.0
        return float(np.mean([covered(u, a, b) for u in self.busy.values()]))

    def module_time_in(self, a: float, b: float) -> float:
        """Seconds of device programs that ran inside [a, b), summed over
        programs and averaged over the chips."""
        if not self.modules:
            return 0.0
        per = []
        for v in self.modules.values():
            per.append(sum(max(0.0, min(e, b) - max(s, a)) for s, e, _ in v))
        return float(np.mean(per))

    def idle_gaps(self) -> List[Tuple[float, float]]:
        """Gaps inside the window in which chip 0's device ran nothing."""
        if not self.busy:
            return [(self.t0, self.t1)]
        u = next(iter(self.busy.values()))
        gaps, t = [], self.t0
        for s, e in u:
            if e <= self.t0:
                continue
            if s >= self.t1:
                break
            if s > t:
                gaps.append((t, s))
            t = max(t, e)
        if t < self.t1:
            gaps.append((t, self.t1))
        return gaps

    def host_label(self, t: float) -> str:
        """The innermost ``bench.*`` span other than the window at ``t``."""
        best = None
        for s, e, name in self.spans:
            if name != "bench.window" and s <= t < e:
                if best is None or (e - s) < (best[1] - best[0]):
                    best = (s, e, name)
        return best[2] if best else "host:outside-spans"

    def breakdown(self, top: int = 10) -> dict:
        by_op: Dict[str, float] = {}
        for v in list(self.ops.values())[:1]:
            inside = [o for o in v if o[0] >= self.t0 and o[1] <= self.t1]
            for name, t in self_times(inside):
                fam = op_family(name)
                by_op[fam] = by_op.get(fam, 0.0) + t
        by_gap: Dict[str, float] = {}
        for a, b in self.idle_gaps():
            lab = self.host_label(0.5 * (a + b))
            by_gap[lab] = by_gap.get(lab, 0.0) + (b - a)
        ops = sorted(by_op.items(), key=lambda kv: -kv[1])[:top]
        gaps = sorted(by_gap.items(), key=lambda kv: -kv[1])[:top]
        return {"device_ops": [[k, float(v)] for k, v in ops],
                "idle_gaps": [[k, float(v)] for k, v in gaps]}


def find_xplane(trace_dir: Path) -> Optional[Path]:
    found = sorted(Path(trace_dir).rglob("*.xplane.pb"))
    return found[-1] if found else None


def from_profile(data, window_s: float = 0.0,
                 first: Optional[str] = None) -> Trace:
    """Reduce a ``jax.profiler.ProfileData`` to a ``Trace``; ``first``
    names the program each launching span runs first (see ``align``)."""
    ops: Dict[str, List[tuple]] = {}
    modules: Dict[str, List[tuple]] = {}
    spans: List[tuple] = []
    for plane in data.planes:
        if plane.name.startswith("/device:"):
            if "TPU" not in plane.name and "GPU" not in plane.name:
                continue
            for line in plane.lines:
                if line.name == "XLA Ops":
                    ops.setdefault(plane.name, []).extend(
                        (ev.start_ns * 1e-9, ev.end_ns * 1e-9, ev.name)
                        for ev in line.events)
                elif line.name == "XLA Modules":
                    modules.setdefault(plane.name, []).extend(
                        (ev.start_ns * 1e-9, ev.end_ns * 1e-9, ev.name)
                        for ev in line.events)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith("bench."):
                        spans.append((ev.start_ns * 1e-9, ev.end_ns * 1e-9,
                                      ev.name))
    return Trace(ops, modules, spans, window_s, first)


def load(trace_dir: Path, window_s: float = 0.0,
         first: Optional[str] = None) -> Trace:
    from jax.profiler import ProfileData
    path = find_xplane(trace_dir)
    if path is None:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return from_profile(ProfileData.from_file(str(path)), window_s, first)
