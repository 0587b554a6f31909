"""The program's own spans and named scopes, read from a traced run.

The engine marks its host stages with ``repro.*`` profiler spans
(``serving/engine.py``) and the diffusion model names its sublayers with
``jax.named_scope`` (``models/diffusion.py``).  ``jax.profiler.ProfileData``
shows neither the scope of a device operation nor where it could find
it, so this module decodes the two parts of the ``.xplane.pb`` it needs
straight from the protobuf:

- each device plane's ``event_metadata``: (program id, operation name)
  -> the ``tf_op`` stat, the path of named scopes, e.g.
  ``jit(denoise_range)/while/body/closed_call/unet/down0/self_attn/
  dot_general:``.  The name is the HLO text the trace shows as the
  event's name, and the same text can name different operations in two
  programs (the denoise programs of two step counts are numbered
  apart), so an event is looked up under the program it runs in: the
  id in the name of the ``XLA Modules`` event around it.  A key whose
  entries carry two different paths counts as unscoped;
- the host planes' ``repro.*`` events, on the host clock that the
  harness's ``bench.*`` spans are on.

The device operations themselves come from the harness's ``run.trace``
(``bench/tracing.py``), already moved onto the host clock.  The file is
parsed once per path and the reading once per trace, for all metric
files.  ``read`` prints one table to stderr.
"""
from __future__ import annotations

import dataclasses
import re
import struct
import sys
import time
from operator import itemgetter
from pathlib import Path
from typing import Dict, List, Optional, Tuple

import numpy as np

from bench import tracing
from bench.harness import CACHE

#: where the harness writes the traced run's profile
TRACE_DIR = CACHE / "trace"
#: the engine's denoise program, by its name in the trace
DENOISE = "jit_denoise_range"
#: the named scopes of models/diffusion.py that a device op is put down
#: to (its innermost one); ``unet`` alone counts as unscoped
SCOPES = ("resblock", "self_attn", "cross_attn", "mlp", "xattn_proj",
          "resample", "stem", "head", "guidance", "text_encoder")
_LEVEL = re.compile(r"(down|up)\d+|mid")
UNSCOPED = "unscoped"


# --------------------------------------------------------------------------
# Protobuf wire format, the little of it an XSpace needs
# --------------------------------------------------------------------------
def _varint(b, i: int) -> Tuple[int, int]:
    r = s = 0
    while True:
        c = b[i]
        i += 1
        r |= (c & 0x7F) << s
        if c < 0x80:
            return r, i
        s += 7


def _fields(b, i: int = 0, end: Optional[int] = None):
    """(field number, value) of a message in ``b[i:end]``: an int for a
    varint, (start, end) for a length-delimited field, raw bytes for a
    fixed-width one."""
    end = len(b) if end is None else end
    while i < end:
        key, i = _varint(b, i)
        wire = key & 7
        if wire == 0:
            v, i = _varint(b, i)
        elif wire == 2:
            n, i = _varint(b, i)
            v, i = (i, i + n), i + n
        elif wire == 1:
            v, i = b[i:i + 8], i + 8
        elif wire == 5:
            v, i = b[i:i + 4], i + 4
        else:
            raise ValueError(f"protobuf wire type {wire} at byte {i}")
        yield key >> 3, v


def _str(b, span) -> str:
    return bytes(b[span[0]:span[1]]).decode("utf-8", "replace")


def _signed(v: int) -> int:
    return v - (1 << 64) if v >= 1 << 63 else v


def _stat(b, span, names: Dict[int, str]):
    """(stat metadata id, value) of an ``XStat``; a ``ref_value`` is the
    name of the stat metadata it points to."""
    mid, val = 0, None
    for f, v in _fields(b, *span):
        if f == 1:
            mid = v
        elif f == 2:
            val = struct.unpack("<d", bytes(v))[0]
        elif f in (3, 4):
            val = _signed(v) if f == 4 else v
        elif f == 5:
            val = _str(b, v)
        elif f == 7:
            val = names.get(v)
    return mid, val


@dataclasses.dataclass
class Profile:
    #: (program id, device operation name) -> scope path (None where
    #: entries disagree)
    tf_op: Dict[Tuple[int, str], Optional[str]]
    #: host ``repro.*`` events: (start s, end s, name, {arg: value})
    spans: List[tuple]


def _plane(b, span, ops: Dict[tuple, set], spans: List[tuple]):
    name, lines, emeta, smeta = "", [], [], {}
    for f, v in _fields(b, *span):
        if f == 2:
            name = _str(b, v)
        elif f == 3:
            lines.append(v)
        elif f == 4:
            emeta.append(v)
        elif f == 5:
            entry = dict(_fields(b, *v))
            if 2 in entry:
                md = dict(_fields(b, *entry[2]))
                smeta[md.get(1, entry.get(1, 0))] = (
                    _str(b, md[2]) if 2 in md else "")
    device = name.startswith("/device:")
    if not device and not name.startswith("/host:"):
        return
    tf_op_id = next((k for k, v in smeta.items() if v == "tf_op"), None)
    pid_id = next((k for k, v in smeta.items() if v == "program_id"), None)
    events: Dict[int, str] = {}
    for e in emeta:
        entry = dict(_fields(b, *e))
        if 2 not in entry:
            continue
        eid, ename, path, pid = entry.get(1, 0), "", None, None
        for f, v in _fields(b, *entry[2]):
            if f == 1:
                eid = v
            elif f == 2:
                ename = _str(b, v)
            elif f == 5 and device:
                mid, val = _stat(b, v, smeta)
                if mid == tf_op_id:
                    path = val
                elif mid == pid_id:
                    pid = val
        if device:
            ops.setdefault((pid, ename), set()).add(path)
        elif ename.startswith("repro."):
            events[eid] = ename.split("#", 1)[0]
    if device or not events:
        return
    for ln in lines:
        t0_ns = 0
        evs = []
        for f, v in _fields(b, *ln):
            if f == 3:
                t0_ns = _signed(v)
            elif f == 4:
                evs.append(v)
        for a, z in evs:
            key, i = _varint(b, a)
            mid, _ = _varint(b, i) if key == 0x08 else (None, i)
            if mid not in events:
                continue
            off = dur = 0
            args = {}
            for f, v in _fields(b, a, z):
                if f == 2:
                    off = _signed(v)
                elif f == 3:
                    dur = _signed(v)
                elif f == 4:
                    k, val = _stat(b, v, smeta)
                    args[smeta.get(k, str(k))] = val
            start = (t0_ns * 1000 + off) * 1e-12
            spans.append((start, start + dur * 1e-12, events[mid], args))


_FILES: Dict[tuple, Profile] = {}


def parse(path: Path) -> Profile:
    """The scope map and ``repro.*`` spans of one ``.xplane.pb``,
    memoized by path, size and modification time."""
    st = Path(path).stat()
    key = (str(path), st.st_size, st.st_mtime_ns)
    if key not in _FILES:
        b = memoryview(Path(path).read_bytes())
        ops: Dict[tuple, set] = {}
        spans: List[tuple] = []
        for f, v in _fields(b):
            if f == 1:
                _plane(b, v, ops, spans)
        spans.sort()
        paths = {}
        for op, found in ops.items():
            found.discard(None)           # an entry without a path
            paths[op] = found.pop() if len(found) == 1 else None
        _FILES[key] = Profile(paths, spans)
    return _FILES[key]


def scope_of(path: Optional[str]) -> Tuple[str, str]:
    """(level, scope) of a ``tf_op`` path: the level component
    (``down{l}``, ``mid``, ``up{l}``, else ``-``) and the innermost
    named scope of ``SCOPES`` (else ``unscoped``)."""
    if not path:
        return "-", UNSCOPED
    parts = path.rsplit(":", 1)[0].split("/")
    level = next((p for p in parts if _LEVEL.fullmatch(p)), "-")
    scope = next((p for p in reversed(parts) if p in SCOPES), UNSCOPED)
    return level, scope


# --------------------------------------------------------------------------
# The reading of one traced run
# --------------------------------------------------------------------------
@dataclasses.dataclass
class Reading:
    #: device self-seconds inside ``bench.process_group``, all programs
    by_scope: Dict[Tuple[str, str], float]
    #: the same, in the denoise program only, summed over levels
    denoise_scope_s: Dict[str, float]
    denoise_ops_s: float          # op self-time in the denoise program
    denoise_module_s: float       # its program events' time
    #: unscoped op self-time in the denoise program, by op family
    unscoped_ops: Dict[str, float]
    #: ``repro.*`` spans inside the window: (start, end, name, args)
    spans: List[tuple]
    #: device idle inside ``bench.process_group``, by innermost span
    idle: Dict[str, float]

    @property
    def scoped_share(self) -> Optional[float]:
        if self.denoise_ops_s <= 0:
            return None
        scoped = sum(v for k, v in self.denoise_scope_s.items()
                     if k != UNSCOPED)
        return scoped / self.denoise_ops_s

    def span_lengths(self, name: str) -> List[float]:
        return [e - s for s, e, n, _ in self.spans if n == name]


def _program_id(module: str) -> Optional[int]:
    """``jit_denoise_range(123)`` -> 123, the id the program's ops carry."""
    m = re.search(r"\((\d+)\)$", module)
    return int(m.group(1)) if m else None


def _covered(union: np.ndarray, a: float, b: float) -> float:
    """Seconds of [a, b) that the merged, sorted ``union`` covers."""
    if not len(union) or b <= a:
        return 0.0
    i = np.searchsorted(union[:, 1], a, "right")
    j = np.searchsorted(union[:, 0], b, "left")
    seg = union[i:j]
    return float(np.sum(np.minimum(seg[:, 1], b) - np.maximum(seg[:, 0], a)))


def _inside(starts: np.ndarray, spans: List[tuple]) -> np.ndarray:
    """Index of the span of ``spans`` (sorted, disjoint) holding each
    start, -1 where none does."""
    if not spans:
        return np.full(len(starts), -1)
    s = np.array([x[0] for x in spans])
    e = np.array([x[1] for x in spans])
    k = np.searchsorted(s, starts, "right") - 1
    ok = (k >= 0) & (starts < e[np.maximum(k, 0)])
    return np.where(ok, k, -1)


def _self_times(starts: np.ndarray, ends: np.ndarray) -> np.ndarray:
    """Self time of each op of one line (sorted by start, then by
    longest), where ops nest like calls (``tracing.self_times``): its
    length less that of the ops it directly holds.  Only control flow
    (a ``while`` and its body) holds ops, so the parent of each op is
    set from the few ops that hold another, outer ones first."""
    n = len(starts)
    parent = np.full(n, -1)
    first_after = np.searchsorted(starts, ends, "left")
    for k in np.flatnonzero(first_after > np.arange(n) + 1):
        parent[k + 1:first_after[k]] = k
    length = ends - starts
    nested = parent >= 0
    return length - np.bincount(parent[nested], weights=length[nested],
                                minlength=n)


def _idle(trace, groups: List[tuple], spans: List[tuple]) -> Dict[str, float]:
    """Device idle seconds inside each ``bench.process_group`` span, put
    down to the innermost ``repro.*`` span around them (spans nest on
    the calling thread); idle outside any is the harness span's own."""
    union = next(iter(trace.busy.values()), np.zeros((0, 2)))
    out: Dict[str, float] = {}
    tree = sorted(list(groups) + [x[:3] for x in spans],
                  key=lambda x: (x[0], -x[1]))
    stack: List[list] = []          # [end, name, inclusive idle, children]

    def close(node):
        own = node[2] - node[3]
        out[node[1]] = out.get(node[1], 0.0) + own

    for s, e, n in tree:
        while stack and s >= stack[-1][0]:
            close(stack.pop())
        if n.startswith("repro.") and not stack:
            continue                # outside every bench.process_group
        idle = (e - s) - _covered(union, s, e)
        if stack:
            stack[-1][3] += idle
        stack.append([e, n, idle, 0.0])
    while stack:
        close(stack.pop())
    return out


_READINGS: Dict[int, tuple] = {}


def read(run) -> Optional[Reading]:
    """The reading of ``run``'s traced window, or None where it has no
    trace or its profile cannot be found."""
    trace = getattr(run, "trace", None)
    if trace is None:
        return None
    hit = _READINGS.get(id(trace))
    if hit is not None and hit[0] is trace:
        return hit[1]
    path = tracing.find_xplane(TRACE_DIR)
    if path is None:
        return None
    t = time.perf_counter()
    prof = parse(path)
    parse_s = time.perf_counter() - t
    groups = sorted(trace.spans_named("bench.process_group"))
    by_scope: Dict[Tuple[str, str], float] = {}
    denoise: Dict[str, float] = {}
    unscoped: Dict[str, float] = {}
    ops_s = module_s = 0.0
    for plane, ops in list(trace.ops.items())[:1]:
        starts = np.fromiter(map(itemgetter(0), ops), float, len(ops))
        ends = np.fromiter(map(itemgetter(1), ops), float, len(ops))
        order = np.lexsort((-ends, starts))
        order = order[_inside(starts[order], groups) >= 0]
        starts, ends = starts[order], ends[order]
        self_s = _self_times(starts, ends)
        mods = sorted(trace.modules.get(plane, ()))
        module_s = sum(e - s for s, e, n in mods if n.startswith(DENOISE)
                       and _inside(np.array([s]), groups)[0] >= 0)
        # one key per (op name, program it ran in), summed with bincount
        progs = sorted({m[2] for m in mods})
        prog_of = np.array([-1] + [progs.index(m[2]) for m in mods])
        names = [ops[i][2] for i in order.tolist()]
        codes = {x: i for i, x in enumerate(dict.fromkeys(names))}
        code = np.fromiter(map(codes.__getitem__, names), np.int64,
                           len(names))
        prog = prog_of[_inside(starts, mods) + 1]
        pairs, inv = np.unique(code * (len(progs) + 1) + prog + 1,
                               return_inverse=True)
        seconds = np.bincount(inv.ravel(), weights=self_s,
                              minlength=len(pairs))
        unique_names = list(codes)
        for pair, t_self in zip(pairs.tolist(), seconds.tolist()):
            c, k = divmod(pair, len(progs) + 1)
            name, mod = unique_names[c], (progs[k - 1] if k else "")
            op = (_program_id(mod), name)
            key = scope_of(prof.tf_op.get(op, prof.tf_op.get((None, name))))
            by_scope[key] = by_scope.get(key, 0.0) + t_self
            if mod.startswith(DENOISE):
                denoise[key[1]] = denoise.get(key[1], 0.0) + t_self
                ops_s += t_self
                if key[1] == UNSCOPED:
                    fam = tracing.op_family(name)
                    unscoped[fam] = unscoped.get(fam, 0.0) + t_self
    spans = [x for x in prof.spans if trace.t0 <= x[0] < trace.t1]
    reading = Reading(by_scope, denoise, ops_s, module_s, unscoped, spans,
                      _idle(trace, groups, spans))
    _READINGS.clear()
    _READINGS[id(trace)] = (trace, reading)
    report(reading, path, parse_s, time.perf_counter() - t)
    return reading


def _level_order(level: str) -> tuple:
    """down0, down1, ..., mid, ..., up1, up0, then ``-``: the order a
    UNet evaluation runs them."""
    if level.startswith("down"):
        return (0, int(level[4:]))
    if level == "mid":
        return (1, 0)
    if level.startswith("up"):
        return (2, -int(level[2:]))
    return (3, 0)


def report(r: Reading, path: Path, parse_s: float, total_s: float):
    def say(*a):
        print("program_trace:", *a, file=sys.stderr, flush=True)
    say(f"{path.name}: parsed in {parse_s:.3f} s, read in {total_s:.3f} s")
    levels = sorted({k[0] for k in r.by_scope}, key=_level_order)
    cols = [c for c in SCOPES + (UNSCOPED,)
            if any(k[1] == c for k in r.by_scope)]
    if r.by_scope:
        say("device self-seconds inside bench.process_group, by level and "
            "named scope:")
        say(f"{'level':>6} " + " ".join(f"{c:>12}" for c in cols))
        for lv in levels:
            say(f"{lv:>6} " + " ".join(
                f"{r.by_scope.get((lv, c), 0.0):12.6f}" for c in cols))
    else:
        say("no device operations inside bench.process_group")
    share = r.scoped_share
    say(f"{DENOISE}: program events {r.denoise_module_s:.6f} s, op self "
        f"{r.denoise_ops_s:.6f} s, named scopes "
        + (f"{100 * share:.3f}% of op self" if share is not None else
           "none"))
    for c in cols:
        if c in r.denoise_scope_s:
            say(f"  {c:>12} {r.denoise_scope_s[c]:12.6f} s")
    if r.unscoped_ops:
        say("largest unscoped op families in the denoise program: "
            + ", ".join(f"{k} {v:.6f} s" for k, v in sorted(
                r.unscoped_ops.items(), key=lambda kv: -kv[1])[:6]))
    names = sorted({n for _, _, n, _ in r.spans})
    for n in names:
        ls = r.span_lengths(n)
        say(f"span {n}: {len(ls)} x, mean {np.mean(ls):.6f} s, "
            f"sum {np.sum(ls):.6f} s")
    say("device idle inside bench.process_group, by innermost span: "
        + ", ".join(f"{k} {v:.6f} s" for k, v in
                    sorted(r.idle.items(), key=lambda kv: -kv[1])))


# --------------------------------------------------------------------------
# What the metric files read
# --------------------------------------------------------------------------
def scope_s_per_image_step(run, scope: str) -> Optional[float]:
    """Device self-seconds of ``scope`` in the denoise program over the
    guided steps served: sum over groups of n_cloud x batch."""
    r = read(run)
    if r is None:
        return None
    sec = r.denoise_scope_s.get(scope, 0.0)
    steps = sum(g.n_cloud * len(g.members) for g in run.groups if g.ok)
    if sec <= 0 or not steps:
        return None
    return sec / steps


def mean_span_s(run, name: str) -> Optional[float]:
    """Mean length of the program's span ``name`` inside the window."""
    r = read(run)
    ls = r.span_lengths(name) if r is not None else []
    return float(np.mean(ls)) if ls else None
