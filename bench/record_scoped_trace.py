#!/usr/bin/env python3
"""Record the small trace that ``bench/tests/test_program_trace.py``
reads: the program's ``DiffusionSplitEngine`` at ``test_faults.py``'s
``SD_SMALL`` sizes serving ``GROUPS`` (one group of one request, one of
two), each call inside a ``bench.process_group`` span, all inside
``bench.window``.  Every program is compiled and run once before the
trace starts, as the harness's warm-up does.  What no reader reads is
taken out of the file before it is written (``thin``), which keeps it
under a megabyte.

    python3 bench/record_scoped_trace.py <output .xplane.pb>
"""
import os
import shutil
import sys
import tempfile
from pathlib import Path

os.environ.setdefault("TPU_LOG_DIR", "disabled")
ROOT = Path(__file__).resolve().parent.parent
for p in (ROOT, ROOT / "src"):
    sys.path.insert(0, str(p))

#: (n_cloud, batch) of the recorded groups, in order
GROUPS = ((3, 1), (3, 2))


def engine_and_requests():
    import jax
    import numpy as np
    from bench.tests.test_faults import SD_SMALL
    from repro.configs.stable_diffusion_v1 import DiffusionConfig
    from repro.core.cost_model import CostParams
    from repro.core.telemetry import DeviceProfile
    from repro.core.transport import LOCAL_LINK
    from repro.models import diffusion as dif
    from repro.serving.engine import DiffusionSplitEngine, Request
    cfg = DiffusionConfig(**{k: tuple(v) if isinstance(v, list) else v
                             for k, v in SD_SMALL.items()})
    params = dif.init_params(cfg, jax.random.PRNGKey(0))
    cost = CostParams(r_cloud=10.0, n_total=cfg.n_total_iterations,
                      n_step=cfg.split_stride, t_lim=5.0)
    engine = DiffusionSplitEngine(params, cfg, cost, link=LOCAL_LINK)
    rng = np.random.default_rng(0)
    reqs = [Request(f"req{i}", DeviceProfile(f"dev{i}", 2.0),
                    rng.integers(0, cfg.text_vocab, (1, cfg.text_len),
                                 dtype=np.int32),
                    np.zeros((1, cfg.text_len), np.int32))
            for i in range(sum(b for _, b in GROUPS))]
    return engine, reqs


def _uvarint(n: int) -> bytes:
    out = bytearray()
    while True:
        byte, n = n & 0x7F, n >> 7
        out.append(byte | (0x80 if n else 0))
        if not n:
            return bytes(out)


def _field(f: int, v, b) -> bytes:
    """One field as ``program_trace._fields`` yields it, encoded again."""
    if isinstance(v, int):
        return _uvarint(f << 3) + _uvarint(v)
    if isinstance(v, tuple):
        return _message(f, bytes(b[v[0]:v[1]]))
    return _uvarint(f << 3 | (1 if len(v) == 8 else 5)) + bytes(v)


def _message(f: int, payload: bytes) -> bytes:
    return _uvarint(f << 3 | 2) + _uvarint(len(payload)) + payload


def thin(raw: bytes, keep=("tf_op", "program_id")) -> bytes:
    """The profile less what no reader reads: the ``/host:metadata``
    plane (the programs' HLO) and, in the device planes' operation
    metadata, every stat but ``keep``, the display name, and the name
    past the instruction's own (``%fusion.12 = f32[...] fusion(...)``
    becomes ``%fusion.12``, unique within its program).  Events, their
    times and stats, and the host planes are left as they are."""
    from bench.program_trace import _fields, _str
    b = memoryview(raw)
    out = bytearray()
    for f, v in _fields(b):
        if f != 1:
            out += _field(f, v, b)
            continue
        fields = list(_fields(b, *v))
        name = next((_str(b, x) for g, x in fields if g == 2), "")
        if name == "/host:metadata":
            continue
        if not name.startswith("/device:"):
            out += _field(f, v, b)
            continue
        stat_ids = set()
        for g, x in fields:
            if g == 5:
                md = dict(_fields(b, *dict(_fields(b, *x))[2]))
                if _str(b, md.get(2, (0, 0))) in keep:
                    stat_ids.add(md.get(1, 0))
        plane = bytearray()
        for g, x in fields:
            if g != 4:
                plane += _field(g, x, b)
                continue
            entry = bytearray()
            for h, y in _fields(b, *x):
                if h != 2:
                    entry += _field(h, y, b)
                    continue
                meta = bytearray()
                for k, z in _fields(b, *y):
                    if k == 2:
                        meta += _message(2, _str(b, z).split(" = ", 1)[0]
                                         .encode())
                    elif k != 4 and (k != 5 or dict(_fields(b, *z)).get(1)
                                     in stat_ids):
                        meta += _field(k, z, b)
                entry += _message(2, bytes(meta))
            plane += _message(4, bytes(entry))
        out += _message(1, bytes(plane))
    return bytes(out)


def main(out):
    import jax
    from jax.profiler import TraceAnnotation
    engine, reqs = engine_and_requests()
    for n, b in GROUPS:
        engine.process_group(reqs[:b], n, seed=0)
    tmp = Path(tempfile.mkdtemp(dir=Path(out).resolve().parent))
    jax.profiler.start_trace(str(tmp))
    with TraceAnnotation("bench.window"):
        k = 0
        for g, (n, b) in enumerate(GROUPS):
            with TraceAnnotation("bench.process_group"):
                engine.process_group(reqs[k:k + b], n, seed=g + 1)
            k += b
    jax.profiler.stop_trace()
    found = sorted(tmp.rglob("*.xplane.pb"))
    Path(out).write_bytes(thin(found[-1].read_bytes()))
    shutil.rmtree(tmp)
    print(f"wrote {out} ({Path(out).stat().st_size} B) on "
          f"{jax.devices()[0].device_kind}")


if __name__ == "__main__":
    main(sys.argv[1])
