"""Device seconds of the programs that ``process_group`` ran (the cloud's
embedding and layers, ``jit_cloud_layers``) over the prompt tokens
served (device trace).  Also prints the share of the op self-time inside
``bench.process_group`` that carries a named scope."""
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent))
from _prefill import tokens_served  # noqa: E402

from bench import program_trace  # noqa: E402


def read(run):
    if run.trace is None:
        return None
    r = program_trace.read(run)
    if r is not None and r.by_scope:
        total = sum(r.by_scope.values())
        scoped = sum(v for (_, s), v in r.by_scope.items()
                     if s != program_trace.UNSCOPED)
        print(f"bench: named scopes cover {100 * scoped / total:.3f}% of "
              f"{total:.6f} s of op self-time inside bench.process_group",
              file=sys.stderr)
    spans = run.trace.spans_named("bench.process_group")
    dev = sum(run.trace.module_time_in(s, e) for s, e, _ in spans)
    n = tokens_served(run)
    if not spans or not n or dev <= 0:
        return None
    return dev / n
