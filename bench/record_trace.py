#!/usr/bin/env python3
"""Record the small device trace that ``bench/tests/test_tracing.py``
reads: three rounds of a ``bench.process_group`` span around a jitted
matmul chain that ends in ``block_until_ready``, then a ``bench.wait``
span of 0.1 s of sleep, all inside ``bench.window``.

    python3 bench/record_trace.py <output .xplane.pb>
"""
import os
import shutil
import sys
import tempfile
import time
from pathlib import Path

os.environ.setdefault("TPU_LOG_DIR", "disabled")

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.profiler import TraceAnnotation  # noqa: E402


def main(out):
    @jax.jit
    def work(x):
        for _ in range(8):
            x = jnp.tanh(x @ x)
        return x

    x = jnp.ones((2048, 2048), jnp.float32) / 2048
    work(x).block_until_ready()
    tmp = Path(tempfile.mkdtemp(dir=Path(out).parent))
    jax.profiler.start_trace(str(tmp))
    with TraceAnnotation("bench.window"):
        for _ in range(3):
            with TraceAnnotation("bench.process_group"):
                work(x).block_until_ready()
            with TraceAnnotation("bench.wait"):
                time.sleep(0.1)
    jax.profiler.stop_trace()
    found = sorted(tmp.rglob("*.xplane.pb"))
    shutil.copy(found[-1], out)
    shutil.rmtree(tmp)
    print(f"wrote {out} ({Path(out).stat().st_size} B) on "
          f"{jax.devices()[0].device_kind}")


if __name__ == "__main__":
    main(sys.argv[1])
