#!/usr/bin/env python3
"""Run one benchmark cell: see ``bench/harness.py``.

    python3 bench/run.py --workload sd-v1.fleet-poisson --seed 7 --seconds 51 --trace 0
"""
import time

T_START = time.perf_counter()

import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

# the TPU compiler logs under /tmp unless told otherwise
os.environ.setdefault("TPU_LOG_DIR", "disabled")
sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

from bench.harness import main  # noqa: E402

if __name__ == "__main__":
    sys.exit(main(sys.argv[1:], t_start=T_START))
