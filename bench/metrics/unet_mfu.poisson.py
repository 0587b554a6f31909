"""Share of the chip's bf16 peak that the served groups reached while
they held it: their algorithmic operations (``bench/configs/<config>.ref.py``,
from the configuration's shapes) over the summed wall time of the
``process_group`` calls times the peak (host clock)."""
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent))
from _lib import share_of_peak  # noqa: E402


def read(run):
    groups = [g for g in run.groups if g.ok]
    return share_of_peak(sum(g.flops for g in groups),
                         sum(g.end - g.start for g in groups), run.peak_flops)
