"""Host seconds per ``process_group`` call: the harness span around the
call less the device busy time inside it (device trace)."""
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent))
from _lib import host_self_s  # noqa: E402


def read(run):
    return host_self_s(run, "bench.process_group")
