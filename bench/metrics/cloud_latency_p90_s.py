"""90th percentile, over every request due in the window, of the seconds
from its due time to its boundary payload packed (host clock)."""
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent))
from _lib import latencies, latency_percentile  # noqa: E402


def read(run):
    lat = latencies(run)
    if not lat:
        return None
    print(f"bench: latency median {latency_percentile(lat, 50)!r} s over "
          f"{len(lat)} requests", file=sys.stderr)
    return latency_percentile(lat, 90)
