"""Device seconds of the LM layers' self-attention (RMSNorm, q/k/v
projections, RoPE, the masked chunked attention, output projection,
residual add) over the prompt tokens served: self-time of the device
operations whose innermost named scope is ``self_attn``, inside the
harness's ``bench.process_group`` spans (device trace,
``bench/program_trace.py``)."""
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent))
from _prefill import scope_s_per_token  # noqa: E402


def read(run):
    return scope_s_per_token(run, "self_attn")
