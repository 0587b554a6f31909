"""What the prefill cells' metric readers share: the prompt tokens
served, and device time per token of a named scope."""
from typing import Optional

from bench import program_trace


def tokens_served(run) -> int:
    """Prompt tokens of the requests in groups that ran without error."""
    return run.traffic["prompt_tokens"] * sum(
        len(g.members) for g in run.groups if g.ok)


def scope_s_per_token(run, scope: str) -> Optional[float]:
    """Device self-seconds of the ops whose innermost named scope is
    ``scope``, inside the harness's ``bench.process_group`` spans, over
    the prompt tokens served (device trace, ``bench/program_trace.py``)."""
    r = program_trace.read(run)
    if r is None:
        return None
    sec = sum(v for (_, s), v in r.by_scope.items() if s == scope)
    n = tokens_served(run)
    if sec <= 0 or not n:
        return None
    return sec / n
