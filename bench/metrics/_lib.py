"""Arithmetic the metric readers share."""
import math

import numpy as np


def latency_percentile(values, q):
    """Copied from the program (``core.telemetry.latency_percentile``):
    ``np.percentile`` with ``q`` in [0, 100]; NaN on no values."""
    if not len(values):
        return math.nan
    return float(np.percentile(values, q))


def latencies(run):
    """Seconds from due time to payload packed, every request due in the
    window that was served."""
    return [run.done[i] - run.due[i] for i in run.due if i in run.done]


def host_self_s(run, span):
    """Mean over ``span`` of its length less the device busy time in it."""
    if run.trace is None:
        return None
    spans = run.trace.spans_named(span)
    if not spans:
        return None
    return float(np.mean([(e - s) - run.trace.busy_in(s, e)
                          for s, e, _ in spans]))


def share_of_peak(flops, seconds, peak):
    """Percent of the chip's peak; None where nothing ran."""
    if not flops or not seconds or seconds <= 0 or not math.isfinite(peak):
        return None
    return 100.0 * flops / (seconds * peak)
