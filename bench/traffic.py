"""The one traffic generator: turns a traffic file and a seed into work.

A traffic file (``bench/traffic/<mix>.json``) holds parameters only: an
open-loop schedule of arrivals, each with a device profile drawn from a
fleet (the paper's phones).  ``arrivals`` gives the process (``poisson``
or ``on_off``) and its rates; ``fleet`` the device-rate distribution.

The draws are stratified: the arrival gaps are the quantiles of the
exponential distribution and the device rates the quantiles of the
fleet's normal.  Their order is drawn from the file's own
``schedule_seed``, not from the run's seed: with some thirty requests in
a window, the order alone moves a tail by a quarter (a cluster of short
gaps queues), so every run serves the same arrivals and phones and the
run's seed draws the prompts (and, in the configuration, the weights and
the noise).  The distributions are the program's (``core.telemetry``'s Poisson and on/off
generators, ``serving.simulator.table4_fleet``);
``bench/tests/test_traffic.py`` holds the stratified draws to them.
"""
from __future__ import annotations

import dataclasses
import math
from statistics import NormalDist
from typing import List, Tuple

import numpy as np


def derive(seed: int, *tags: int) -> int:
    """A 31-bit seed derived from the run's seed and integer tags (JAX's
    ``PRNGKey`` and numpy both take it)."""
    state = np.random.SeedSequence([int(seed) % (1 << 64), *tags])
    return int(state.generate_state(1, np.uint32)[0] >> 1)


# --------------------------------------------------------------------------
# Copied arithmetic (core/telemetry.py ``_bursty_rates``).
# --------------------------------------------------------------------------
def on_off_rates(rate: float, burst_factor: float,
                 on_fraction: float) -> Tuple[float, float]:
    """(high, low) phase rates of an on/off process of mean ``rate``."""
    if not 0.0 < on_fraction < 1.0:
        raise ValueError("on_fraction must be in (0, 1)")
    if burst_factor * on_fraction > 1.0:
        raise ValueError("burst_factor * on_fraction > 1")
    high = burst_factor * rate
    low = rate * (1.0 - on_fraction * burst_factor) / (1.0 - on_fraction)
    return high, low


# --------------------------------------------------------------------------
# Stratified draws: the same set of sizes for every seed, in another order.
# --------------------------------------------------------------------------
def _exp_gaps(n: int, rate: float) -> np.ndarray:
    q = (np.arange(n) + 0.5) / n
    return -np.log1p(-q) / rate


def _phases(arr: dict, duration: float) -> List[Tuple[float, float, float]]:
    """(start, end, rate) segments covering [0, duration)."""
    if arr["process"] == "poisson":
        return [(0.0, duration, float(arr["rate"]))]
    if arr["process"] != "on_off":
        raise ValueError(f"unknown arrival process {arr['process']!r}")
    high, low = on_off_rates(arr["rate"], arr["burst_factor"],
                             arr["on_fraction"])
    cycle, on = arr["cycle_s"], arr["on_fraction"] * arr["cycle_s"]
    out, t = [], 0.0
    while t < duration:
        out.append((t, min(t + on, duration), high))
        if t + on < duration:
            out.append((t + on, min(t + cycle, duration), low))
        t += cycle
    return out


def stratified_times(arr: dict, duration: float,
                     rng: np.random.Generator) -> np.ndarray:
    """Each phase holds round(rate x length) arrivals (the remainder is
    carried to the next phase), spaced by exponential quantile gaps in an
    order drawn from ``rng`` and scaled to fill the phase."""
    times, carry = [], 0.0
    for a, b, lam in _phases(arr, duration):
        want = lam * (b - a) + carry
        n = int(math.floor(want + 0.5))
        carry = want - n
        if n == 0:
            continue
        gaps = rng.permutation(_exp_gaps(n, lam))
        # n gaps plus one more mean gap span the phase
        pts = np.cumsum(gaps) * (b - a) / (gaps.sum() + 1.0 / lam)
        times.append(a + pts)
    return np.concatenate(times) if times else np.zeros(0)


def stratified_rates(n: int, mean: float, std: float,
                     rng: np.random.Generator) -> np.ndarray:
    nd = NormalDist(mean, std)
    q = [nd.inv_cdf((i + 0.5) / n) for i in range(n)]
    return rng.permutation(np.clip(np.asarray(q), 0.05, None))


# --------------------------------------------------------------------------
# The schedule the harness drives.
# --------------------------------------------------------------------------
@dataclasses.dataclass
class Arrival:
    index: int
    due: float          # seconds after the window opens
    r_dev: float        # device iterations/s (paper fleet)
    token_seed: int     # seed of this request's prompt tokens


def open_loop_schedule(traffic: dict, seed: int,
                       duration: float) -> List[Arrival]:
    """Arrivals due in [0, duration): times and phones in the order of the
    traffic file's ``schedule_seed``, prompt seeds from the run's."""
    arr, fleet = traffic["arrivals"], traffic["fleet"]
    rng = np.random.default_rng(derive(traffic["schedule_seed"], 1))
    times = stratified_times(arr, duration, rng)
    rates = stratified_rates(len(times), fleet["r_dev_mean"],
                             fleet["r_dev_std"], rng)
    return [Arrival(i, float(t), float(r), derive(seed, 3, i))
            for i, (t, r) in enumerate(zip(times, rates))]


def prompt_tokens(token_seed: int, length: int, vocab: int) -> np.ndarray:
    """(1, length) int32 token ids in [1, vocab)."""
    rng = np.random.default_rng(token_seed)
    return rng.integers(1, vocab, (1, length), dtype=np.int32)
