"""The traffic generator: its stratified draws follow the program's
distributions, and give every seed the same work in the same order.

    JAX_PLATFORMS=cpu python -m pytest -q bench/tests
"""
import json
from pathlib import Path

import numpy as np
import pytest

from bench import traffic as T

BENCH = Path(__file__).resolve().parents[1]
SEED = 2**31 + 12345
#: an on/off mix like the flash crowd a later cell may run
ON_OFF = {"arrivals": {"process": "on_off", "rate": 0.9, "burst_factor": 4.0,
                       "on_fraction": 0.2, "cycle_s": 20.0},
          "fleet": {"r_dev_mean": 2.25, "r_dev_std": 0.28},
          "schedule_seed": 7}


def poisson_mix():
    return json.loads((BENCH / "traffic" / "fleet-poisson.json").read_text())


def ks_distance(a, b) -> float:
    """Largest gap between the empirical distribution functions."""
    a, b = np.sort(a), np.sort(b)
    x = np.concatenate([a, b])
    fa = np.searchsorted(a, x, side="right") / len(a)
    fb = np.searchsorted(b, x, side="right") / len(b)
    return float(np.max(np.abs(fa - fb)))


def test_on_off_rates_match_program():
    from repro.core import telemetry as tel
    args = dict(burst_factor=4.0, on_fraction=0.2)
    assert T.on_off_rates(0.9, **args) == tel._bursty_rates(0.9, **args)


def test_latency_percentile_matches_program():
    import importlib.util
    from repro.core.telemetry import latency_percentile
    spec = importlib.util.spec_from_file_location(
        "mlib", BENCH / "metrics" / "_lib.py")
    lib = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(lib)
    v = list(np.random.default_rng(1).exponential(size=37))
    for q in (50, 90, 95):
        assert lib.latency_percentile(v, q) == latency_percentile(v, q)


def test_poisson_gaps_follow_program():
    from repro.core.telemetry import poisson_arrivals
    rate = poisson_mix()["arrivals"]["rate"]
    want = np.diff(list(poisson_arrivals(rate, 4000.0, seed=SEED)))
    arr = {"process": "poisson", "rate": rate}
    got = np.diff(T.stratified_times(arr, 4000.0,
                                     np.random.default_rng(SEED)))
    assert abs(len(got) - len(want)) < 4 * np.sqrt(len(want))
    assert ks_distance(got, want) < 0.05


def test_fleet_follows_program():
    from repro.serving.simulator import table4_fleet
    fleet = poisson_mix()["fleet"]
    want = [d.r_dev for d in table4_fleet(2000, seed=SEED)]
    got = T.stratified_rates(2000, fleet["r_dev_mean"], fleet["r_dev_std"],
                             np.random.default_rng(SEED))
    assert ks_distance(got, want) < 0.05


def test_on_off_follows_program():
    from repro.core.telemetry import bursty_arrivals
    a = ON_OFF["arrivals"]
    want = np.fromiter(bursty_arrivals(
        a["rate"], 4000.0, seed=SEED, burst_factor=a["burst_factor"],
        on_fraction=a["on_fraction"], cycle_s=a["cycle_s"]), float)
    got = T.stratified_times(a, 4000.0, np.random.default_rng(SEED))
    on = a["on_fraction"] * a["cycle_s"]
    share = lambda t: np.mean((t % a["cycle_s"]) < on)   # noqa: E731
    assert abs(len(got) - len(want)) < 4 * np.sqrt(len(want))
    assert abs(share(got) - share(want)) < 0.03
    assert ks_distance(got % a["cycle_s"], want % a["cycle_s"]) < 0.05


@pytest.mark.parametrize("mix", ["fleet-poisson", "on-off"])
def test_stratified_same_work_every_seed(mix):
    t = poisson_mix() if mix == "fleet-poisson" else ON_OFF
    runs = [T.open_loop_schedule(t, s, 51.0) for s in (1, 2, SEED)]
    # every run seed serves the same arrivals and phones, in one order
    work = [[(a.due, a.r_dev) for a in r] for r in runs]
    assert work[0] == work[1] == work[2]
    due = [a.due for a in runs[0]]
    assert due == sorted(due) and 0.0 <= due[0] and due[-1] < 51.0
    # the run seed draws the prompts
    assert [a.token_seed for a in runs[0]] != [a.token_seed for a in runs[1]]
    # another schedule seed: the same gaps and phones, in another order
    other = T.open_loop_schedule(dict(t, schedule_seed=t["schedule_seed"] + 1),
                                 1, 51.0)
    assert len(other) == len(runs[0])
    assert sorted(a.r_dev for a in other) == sorted(a.r_dev for a in runs[0])
    assert [a.r_dev for a in other] != [a.r_dev for a in runs[0]]


def test_on_off_phases_hold_their_share():
    a = ON_OFF["arrivals"]
    sched = T.open_loop_schedule(ON_OFF, 5, 60.0)
    high, low = T.on_off_rates(a["rate"], a["burst_factor"], a["on_fraction"])
    on = a["on_fraction"] * a["cycle_s"]
    n_on = sum(1 for x in sched if (x.due % a["cycle_s"]) < on)
    # the remainder carried from phase to phase moves at most one arrival
    # across each boundary; the total is kept to within one
    assert abs(n_on - 3 * high * on) <= 3.0
    assert abs(len(sched) - 3 * (high * on + low * (a["cycle_s"] - on))) <= 1.0


def test_same_seed_same_schedule():
    t = poisson_mix()
    a = T.open_loop_schedule(t, SEED, 51.0)
    b = T.open_loop_schedule(t, SEED, 51.0)
    assert [(x.due, x.r_dev, x.token_seed) for x in a] == \
        [(x.due, x.r_dev, x.token_seed) for x in b]
