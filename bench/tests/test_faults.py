"""A run with the timed path broken underneath reads ``correct: false``.

Each test drives the whole of ``bench/harness.main`` on the CPU at a
small size: schedule, planner, engine, window, metrics and the
reference check.  Only the look for a chip is skipped.  The fault is
planted in the system under test after it is built, where the answer is
produced; the limits are the cell's own.

    JAX_PLATFORMS=cpu python -m pytest -q bench/tests/test_faults.py
"""
import json

import numpy as np
import pytest

from bench import harness

SD_SMALL = dict(latent_size=8, text_len=16, text_width=64, text_layers=2,
                text_heads=4, text_vocab=256, unet_base=32, unet_mults=[1, 2],
                unet_attn_levels=[0, 1], unet_res_blocks=1, unet_heads=4,
                vae_base=16, vae_mults=[1, 2])
WORKLOAD = "sd-v1.fleet-poisson"


#: arrivals fast enough that groups fill to two at this size
BUSY = {"arrivals": {"process": "poisson", "rate": 2.0}}


def run_cell(capsys, hook, seconds=6, traffic_over=None):
    rc = harness.main(["--workload", WORKLOAD, "--seed", str(2**31 + 99),
                       "--seconds", str(seconds), "--trace", "0"],
                      system_hook=hook, sizes=SD_SMALL,
                      traffic_over=traffic_over)
    assert rc == 0
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


def wrap(system, change):
    """Pass every group's results through ``change(results)``."""
    engine = system.engine
    real = engine.process_group

    def broken(requests, n_cloud, seed=0):
        return change(real(requests, n_cloud, seed))
    engine.process_group = broken


def step_short(system):
    """The last DDIM step returns its state unchanged: each group runs
    one step fewer than it reports."""
    engine = system.engine
    real = engine.process_group

    def broken(requests, n_cloud, seed=0):
        out = real(requests, n_cloud - 1, seed)
        for r in out:
            r.n_cloud = n_cloud
        return out
    engine.process_group = broken


def half_batch(system):
    """Half of a group is left out: the first half's answers are handed
    to the rest."""
    engine = system.engine
    real = engine.process_group

    def broken(requests, n_cloud, seed=0):
        if len(requests) < 2:
            return real(requests, n_cloud, seed)
        k = len(requests) // 2
        out = real(requests[:k], n_cloud, seed)
        extra = []
        for j, r in enumerate(requests[k:]):
            like = out[j % k]
            extra.append(type(like)(**dict(vars(like),
                                           request_id=r.request_id)))
        return out + extra
    engine.process_group = broken


def payload_altered(system):
    """Each payload's latent is altered by 10% of its scale after packing."""
    from repro.core.transport import pack_boundary, unpack_boundary

    def change(out):
        for r in out:
            lat, ctx = unpack_boundary(r.payload)
            lat = lat + 0.1 * np.std(lat) * np.sign(lat)
            r.payload = pack_boundary(lat, ctx)
        return out
    wrap(system, change)


def context_unconditional(system):
    """Each payload ships the unconditional prompt's context in place of
    the request's own."""
    from repro.core.transport import pack_boundary, unpack_boundary

    def change(out):
        for r in out:
            lat, ctx = unpack_boundary(r.payload)
            r.payload = pack_boundary(lat, np.stack([ctx[0], ctx[0]]))
        return out
    wrap(system, change)


def sound(system):
    pass


@pytest.mark.parametrize("fault,correct,over", [
    (sound, True, None), (sound, True, BUSY), (step_short, False, None),
    (half_batch, False, BUSY), (payload_altered, False, None),
    (context_unconditional, False, None)])
def test_sd_fault(capsys, fault, correct, over):
    out = run_cell(capsys, fault, traffic_over=over)
    assert out["attempted"] > 0
    assert out["correct"] is correct, out["checks"]
    assert list(out)[-1] == "checks"
