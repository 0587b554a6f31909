"""sd-v1 served through the program: weights, engine, warm-up, check.

The system under test is ``serving.engine.DiffusionSplitEngine`` with the
program's ``Planner`` (paper constants ``CALIBRATED``, batch size from
the traffic file) on the paper's local link with the default ``paper``
boundary encoding.  The benchmark makes the weights itself, from the
seed, on the device, in one jitted call; the program is given them as
its parameter tree.  The check compares served payloads with the plain
reference in ``sd-v1.ref.py``.
"""
from __future__ import annotations

import concurrent.futures as cf
import dataclasses
import importlib.util
import math
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

HERE = Path(__file__).resolve().parent


def load_reference():
    spec = importlib.util.spec_from_file_location("sd_v1_ref",
                                                  HERE / "sd-v1.ref.py")
    mod = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = mod
    spec.loader.exec_module(mod)
    return mod


REF = load_reference()


def make_cfg(sizes: dict):
    from repro.configs.stable_diffusion_v1 import DiffusionConfig
    kw = {k: tuple(v) if isinstance(v, list) else v for k, v in sizes.items()}
    return DiffusionConfig(**kw)


def _leaf_rule(path, shape):
    name = str(getattr(path[-1], "key", path[-1]))
    if name == "scale":
        return "ones", 0.0
    if name == "bias":
        return "zeros", 0.0
    if name in ("tok", "pos"):
        return "normal", 0.02
    fan = int(np.prod(shape[1:])) if len(shape) == 4 else int(shape[0])
    return "clipped", 1.0 / np.sqrt(max(fan, 1))


def make_params(cfg, seed: int):
    """The parameter tree the program expects, drawn by the benchmark
    (``bench/weights.py``).  Its layout is read from the program's
    ``init_params`` by shape only."""
    from repro.models import diffusion as dif
    from bench.weights import draw
    shapes = jax.eval_shape(lambda k: dif.init_params(cfg, k),
                            jax.random.PRNGKey(0))
    return draw(shapes, _leaf_rule, seed)


@dataclasses.dataclass
class Served:
    """What one request left behind: enough to re-run it in the reference."""
    index: int
    n_cloud: int
    group_seed: int
    batch: int
    row: int
    payload: bytes


class System:
    """The open-loop adapter the harness drives."""

    #: the program each ``process_group`` call runs first on the device
    #: (the prompt encoder), by its name in the trace (``bench/tracing.py``)
    first_program = "jit_encode_prompt"

    def __init__(self, spec: dict, traffic: dict, seed: int):
        from repro.core.planner import Planner
        from repro.core.transport import LOCAL_LINK
        from repro.serving.simulator import CALIBRATED
        self.spec = spec
        self.sizes = spec["sizes"]
        self.cfg = make_cfg(self.sizes)
        self.traffic = traffic
        self.seed = seed
        self.link = LOCAL_LINK
        self.batch_size = traffic["planner"]["batch_size"]
        self.planner = Planner(CALIBRATED, batch_size=self.batch_size)
        self.cost = CALIBRATED
        self.params = None
        self.engine = None
        self.requests = {}

    # -- set-up ------------------------------------------------------------
    def profile(self, arrival):
        from repro.core.telemetry import DeviceProfile
        return DeviceProfile(f"dev{arrival.index}", arrival.r_dev,
                             k_decode=self.traffic["fleet"]["k_decode"],
                             rtt=self.link.rtt)

    def prepare(self, schedule, prompt_tokens):
        """Build every request object before the window; returns the
        ``(n_final, batch)`` keys the schedule can dispatch: batch 1 for
        every planned ``n_final``, and batches up to the planner's size
        where that many requests plan to it."""
        from repro.core.planner import PlanRequest
        from repro.serving.engine import Request
        L = self.cfg.text_len
        uncond = np.zeros((1, L), np.int32)
        count = {}
        for a in schedule:
            prof = self.profile(a)
            cond = prompt_tokens(a.token_seed, L, self.cfg.text_vocab)
            self.requests[a.index] = Request(f"req{a.index}", prof, cond,
                                             uncond)
            n = self.planner.plan(PlanRequest(device=prof)).n_final
            count[n] = count.get(n, 0) + 1
        return sorted((n, b) for n, c in count.items() if n > 0
                      for b in range(1, min(c, self.batch_size) + 1))

    def build(self):
        from repro.serving.engine import DiffusionSplitEngine
        self.params = make_params(self.cfg, self.seed)
        self.engine = DiffusionSplitEngine(
            self.params, self.cfg, self.cost, link=self.link,
            planner=self.planner)

    def warm(self, keys, threads: int = 3):
        """Compile and run every ``(n_cloud, batch)`` program the window
        can use, ``threads`` keys at a time (each holds its temporaries on
        the device while it runs)."""
        dummy = list(self.requests.values())[:1] * max(b for _, b in keys)
        with cf.ThreadPoolExecutor(min(threads, len(keys))) as pool:
            list(pool.map(lambda k: self.engine.process_group(
                dummy[:k[1]], k[0], seed=0), keys))
        return dict(self.engine.stats)

    # -- the window ----------------------------------------------------------
    def plan(self, arrival):
        d = self.engine.plan(self.requests[arrival.index].device)
        return d.n_final, d.batch_admit, d.batch_max_wait

    def run_group(self, indices, n_cloud, group_seed):
        reqs = [self.requests[i] for i in indices]
        results = self.engine.process_group(reqs, n_cloud, seed=group_seed)
        return [Served(i, n_cloud, group_seed, len(reqs), row, r.payload)
                for row, (i, r) in enumerate(zip(indices, results))]

    # -- after the window ------------------------------------------------------
    @staticmethod
    def finite(served: Served) -> bool:
        from repro.core.transport import unpack_boundary
        lat, ctx = unpack_boundary(served.payload)
        return bool(np.all(np.isfinite(lat))
                    and (ctx is None or np.all(np.isfinite(ctx))))

    def group_flops(self, n_cloud, batch):
        return REF.group_flops(self.sizes, n_cloud, batch)

    def release(self):
        """Drop the engine and its executables before the reference runs;
        the weights stay, the reference reads them."""
        self.engine = None

    def sample(self, served, rng, k):
        """``k`` served requests drawn from the seed: the longest, one
        from a later row of a batched group where there is one (a row
        that only batching can get wrong), then any others."""
        longest = max(served, key=lambda s: (s.n_cloud, -s.index))
        picked = [longest]
        later = [s for s in served if s.row > 0 and s is not longest]
        if later and k > 1:
            picked.append(later[rng.integers(len(later))])
        rest = [s for s in served if all(s is not p for p in picked)]
        pick = rng.choice(len(rest), size=min(k - len(picked), len(rest)),
                          replace=False)
        return picked + [rest[i] for i in sorted(pick)]

    def reference(self, precision=None):
        """The plain reference at the precision the configuration states,
        or at ``precision`` (a key of ``REF.PRECISIONS``)."""
        name = precision or self.spec["matmul_precision"]
        return REF.Reference(self.sizes, self.spec["schedule"],
                             precision=REF.PRECISIONS[name])

    def control_reference(self):
        """The reference one precision step below the configuration's
        float32 at the TPU's default precision: bfloat16 throughout."""
        return REF.Reference(self.sizes, self.spec["schedule"],
                             dtype=jnp.bfloat16,
                             precision=jax.lax.Precision.DEFAULT)

    def check_run(self, served, rng):
        return self.check(self.sample(served, rng,
                                      self.spec["check"]["sample"]))

    def answers(self, samples, ref):
        """Each sample's cloud half computed by ``ref``, from the request's
        prompt, its group's initial noise and its ``n_cloud``:
        [(latent (C, H, W), context (2, L, W))]."""
        L = self.cfg.text_len
        out = []
        for s in samples:
            req = self.requests[s.index]
            lat0 = jax.random.normal(
                jax.random.PRNGKey(s.group_seed),
                (s.batch, self.cfg.latent_channels, self.cfg.latent_size,
                 self.cfg.latent_size))[s.row:s.row + 1]
            lat, ctx = ref.cloud_half(
                self.params, jnp.asarray(req.cond_tokens),
                jnp.zeros((1, L), jnp.int32), lat0, s.n_cloud)
            out.append((lat[0], ctx[:, 0]))
        return out

    @staticmethod
    def served_answers(samples):
        """Each sample's payload as ``unpack_boundary`` decodes it."""
        from repro.core.transport import unpack_boundary
        return [unpack_boundary(s.payload) for s in samples]

    @staticmethod
    def compare(got, want):
        """Worst relative error over the samples, of the latent and of
        the context (both prompts' rows); a payload with no context reads
        as infinitely wrong, since every cell's split leaves steps to the
        phone."""
        worst = {"latent_rel_err": 0.0, "context_rel_err": 0.0}
        for (lat_g, ctx_g), (lat_w, ctx_w) in zip(got, want):
            worst["latent_rel_err"] = max(worst["latent_rel_err"],
                                          rel_err(lat_g, lat_w))
            worst["context_rel_err"] = max(
                worst["context_rel_err"],
                math.inf if ctx_g is None else rel_err(ctx_g, ctx_w))
        return worst

    def check(self, samples, reference=None):
        """Compare each sampled payload with the reference's cloud half.
        Returns {name: worst value} over the sample."""
        want = self.answers(samples, reference or self.reference())
        return self.compare(self.served_answers(samples), want)


def rel_err(got, want) -> float:
    """||got - want|| / ||want||; inf where either holds a non-finite."""
    got = np.asarray(got, np.float64)
    want = np.asarray(want, np.float64)
    if not (np.all(np.isfinite(got)) and np.all(np.isfinite(want))):
        return float("inf")
    return float(np.linalg.norm(got - want) / max(np.linalg.norm(want),
                                                  1e-30))
