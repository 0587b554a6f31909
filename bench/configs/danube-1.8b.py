"""danube-1.8b served through the program: weights, engine, warm-up, check.

The system under test is ``serving.engine.LayerSplitEngine``: each group
of prompts, stacked into one ``(batch, prompt_tokens)`` token array,
runs the embedding and layers [0, split) on the chip and leaves as the
fp16 hidden states the phone would receive.  Every request is planned to
the traffic file's fixed split and admitted to a batching window of
``max_wait_s`` (the planner has no cost terms for an LM split yet).  The
benchmark makes the weights itself, from the seed, on the device; the
program is given them as its parameter tree.  The check compares each
sampled payload, and ``LayerSplitDevice.complete`` run on it (the
phone's half: the remaining layers, the final norm and the head), with
the plain reference in ``danube-1.8b.ref.py``.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

HERE = Path(__file__).resolve().parent


def load_reference():
    spec = importlib.util.spec_from_file_location("danube_1_8b_ref",
                                                  HERE / "danube-1.8b.ref.py")
    mod = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = mod
    spec.loader.exec_module(mod)
    return mod


REF = load_reference()


def make_cfg(sizes: dict):
    """The program's configuration with the file's sizes stated over it."""
    from repro.configs.h2o_danube_1_8b import CONFIG
    return dataclasses.replace(CONFIG, **sizes)


def _leaf_rule(path, shape):
    keys = [str(getattr(k, "key", k)) for k in path]
    name = keys[-1]
    if name == "scale":
        return "ones", 0.0
    if name == "embed":
        return "normal", 0.005
    if keys[0] == "blocks":
        shape = shape[1:]               # stacked over layers
    fan = int(np.prod(shape[:-1])) if name == "wo" else int(shape[0])
    return "clipped", 1.0 / np.sqrt(fan)


def make_params(cfg, seed: int):
    """The parameter tree the program expects, drawn by the benchmark
    (``bench/weights.py``).  Its layout is read from the program's
    ``init_params`` by shape only."""
    from repro.models import transformer as tr
    from bench.weights import draw
    shapes = jax.eval_shape(lambda k: tr.init_params(cfg, k),
                            jax.random.PRNGKey(0))
    return draw(shapes, _leaf_rule, seed)


@dataclasses.dataclass
class Served:
    """What one request left behind: its payload and where it came from."""
    index: int
    n_cloud: int
    batch: int
    row: int
    hidden: np.ndarray              # (prompt_tokens, d_model) float16


class System:
    """The open-loop adapter the harness drives."""

    #: the program each ``process_group`` call runs on the device, by its
    #: name in the trace (``bench/tracing.py``)
    first_program = "jit_cloud_layers"

    def __init__(self, spec: dict, traffic: dict, seed: int):
        from repro.core.transport import LOCAL_LINK
        self.spec = spec
        self.sizes = spec["sizes"]
        self.cfg = make_cfg(self.sizes)
        self.seed = seed
        self.link = LOCAL_LINK
        plan = traffic["planner"]
        self.split = plan["split"]
        self.batch_size = plan["batch_size"]
        self.max_wait_s = plan["max_wait_s"]
        self.prompt_len = traffic["prompt_tokens"]
        self.params = None
        self.engine = None
        self.tokens = {}

    # -- set-up ------------------------------------------------------------
    def prepare(self, schedule, prompt_tokens):
        """Draw every prompt before the window; returns the
        ``(split, batch)`` keys the schedule can dispatch."""
        for a in schedule:
            self.tokens[a.index] = prompt_tokens(
                a.token_seed, self.prompt_len, self.cfg.vocab_size)
        most = min(len(schedule), self.batch_size)
        return [(self.split, b) for b in range(1, most + 1)]

    def build(self):
        from repro.serving.engine import LayerSplitEngine
        self.params = make_params(self.cfg, self.seed)
        self.engine = LayerSplitEngine(self.params, self.cfg, link=self.link)

    def warm(self, keys):
        """Compile and run every ``(split, batch)`` program the window can
        use, one at a time (each holds its score blocks on the device)."""
        for n, b in keys:
            self.engine.process(
                {"tokens": np.ones((b, self.prompt_len), np.int32)}, n)
        return dict(self.engine.stats)

    # -- the window ----------------------------------------------------------
    def plan(self, arrival):
        return self.split, True, self.max_wait_s

    def run_group(self, indices, n_cloud, group_seed):
        tokens = np.concatenate([self.tokens[i] for i in indices])
        payload, _ = self.engine.process({"tokens": tokens}, n_cloud)
        return [Served(i, n_cloud, len(indices), row, payload[row])
                for row, i in enumerate(indices)]

    # -- after the window ------------------------------------------------------
    @staticmethod
    def finite(served: Served) -> bool:
        return bool(np.all(np.isfinite(served.hidden)))

    def group_flops(self, n_cloud, batch):
        return REF.group_flops(self.sizes, n_cloud, batch, self.prompt_len)

    def release(self):
        """Drop the engine and its executables before the reference runs;
        the weights stay, the reference and the phone's half read them."""
        self.engine = None

    @staticmethod
    def sample(served, rng, k):
        """``k`` served requests drawn from the seed: one from a later row
        of a batched group where there is one (a row that only batching
        can get wrong), then any others."""
        picked = []
        later = [s for s in served if s.row > 0]
        if later:
            picked.append(later[rng.integers(len(later))])
        rest = [s for s in served if all(s is not p for p in picked)]
        pick = rng.choice(len(rest), size=min(k - len(picked), len(rest)),
                          replace=False)
        return picked + [rest[i] for i in sorted(pick)]

    def reference(self, precision=None):
        """The plain reference: float32 at the highest matmul precision,
        the only one this configuration offers (``bench/control.py
        --also`` has no other to read against)."""
        if precision is not None:
            raise ValueError(f"no reference at precision {precision!r}")
        return REF.Reference(self.sizes)

    def control_reference(self):
        """The reference one precision step below the configuration's
        bfloat16: every matmul operand rounded to fp8 (e4m3)."""
        return REF.Reference(self.sizes, operands=jnp.float8_e4m3fn)

    def check_run(self, served, rng):
        return self.check(self.sample(served, rng,
                                      self.spec["check"]["sample"]))

    def answers(self, samples, ref):
        """Each sample computed by ``ref`` from its prompt: [(hidden
        states after its split (S, d), last-position logits (vocab,))]."""
        return [ref.forward(self.params, self.tokens[s.index][0], s.n_cloud)
                for s in samples]

    def served_answers(self, samples):
        """Each sample's payload, and the phone's half of the program run
        on it: [(hidden (S, d), last-position logits (vocab,))]."""
        from repro.serving.engine import LayerSplitDevice
        phone = LayerSplitDevice(self.params, self.cfg)
        out = []
        for s in samples:
            logits = phone.complete(s.hidden[None], s.n_cloud)
            out.append((s.hidden, np.asarray(logits, np.float32)[
                0, -1, :self.cfg.vocab_size]))
        return out

    @staticmethod
    def compare(got, want):
        """Worst relative error over the samples, of the hidden states and
        of the logits."""
        worst = {"hidden_rel_err": 0.0, "logits_rel_err": 0.0}
        for (h_g, l_g), (h_w, l_w) in zip(got, want):
            worst["hidden_rel_err"] = max(worst["hidden_rel_err"],
                                          rel_err(h_g, h_w))
            worst["logits_rel_err"] = max(worst["logits_rel_err"],
                                          rel_err(l_g, l_w))
        return worst

    def check(self, samples, reference=None):
        """Compare each sampled payload and its logits with the
        reference.  Returns {name: worst value} over the sample."""
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
