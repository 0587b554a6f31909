"""Plain reference of h2o-danube-1.8b's forward pass, and its operation
count.

Written from the published block (the configuration file's ``sizes``):
token embedding; per layer an RMSNorm, q/k/v projections, RoPE (the
half-split rotation), grouped-query attention under an explicit causal
and sliding-window mask (query i sees keys i - window < j <= i), the
output projection and the residual, then an RMSNorm, a SwiGLU MLP and
the residual; after the last layer the final RMSNorm and the untied
head.  Straightforward ``jax.numpy``; it imports nothing of the program
and reads the weights the benchmark made by the key names of their tree
(``embed``, ``blocks/b0/{norm1,wq,wk,wv,wo,norm2,mlp}``, ``final_norm``,
``lm_head``).  Weights stay bfloat16 on the device and are upcast one
layer at a time.  So that it fits beside the program's weights at 8192
tokens, attention is computed for one block of queries at a time
against every key, with the mask; each query's softmax still sees all
of its row.

``Reference(sizes)`` computes in float32 at the highest matmul
precision.  ``Reference(sizes, operands=jnp.float8_e4m3fn)`` is the
control: the same arithmetic with every matmul operand scaled and
rounded to fp8 (e4m3), one precision step below the configuration's
bfloat16.  ``tests/lm_reference.py`` is the same forward pass in one
piece; the tier-1 tests hold the two equal.

Departure: the vocabulary rows past ``vocab_size`` (the program pads the
embedding and the head) are never read.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST


class Reference:
    def __init__(self, sizes: dict, *, operands=None, q_block: int = 1024):
        self.s = sizes
        self.operands = operands
        self.q_block = q_block
        self._embed = jax.jit(lambda e, t: e[t].astype(jnp.float32))
        self._layer = jax.jit(self._layer_fn)
        self._head = jax.jit(self._head_fn)

    # -- primitives ---------------------------------------------------------
    def _r(self, a):
        """``a`` in float32, or rounded to the control's format after
        scaling the whole operand so that its largest magnitude is the
        format's largest value, as fp8 matmuls are run (unscaled, the
        attention probabilities of a 4096-key window, about 2e-4, would
        fall below e4m3's smallest subnormal)."""
        a = a.astype(jnp.float32)
        if self.operands is not None:
            scale = jnp.max(jnp.abs(a)) / float(jnp.finfo(self.operands).max)
            scale = jnp.where(scale > 0, scale, 1.0)
            a = (a / scale).astype(self.operands).astype(jnp.float32) * scale
        return a

    def _mm(self, eq, a, b):
        return jnp.einsum(eq, self._r(a), self._r(b), precision=HIGHEST)

    def _norm(self, x, scale):
        ms = jnp.mean(x * x, axis=-1, keepdims=True)
        return x / jnp.sqrt(ms + self.s["norm_eps"]) * scale.astype(
            jnp.float32)

    def _rope(self, x):
        """x (S, H, D): rotate (x[..., i], x[..., i + D/2]) by position x
        theta^(-2i/D)."""
        S, D = x.shape[0], x.shape[-1]
        inv = 1.0 / self.s["rope_theta"] ** (np.arange(0, D, 2) / D)
        ang = np.arange(S)[:, None] * inv[None, :]
        cos = jnp.asarray(np.cos(ang), jnp.float32)[:, None, :]
        sin = jnp.asarray(np.sin(ang), jnp.float32)[:, None, :]
        a, b = x[..., :D // 2], x[..., D // 2:]
        return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], -1)

    def _attend(self, q, k, v):
        """q (S, H, D), k and v (S, H, D) -> (S, H, D), one block of
        ``q_block`` queries at a time against every key."""
        S, H, D = q.shape
        W = self.s["window"]
        qb = min(self.q_block, S)
        assert S % qb == 0
        keys = jnp.arange(S)

        def block(args):
            i, qi = args
            pos = i * qb + jnp.arange(qb)
            mask = keys[None, :] <= pos[:, None]
            if W:
                mask &= keys[None, :] > pos[:, None] - W
            s = self._mm("qhe,khe->hqk", qi, k) / np.sqrt(D)
            s = jnp.where(mask[None], s, -jnp.inf)
            return self._mm("hqk,khe->qhe", jax.nn.softmax(s, axis=-1), v)

        out = jax.lax.map(block, (jnp.arange(S // qb),
                                  q.reshape(S // qb, qb, H, D)))
        return out.reshape(S, H, D)

    # -- one layer, and the head ---------------------------------------------
    def _layer_fn(self, p, x):
        """x (S, d) float32 after the layers before; ``p`` that layer's
        weights (bfloat16)."""
        H, Hkv = self.s["num_heads"], self.s["num_kv_heads"]
        h = self._norm(x, p["norm1"]["scale"])
        q = self._rope(self._mm("sd,dhe->she", h, p["wq"]))
        k = self._rope(self._mm("sd,dhe->she", h, p["wk"]))
        v = self._mm("sd,dhe->she", h, p["wv"])
        # query head n reads kv head n // (H / Hkv)
        k = jnp.repeat(k, H // Hkv, axis=1)
        v = jnp.repeat(v, H // Hkv, axis=1)
        x = x + self._mm("she,hed->sd", self._attend(q, k, v), p["wo"])
        h = self._norm(x, p["norm2"]["scale"])
        m = p["mlp"]
        g = self._mm("sd,df->sf", h, m["wi_gate"])
        u = self._mm("sd,df->sf", h, m["wi_up"])
        return x + self._mm("sf,fd->sd", jax.nn.silu(g) * u, m["wo"])

    def _head_fn(self, params, x):
        x = self._norm(x[-1], params["final_norm"]["scale"])
        return self._mm("d,dv->v", x, params["lm_head"])[
            :self.s["vocab_size"]]

    def forward(self, params, tokens, stop_layer: int):
        """tokens (S,) -> (hidden states after ``stop_layer`` layers
        (S, d), last-position logits (vocab_size,)), float32 numpy."""
        blocks = params["blocks"]["b0"]
        x = self._embed(params["embed"], jnp.asarray(tokens))
        hidden = x
        for n in range(self.s["num_layers"]):
            if n == stop_layer:
                hidden = x
            x = self._layer(jax.tree.map(lambda a, n=n: a[n], blocks), x)
        if stop_layer == self.s["num_layers"]:
            hidden = x
        return np.asarray(hidden), np.asarray(self._head(params, x))


# --------------------------------------------------------------------------
# Useful operations: two per multiply-add, attention counted under the
# causal-window mask (the keys each query may see), the head not at all
# (the phone runs it).
# --------------------------------------------------------------------------
def matmul_flops_per_token(s: dict) -> int:
    """q, k, v and output projections and the SwiGLU MLP, one layer."""
    d, hd = s["d_model"], s["head_dim"]
    proj = d * s["num_heads"] * hd * 2 + d * s["num_kv_heads"] * hd * 2
    return 2 * (proj + 3 * d * s["d_ff"])


def visible_keys(seq_len: int, window: int) -> int:
    """Keys summed over the queries of one sequence: min(i + 1, window)
    for query i (i + 1 where there is no window)."""
    i = np.arange(seq_len, dtype=np.int64) + 1
    return int(np.sum(np.minimum(i, window) if window else i))


def attention_flops(s: dict, seq_len: int) -> int:
    """Scores and weighted sum of one layer over one sequence."""
    return (2 * 2 * s["num_heads"] * s["head_dim"]
            * visible_keys(seq_len, s["window"]))


def group_flops(s: dict, n_layers: int, batch: int, seq_len: int) -> int:
    """A group of ``batch`` prompts of ``seq_len`` tokens through the
    first ``n_layers`` layers."""
    return batch * n_layers * (seq_len * matmul_flops_per_token(s)
                               + attention_flops(s, seq_len))
