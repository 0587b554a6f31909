"""Split-serving engines: the paper's system, executing real JAX models.

``DiffusionSplitEngine`` — iteration-granularity split (the paper's main
system).  The cloud runs denoising iterations [0, n_final) for each
request, batched within n_final groups (the n_step quantization is what
makes groups batchable AND bounds the number of compiled executables),
then ships (latent fp32 + context fp16) through the transport layer.

``LayerSplitEngine`` — layer-granularity split for every LM architecture
in the zoo (the generalization of the paper's RegNet Table 1 splitting):
cloud runs pattern groups [0, g), ships the hidden boundary, the device
finishes [g, G) + the LM head.

Each engine and device side holds one ``ProgramCache``: its compiled
programs by key and its stats (executable count, cache hits and misses,
compile and device seconds, bytes shipped), which the benchmarks
aggregate.

``DiffusionSplitEngine.process_group`` marks its host stages with
profiler spans (``repro.engine.*``: process_group, encode_prompt,
compile, denoise, pull, pack), ``LayerSplitEngine.process`` its own
(process_layers, compile, cloud_layers, pull, pack), and every jitted
program here has a stable name (``jit_encode_prompt``,
``jit_denoise_range``, ``jit_device_finish``, ``jit_cloud_layers``,
``jit_device_layers``).
A span costs about a microsecond while no profiler runs.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from repro.core.cost_model import CostParams
from repro.core.planner import PlanRequest, Planner
from repro.core.telemetry import DeviceProfile
from repro.core.transport import (
    LinkProfile,
    WAN_LINK,
    pack_boundary,
    pack_boundary_wire,
    transmission_time,
    unpack_boundary,
)
from repro.kernels import ops
from repro.models import diffusion as dif
from repro.models import transformer as tr
from repro.models.common import pdtype
from repro.models.moe import LOCAL_CTX


#: Unified stats schema — every engine and device side (and the replay
#: reconciler, serving.replay) reports exactly these keys.
#: ``device_seconds`` is steady-state execution only; compilation is
#: accounted separately in ``compile_seconds`` (a cache miss compiles
#: the program AOT BEFORE the timed region, so a request's cloud_seconds
#: never includes compile time).
ENGINE_STATS_KEYS = ("device_seconds", "compile_seconds", "bytes_shipped",
                     "requests", "executables", "cache_hits",
                     "cache_misses")


class ProgramCache:
    """Compiled programs by key, and the stats (``ENGINE_STATS_KEYS``)
    of the engine that holds the cache.

    A compiled program is shape-specialized, so each caller's key
    carries what fixes its shapes.  Neither method takes a lock: the
    engines' callers may compile different keys on several threads."""

    def __init__(self):
        self.programs: Dict[Any, Any] = {}
        self.stats: Dict[str, Any] = {
            k: 0.0 if k.endswith("_seconds") else 0 for k in ENGINE_STATS_KEYS}

    def program(self, key, fn, *example_args, **span_args):
        """The compiled ``fn`` for ``key``.  On a miss, lower and compile
        it for ``example_args`` inside a ``repro.engine.compile`` span
        carrying ``span_args``, charged to ``compile_seconds``.  ``fn``
        goes to ``jax.jit`` as it is, so the program is named
        ``jit_<fn.__name__>``."""
        compiled = self.programs.get(key)
        if compiled is not None:
            self.stats["cache_hits"] += 1
            return compiled
        self.stats["cache_misses"] += 1
        with TraceAnnotation("repro.engine.compile", **span_args):
            t0 = time.perf_counter()
            compiled = jax.jit(fn).lower(*example_args).compile()
            self.stats["compile_seconds"] += time.perf_counter() - t0
        self.programs[key] = compiled
        self.stats["executables"] = len(self.programs)
        return compiled

    def run(self, program, *args):
        """Call ``program`` through ``block_until_ready``, charge the
        seconds to ``device_seconds`` and return ``(out, seconds)``."""
        t0 = time.perf_counter()
        out = program(*args)
        out.block_until_ready()
        seconds = time.perf_counter() - t0
        self.stats["device_seconds"] += seconds
        return out, seconds


def pallas_rowwise_int8(x: np.ndarray) -> Tuple[np.ndarray, np.ndarray]:
    """Per-row symmetric int8 through the real ``kernels/int8_quant``
    Pallas kernel (interpret-mode on CPU; same values as the numpy
    reference ``transport.rowwise_quantize_int8`` — kernel-pinned in
    tests/test_kernels.py).  This is the ``rowwise`` hook
    ``pack_boundary_wire`` accepts, so engine payloads are quantized by
    the accelerator kernel rather than numpy."""
    q, s = ops.int8_quantize(jnp.asarray(x, jnp.float32))
    return np.asarray(q), np.asarray(s)


#: the text encoder as one compiled program per (cfg, batch) rather than
#: an eager dispatch per op
_encode_prompt = jax.jit(dif.encode_prompt, static_argnums=1)


@dataclasses.dataclass
class Request:
    request_id: str
    device: DeviceProfile
    cond_tokens: np.ndarray          # (1, text_len)
    uncond_tokens: np.ndarray


@dataclasses.dataclass
class SplitResult:
    request_id: str
    n_cloud: int
    payload: bytes
    cloud_seconds: float
    transfer_seconds: float


class DiffusionSplitEngine:
    def __init__(self, params, cfg, cost: CostParams,
                 link: LinkProfile = WAN_LINK,
                 planner: Optional[Planner] = None,
                 wire: Optional[str] = None):
        self.params = params
        self.cfg = cfg
        self.cost = cost
        self.link = link
        #: wire-format name (core.transport.WIRE_FORMATS): when set,
        #: payloads ship through ``pack_boundary_wire`` with the Pallas
        #: int8 kernel as the row-wise quantizer; None ships the paper's
        #: encoding (``pack_boundary``: latent fp32 + context fp16)
        self.wire = wire
        # the shared decision-maker: assign() delegates here, so the
        # engine runs the exact per-request policy the simulators and
        # the fleet planner use (pass a shared Planner to keep one
        # adaptive-SLA state across engines).  solve_c_batch=cost.c_batch
        # because this engine EXECUTES groups batched (process_group):
        # the split must be sized for the batched rate, preserving the
        # pre-planner solve bit-exactly for any c_batch
        self.planner = planner if planner is not None else Planner(
            cost, policy="variable", solve_c_batch=cost.c_batch)
        #: one compiled denoise program per (n_cloud, batch)
        self.cache = ProgramCache()
        self.stats = self.cache.stats
        #: self-attention layers per UNet run that take the flash kernel,
        #: recorded on the process_group and compile spans
        self.flash_sites = dif.flash_sites(cfg)

    def assign(self, device: DeviceProfile) -> int:
        """Thin delegate into the unified planner: split solve + step
        quantization (sized at ``cost.c_batch`` — see __init__).  Goes
        through the planner's memoized hot path, so serving a fleet of
        repeat device profiles hits the PlanCache instead of re-running
        the full pipeline per request (epoch-invalidated on set_t_lim /
        set_capacity / set_shed_policy; pinned value-identical to the
        audited plan() below)."""
        return self.planner.plan_profile(device).n_final

    def plan(self, device: DeviceProfile):
        """Full ``PlanDecision`` for one device (JSON-serializable, with
        the explain() trace) — what assign() is a projection of."""
        return self.planner.plan(PlanRequest(device=device))

    def process_group(self, requests: List[Request], n_cloud: int,
                      seed: int = 0) -> List[SplitResult]:
        """Run one batched group at the same n_cloud."""
        if not requests:
            return []
        cfg = self.cfg
        B = len(requests)
        with TraceAnnotation(
                "repro.engine.process_group", n_cloud=n_cloud, batch=B,
                flash_sites=self.flash_sites,
                request_ids=";".join(r.request_id for r in requests)):
            with TraceAnnotation("repro.engine.encode_prompt"):
                cond = jnp.asarray(
                    np.concatenate([r.cond_tokens for r in requests]))
                uncond = jnp.asarray(
                    np.concatenate([r.uncond_tokens for r in requests]))
                ctx2 = _encode_prompt(self.params, cfg, cond, uncond)
                latent = jax.random.normal(
                    jax.random.PRNGKey(seed),
                    (B, cfg.latent_channels, cfg.latent_size,
                     cfg.latent_size))
            device_s = 0.0
            if n_cloud > 0:
                def denoise_range(params, latent, ctx2):
                    return dif.denoise_range(params, cfg, latent, ctx2, 0,
                                             n_cloud)
                run = self.cache.program(
                    (n_cloud, B), denoise_range, self.params, latent, ctx2,
                    n_cloud=n_cloud, batch=B, flash_sites=self.flash_sites)
                with TraceAnnotation("repro.engine.denoise"):
                    latent, device_s = self.cache.run(run, self.params,
                                                      latent, ctx2)
            results = []
            with TraceAnnotation("repro.engine.pull"):
                lat_np = np.asarray(latent, np.float32)
                ctx_np = np.asarray(ctx2, np.float32)
            for i, r in enumerate(requests):
                with TraceAnnotation("repro.engine.pack",
                                     request_id=r.request_id):
                    need_ctx = n_cloud < cfg.n_total_iterations
                    ctx_i = ctx_np[:, i] if need_ctx else None
                    if self.wire is not None:
                        payload = pack_boundary_wire(
                            lat_np[i], ctx_i, self.wire,
                            rowwise=pallas_rowwise_int8)
                    else:
                        payload = pack_boundary(lat_np[i], ctx_i)
                    t_net = transmission_time(len(payload), self.link)
                results.append(SplitResult(
                    request_id=r.request_id, n_cloud=n_cloud,
                    payload=payload, cloud_seconds=device_s / B,
                    transfer_seconds=t_net))
                self.stats["bytes_shipped"] += len(payload)
            self.stats["requests"] += B
            return results

    def serve(self, requests: List[Request], seed: int = 0
              ) -> Dict[str, SplitResult]:
        """Schedule + group + execute a batch of requests."""
        groups: Dict[int, List[Request]] = {}
        for r in requests:
            groups.setdefault(self.assign(r.device), []).append(r)
        out: Dict[str, SplitResult] = {}
        for n_cloud, members in sorted(groups.items()):
            for res in self.process_group(members, n_cloud, seed):
                out[res.request_id] = res
        return out


class DiffusionDeviceSim:
    """The mobile side: receives the payload, finishes [n_cloud, n_total)
    and decodes the VAE — on the same host, standing in for the device."""

    def __init__(self, params, cfg):
        self.params = params
        self.cfg = cfg
        #: one compiled finish-and-decode program per (n_cloud, batch)
        self.cache = ProgramCache()
        self.stats = self.cache.stats

    def complete(self, result: SplitResult):
        cfg = self.cfg
        lat, ctx = unpack_boundary(result.payload)
        latent = jnp.asarray(lat)[None] if lat.ndim == 3 else jnp.asarray(lat)
        n0 = result.n_cloud
        if ctx is not None:
            ctx2 = jnp.asarray(ctx)[:, None] if ctx.ndim == 3 else jnp.asarray(ctx)
        else:
            ctx2 = jnp.zeros((2, latent.shape[0], cfg.text_len,
                              cfg.text_width), jnp.float32)
        B = latent.shape[0]

        def device_finish(params, latent, ctx2):
            out = dif.denoise_range(params, cfg, latent, ctx2, n0,
                                    cfg.n_total_iterations)
            return dif.apply_vae_decoder(params["vae"], cfg, out)
        run = self.cache.program((n0, B), device_finish, self.params, latent,
                                 ctx2, n_cloud=n0, batch=B)
        out, _ = self.cache.run(run, self.params, latent, ctx2)
        self.stats["requests"] += B
        return out


# ==========================================================================
# Layer-granularity split for LM architectures
# ==========================================================================
class LayerSplitEngine:
    """Cloud side of a layer split: embed + groups [0, g), ship hidden.

    ``process`` marks its host stages with profiler spans:
    ``repro.engine.process_layers`` around the call and, on an
    executable-cache miss, ``repro.engine.compile`` (both with
    ``stop_group``, ``batch``, ``tokens``, the prompt length per row,
    and ``flash_sites``, the self-attention layers that run through the
    flash kernel); ``repro.engine.cloud_layers``, the compiled call
    through ``block_until_ready``; ``repro.engine.pull``, the copy of the
    fp16 hidden states to the host; ``repro.engine.pack``, their byte
    count and transfer time.

    The cloud half runs forward only, so on the TPU it takes the Pallas
    kernels of ``kernels.ops.kernel_registry``; elsewhere they would run
    interpreted, which is for validation only."""

    def __init__(self, params, cfg, link: LinkProfile = WAN_LINK):
        self.params = params
        self.cfg = cfg
        self.link = link
        #: one compiled program per (stop_group, batch signature)
        self.cache = ProgramCache()
        self.stats = self.cache.stats
        self.kernels = ops.kernel_registry() if ops.on_tpu() else None

    def _span_args(self, stop_group: int, B: int, S: int):
        sites = (tr.flash_sites(self.cfg, S, stop_group=stop_group)
                 if self.kernels else 0)
        return dict(stop_group=stop_group, batch=B, tokens=S,
                    flash_sites=sites)

    def process(self, batch: Dict[str, np.ndarray], stop_group: int):
        cfg = self.cfg
        B, S = batch["tokens"].shape
        span_args = self._span_args(stop_group, B, S)
        with TraceAnnotation("repro.engine.process_layers", **span_args):
            batch = {k: jnp.asarray(v) for k, v in batch.items()}

            def cloud_layers(params, batch):
                x = tr.embed_inputs(params, batch, cfg)
                positions = jnp.arange(x.shape[1])
                # round to the fp16 payload here: the host then copies it
                # as it ships and converts nothing
                return tr.run_layer_range(
                    params, x, cfg, LOCAL_CTX, start_group=0,
                    stop_group=stop_group, positions=positions,
                    kernels=self.kernels).astype(jnp.float16)
            key = (stop_group, tuple(sorted(
                (k, v.shape, str(v.dtype)) for k, v in batch.items())))
            run = self.cache.program(key, cloud_layers, self.params, batch,
                                     **span_args)
            with TraceAnnotation("repro.engine.cloud_layers"):
                hidden, _ = self.cache.run(run, self.params, batch)
            with TraceAnnotation("repro.engine.pull"):
                payload = np.asarray(hidden)
            with TraceAnnotation("repro.engine.pack"):
                self.stats["bytes_shipped"] += payload.nbytes
                t_net = transmission_time(payload.nbytes, self.link)
            self.stats["requests"] += B
            return payload, t_net


class LayerSplitDevice:
    """Device side: groups [g, G) + tail + head."""

    def __init__(self, params, cfg):
        self.params = params
        self.cfg = cfg
        #: one compiled program per (start_group, hidden shape)
        self.cache = ProgramCache()
        self.stats = self.cache.stats

    def complete(self, hidden_fp16: np.ndarray, start_group: int):
        cfg = self.cfg
        hidden = jnp.asarray(hidden_fp16).astype(pdtype(cfg))

        def device_layers(params, hidden):
            positions = jnp.arange(hidden.shape[1])
            x = tr.run_layer_range(
                params, hidden, cfg, LOCAL_CTX, start_group=start_group,
                stop_group=cfg.num_groups(), positions=positions)
            x = tr.apply_norm(params["final_norm"], x, cfg.norm_eps)
            return tr.unembed(params, x[:, -1:], cfg)
        B, S = hidden.shape[:2]
        run = self.cache.program((start_group, hidden.shape), device_layers,
                                 self.params, hidden, start_group=start_group,
                                 batch=B, tokens=S)
        out, _ = self.cache.run(run, self.params, hidden)
        self.stats["requests"] += B
        return out
