"""Serving CLI — thin front-end over ``repro.serving``.

``diffusion`` mode is the paper's deployment scenario: a queue of
text-conditioned image generations served through the PAS sampler.  The
default engine is the step-level continuous-batching
:class:`repro.serving.DiffusionEngine` (heterogeneous step counts and PAS
plans per request, immediate lane backfill); ``--engine static`` keeps the
seed's fixed-size lockstep batching for comparison.

``lm`` mode serves an assigned LM arch: batched prefill then greedy decode
against the KV cache (the ``decode_*`` dry-run cells lower exactly this
step function).

``--cache`` arms the cross-request feature cache (``repro.serving.cache``)
on the continuous engine: ``intra`` lets a request reuse its own FULL-step
captures (DeepCache-style), ``cross`` lets requests with nearby prompts and
timesteps reuse each other's, with ``--cache-threshold`` as the
quality/reuse knob (0 = bit-exact with ``off``).

``--quality {draft,balanced,high,exact,<q>}`` resolves a per-request
quality/compute tradeoff through ``repro.serving.policy``: the tier (or a
continuous quality in [0, 1]) picks both the PAS plan shape and the
feature-cache threshold per request (``exact`` = all-FULL + threshold 0 =
bit-exact with the stock path).  ``--profile PATH`` loads a shift-score
calibration profile (``examples/pas_calibration.py --profile-out``) and
refines the thresholds per timestep bucket.  Under ``--http`` the quality
knob also arrives per request in the payload (``"quality": "draft"``).

``--kernels {xla,pallas}`` selects the kernel backend for the jitted hot
path (``repro.models.backend``): ``xla`` is the inline reference — bit-exact
with builds predating the backend switch — and ``pallas`` routes Uni-conv,
the fused GroupNorm+SiLU and flash attention through the Pallas kernels
(interpret mode off-TPU).  The backend is engine-wide: payloads may carry
``"kernels"`` only to *assert* it (mismatch = 400 ``forbidden``).

``--shards N`` shards the continuous engine's lane axis over N devices
(``repro.serving.ShardedDiffusionEngine``): each device owns ``batch / N``
lanes, branch classes are chosen per shard, and the feature cache splits
into shard-local rings.  ``--shards 1`` is exactly the single-device
engine.  On CPU-only hosts expose devices first, e.g.
``XLA_FLAGS=--xla_force_host_platform_device_count=8``.

``--http HOST:PORT`` serves the continuous engine over an asyncio HTTP
frontend (``repro.serving.frontend``) instead of running a synthetic batch:
the engine event loop moves onto a dedicated driver thread, requests
arrive as ``POST /generate`` and stream per-step progress as NDJSON,
``POST /cancel`` aborts mid-denoise, backpressure answers 429, and
SIGINT/SIGTERM (or ``POST /shutdown``) drain gracefully.  ``PORT 0``
binds an ephemeral port; ``--port-file`` publishes the bound port for
scripted clients (``python -m repro.serving.client``).

Usage:
  PYTHONPATH=src python -m repro.launch.serve --mode diffusion --requests 8
  PYTHONPATH=src python -m repro.launch.serve --mode diffusion --pas --engine static
  PYTHONPATH=src python -m repro.launch.serve --mode diffusion --pas --cache cross
  XLA_FLAGS=--xla_force_host_platform_device_count=8 \
    PYTHONPATH=src python -m repro.launch.serve --mode diffusion --batch 8 --shards 4
  PYTHONPATH=src python -m repro.launch.serve --mode diffusion \
    --http 127.0.0.1:8080 --batch 4 --timesteps 20
  PYTHONPATH=src python -m repro.launch.serve --mode lm --arch gemma3-1b --requests 4
"""
from __future__ import annotations

import argparse
import asyncio
import dataclasses
import os
import signal
import time
import warnings
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import ARCH_IDS, get_lm_config
from repro.launch.steps import get_adapter
from repro.models import unet as U
from repro.runtime.device import configure_compile_cache, device_info
from repro.serving import (
    EngineDriver,
    GenRequest,
    HTTPFrontend,
    QualityPolicy,
    RequestFactory,
    default_pas_plan as _serving_default_pas_plan,
    serve_static,
)
from repro.serving import config as CFG


# ---------------------------------------------------------------------------
# Request plumbing (lm mode; diffusion uses repro.serving.GenRequest)
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class Request:
    rid: int
    payload: Any  # token prompt
    submitted: float = dataclasses.field(default_factory=time.perf_counter)
    completed: float | None = None
    result: Any = None

    @property
    def latency(self) -> float:
        return (self.completed or time.perf_counter()) - self.submitted


def pack_batches(reqs: list[Request], batch: int) -> list[list[Request]]:
    """Fixed-size batches; the tail batch is padded by repeating the last
    request (results for pad lanes are dropped)."""
    out = []
    for i in range(0, len(reqs), batch):
        out.append(reqs[i : i + batch])
    return out


# ---------------------------------------------------------------------------
# Diffusion serving
# ---------------------------------------------------------------------------


#: the CLI's stock phase-aware plan now lives with the quality policy
#: (``repro.serving.policy``) so the HTTP request factory and this CLI
#: build identical plans; re-exported here for callers of the old name
default_pas_plan = _serving_default_pas_plan


def build_quality_policy(args, ucfg, dcfg, cfg) -> QualityPolicy:
    """The process-wide quality resolver: engine geometry + optional
    shift-score calibration profile (``--profile``, as emitted by
    ``examples/pas_calibration.py --profile-out``).

    ``cfg`` is the :class:`~repro.serving.EngineConfig`; the ``args``
    parameter is legacy (the profile path now rides on the config) and is
    only consulted when ``cfg.profile`` is unset.
    """
    if not cfg.profile and getattr(args, "profile", None):
        cfg = dataclasses.replace(cfg, profile=args.profile)
    return CFG.build_policy(cfg, ucfg, dcfg)


def make_diffusion_requests(args, ucfg, policy: QualityPolicy | None = None) -> list[GenRequest]:
    """Synthetic request stream: per-request prompt embeddings and noise.

    With ``--quality`` (and a ``policy``) every request resolves its plan +
    cache thresholds through the quality policy; otherwise the legacy
    ``--pas`` switch picks the stock plan and the engine threshold applies.
    """
    n_up = U.n_up_steps(ucfg)
    L = ucfg.latent_size**2
    quality = getattr(args, "quality", None)
    reqs = []
    for i in range(args.requests):
        rng = np.random.default_rng(args.seed * 100_003 + i)
        if policy is not None:
            pol = policy.resolve(args.timesteps, quality=quality, pas=args.pas)
            plan, pol_obj = pol.plan, pol
        else:
            plan, pol_obj = (
                default_pas_plan(args.timesteps, n_up) if args.pas else None,
                None,
            )
        reqs.append(
            GenRequest(
                rid=i,
                ctx=rng.normal(size=(ucfg.ctx_len, ucfg.ctx_dim)).astype(np.float32),
                noise=rng.normal(size=(L, ucfg.in_channels)).astype(np.float32),
                timesteps=args.timesteps,
                plan=plan,
                policy=pol_obj,
            )
        )
    return reqs


def _init_diffusion_models(args, *, decode_images: bool = True):
    """Deprecated argparse-coupled shim.

    Model construction lives on the typed config path now:
    ``repro.serving.config.init_models(from_args(args))``.  Kept (one
    release) so external callers of the old name keep working.
    """
    warnings.warn(
        "_init_diffusion_models(args) is deprecated and will be removed; "
        "build an EngineConfig with repro.serving.config.from_args(args) and "
        "pass it to repro.serving.config.init_models(cfg)",
        DeprecationWarning,
        stacklevel=2,
    )
    return CFG.init_models(CFG.from_args(args, decode_images=decode_images))


def build_continuous_engine(args, *, decode_images: bool = True):
    """Deprecated argparse-coupled shim over the typed construction path.

    Use ``repro.serving.config``::

        cfg = config.from_args(args, decode_images=...)
        bundle = config.build_engine(cfg)

    Returns ``(engine, ucfg, dcfg, cfg)`` exactly as before.
    """
    warnings.warn(
        "build_continuous_engine(args) is deprecated and will be removed; "
        "build an EngineConfig with repro.serving.config.from_args(args) and "
        "pass it to repro.serving.config.build_engine(cfg)",
        DeprecationWarning,
        stacklevel=2,
    )
    bundle = CFG.build_engine(CFG.from_args(args, decode_images=decode_images))
    return bundle.engine, bundle.ucfg, bundle.dcfg, bundle.config


def serve_diffusion(args) -> dict:
    engine_kind = getattr(args, "engine", "continuous")
    n_shards = getattr(args, "shards", 1)
    if engine_kind == "static":
        if getattr(args, "cache", "off") != "off":
            raise SystemExit(
                "--cache requires the continuous engine (lockstep batches have "
                "no per-lane micro-steps to demote); drop --engine static or --cache"
            )
        if getattr(args, "profile", None):
            raise SystemExit(
                "--profile requires the continuous engine (calibrated thresholds "
                "drive the feature cache, which lockstep batches don't have); "
                "drop --engine static or --profile"
            )
        if n_shards > 1:
            raise SystemExit(
                "--shards requires the continuous engine (lockstep batches have "
                "no lane axis to shard); drop --engine static or --shards"
            )
        if getattr(args, "kernels", "xla") != "xla":
            raise SystemExit(
                "--kernels pallas requires the continuous engine (the lockstep "
                "baseline is the XLA reference); drop --engine static or --kernels"
            )
        cfg = CFG.from_args(args)
        ucfg, dcfg, params, vae_params = CFG.init_models(cfg)
        n_up = U.n_up_steps(ucfg)
        policy = QualityPolicy(n_up)
        quality = getattr(args, "quality", None)
        reqs = make_diffusion_requests(args, ucfg, policy)
        # lockstep batches share one plan per step count; resolve it through
        # the same policy the continuous engine uses
        plan_fn = lambda t: policy.resolve(t, quality=quality, pas=args.pas).plan
        done, summary = serve_static(
            ucfg, dcfg, params, vae_params, reqs, args.batch, plan_fn=plan_fn
        )
    else:
        bundle = CFG.build_engine(CFG.from_args(args))
        reqs = make_diffusion_requests(args, bundle.ucfg, bundle.policy)
        done, summary = bundle.engine.run(reqs)

    assert sorted(r.rid for r in done) == list(range(args.requests))
    return dict(
        summary,
        mode="diffusion",
        engine=engine_kind,
        pas=bool(args.pas),
        image_shape=tuple(done[0].image.shape),
    )


# ---------------------------------------------------------------------------
# HTTP serving: the async frontend over the engine driver
# ---------------------------------------------------------------------------


def _parse_hostport(value: str) -> tuple[str, int]:
    host, _, port = value.rpartition(":")
    try:
        return host or "127.0.0.1", int(port)
    except ValueError:
        raise SystemExit(f"--http wants HOST:PORT (PORT 0 = ephemeral), got {value!r}")


def serve_http(args) -> None:
    """Run the async HTTP frontend until a graceful drain completes."""
    if getattr(args, "engine", "continuous") == "static":
        raise SystemExit(
            "--http requires the continuous engine (the lockstep baseline has "
            "no event loop to drive asynchronously); drop --engine static"
        )
    host, port = _parse_hostport(args.http)
    cfg = CFG.from_args(args, decode_images=False)
    bundle = CFG.build_engine(cfg)
    driver = EngineDriver(bundle.engine, max_inflight=cfg.max_inflight)
    factory = RequestFactory(
        bundle.ucfg, bundle.dcfg, cfg,
        policy=bundle.policy,
        default_quality=cfg.quality,
    )

    async def amain() -> dict:
        driver.start()
        frontend = HTTPFrontend(driver, factory, host, port)
        await frontend.start()
        print(f"[serve] http listening on {frontend.host}:{frontend.port}", flush=True)
        if args.port_file:
            tmp = args.port_file + ".tmp"
            with open(tmp, "w") as f:
                f.write(str(frontend.port))
            os.replace(tmp, args.port_file)  # atomic: clients never see a partial write
        loop = asyncio.get_running_loop()
        for sig in (signal.SIGINT, signal.SIGTERM):
            loop.add_signal_handler(sig, frontend.request_shutdown)
        return await frontend.serve_until_shutdown()

    summary = asyncio.run(amain())
    print(f"[serve] drained {summary}")
    if not summary.get("drained", False):
        raise SystemExit("server stopped without a clean drain")


# ---------------------------------------------------------------------------
# LM serving: batched prefill + greedy decode
# ---------------------------------------------------------------------------


def serve_lm(args) -> dict:
    cfg = get_lm_config(args.arch, "smoke")
    adapter = get_adapter(cfg)
    params = adapter.init(jax.random.key(args.seed))

    b = args.batch
    prompt_len = args.prompt_len
    max_len = prompt_len + args.gen_len

    @jax.jit
    def prefill(params, tokens):
        logits, _ = adapter.forward(params, tokens)
        return jnp.argmax(logits[:, -1, ...], axis=-1)

    decode = jax.jit(adapter.decode)

    rng = np.random.default_rng(args.seed)
    reqs = [
        Request(rid=i, payload=rng.integers(0, cfg.vocab_size, size=(prompt_len,)).astype(np.int32))
        for i in range(args.requests)
    ]

    done: list[Request] = []
    t_start = time.perf_counter()
    for group in pack_batches(reqs, b):
        toks = np.stack([g.payload for g in group] + [group[-1].payload] * (b - len(group)))
        toks = jnp.asarray(toks)
        nxt = prefill(params, toks)
        if nxt.ndim > 1:  # multi-codebook heads: greedy over codebook 0
            nxt = nxt[..., 0]
        cache = adapter.init_cache(b, max_len)
        # warm the cache with the prompt (teacher-forced decode steps)
        for pos in range(prompt_len):
            _, cache = decode(params, cache, toks[:, pos], jnp.asarray(pos, jnp.int32))
        outs = [nxt]
        for i in range(args.gen_len - 1):
            logits, cache = decode(params, cache, nxt.astype(jnp.int32), jnp.asarray(prompt_len + i, jnp.int32))
            nxt = jnp.argmax(logits, axis=-1)
            if nxt.ndim > 1:
                nxt = nxt[..., 0]
            outs.append(nxt)
        gen = np.stack([np.asarray(o) for o in outs], axis=1)
        now = time.perf_counter()
        for lane, g in enumerate(group):
            g.result = gen[lane]
            g.completed = now
            done.append(g)
    wall = time.perf_counter() - t_start

    lat = [r.latency for r in done]
    total_tokens = len(done) * args.gen_len
    return {
        "mode": "lm",
        "arch": args.arch,
        "requests": len(done),
        "wall_s": round(wall, 3),
        "tok_s": round(total_tokens / wall, 1),
        "p50_latency_s": round(float(np.percentile(lat, 50)), 3),
        "gen_shape": tuple(done[0].result.shape),
    }


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--mode", choices=["diffusion", "lm"], default="diffusion")
    ap.add_argument("--unet", default="sd_toy")
    ap.add_argument("--arch", choices=ARCH_IDS, default="gemma3-1b")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--batch", type=int, default=4, help="lanes (continuous) / batch (static)")
    ap.add_argument("--timesteps", type=int, default=20)
    ap.add_argument("--pas", action="store_true", help="serve with phase-aware sampling")
    ap.add_argument(
        "--quality", default=None, metavar="TIER|Q",
        help="per-request quality knob resolved by repro.serving.policy: a "
        "named tier (draft|balanced|high|exact) or a number in [0,1]. "
        "Decides the PAS plan shape AND the cache threshold per request "
        "(exact = all-FULL + threshold 0 = bit-exact). With --http this is "
        "the default for payloads carrying no 'quality' field.",
    )
    ap.add_argument(
        "--profile", default=None, metavar="PATH",
        help="shift-score calibration profile (.npz from examples/"
        "pas_calibration.py --profile-out); refines quality-tier cache "
        "thresholds into per-timestep-bucket thresholds",
    )
    ap.add_argument(
        "--engine",
        choices=["continuous", "static"],
        default="continuous",
        help="step-level continuous batching vs fixed-size lockstep batches",
    )
    ap.add_argument("--window", type=int, default=4, help="plan-aware admission window")
    ap.add_argument(
        "--kernels",
        choices=["xla", "pallas"],
        default="xla",
        help="kernel backend for the served hot path: xla = inline reference "
        "ops (bit-exact with pre-backend builds), pallas = the Pallas "
        "kernels (Uni-conv, fused GroupNorm+SiLU, flash attention; "
        "interpret mode off-TPU). Engine-wide — requests may only echo it",
    )
    ap.add_argument(
        "--shards", type=int, default=1,
        help="lane shards over a device mesh (continuous engine only; needs "
        ">= N visible devices — on CPU set "
        "XLA_FLAGS=--xla_force_host_platform_device_count=N)",
    )
    ap.add_argument(
        "--cache",
        choices=["off", "intra", "cross"],
        default="off",
        help="feature cache: intra = a request reuses its own captures "
        "(DeepCache-style), cross = requests reuse each other's (continuous "
        "engine only)",
    )
    ap.add_argument(
        "--cache-threshold", type=float, default=0.15,
        help="prompt-signature shift-score bound for a cache hit (0 = never "
        "hit; larger = more reuse, lower fidelity)",
    )
    ap.add_argument("--cache-slots", type=int, default=16, help="feature-cache ring size")
    ap.add_argument(
        "--cache-bucket", type=int, default=125,
        help="timestep bucket width (train-timestep units) for cache keys",
    )
    ap.add_argument(
        "--cache-spill-mb", type=float, default=0.0,
        help="host-RAM spill tier byte budget in MiB (0 = off): HBM-ring "
        "evictions demote into a pinned host ring and admission prefetches "
        "spill-resident slots back onto the device before their first "
        "planned FULL step",
    )
    ap.add_argument(
        "--cache-gossip", dest="cache_gossip", action="store_true", default=True,
        help="route admissions to the cache-warm shard via the scheduler's "
        "fleet-wide warmth map (sharded engine; default on)",
    )
    ap.add_argument(
        "--no-cache-gossip", dest="cache_gossip", action="store_false",
        help="disable warm-shard admission routing (emptiest-shard only)",
    )
    ap.add_argument(
        "--http", metavar="HOST:PORT", default=None,
        help="serve the continuous engine over an asyncio HTTP frontend "
        "(PORT 0 = ephemeral) instead of running a synthetic batch; "
        "drains gracefully on SIGINT/SIGTERM or POST /shutdown",
    )
    ap.add_argument(
        "--port-file", default=None, metavar="PATH",
        help="write the bound HTTP port here (atomically) once listening",
    )
    ap.add_argument(
        "--max-inflight", type=int, default=32,
        help="bounded admission depth of the HTTP frontend (429 beyond it)",
    )
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--gen-len", type=int, default=16)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    cache_dir = configure_compile_cache()
    print(f"[serve] device {device_info()} compile cache {cache_dir}", flush=True)
    if args.http is not None:
        if args.mode != "diffusion":
            raise SystemExit("--http currently serves --mode diffusion only")
        serve_http(args)
        return
    stats = serve_diffusion(args) if args.mode == "diffusion" else serve_lm(args)
    print(f"[serve] {stats}")


if __name__ == "__main__":
    main()
