"""Serving throughput: static lockstep vs continuous vs cache-aware serving.

Open-loop Poisson arrivals of text-conditioned generation requests with
heterogeneous step counts, served on the toy U-Net by (a) the seed-style
fixed-size lockstep batcher and (b) the step-level continuous-batching
engine at equal lane width.  Both paths are compile-warmed before any
timed run, so the comparison measures steady-state serving, not jit.

Static batching wastes lanes two ways the engine reclaims: pad lanes in
partially filled batches (arrival gaps) and lockstep overshoot (every
member runs the batch max step count).  The headline acceptance row
reports the continuous/static throughput speedup at the arrival rates
where static batching leaves >= 25% of its lane-steps idle.

``--cache cross`` additionally runs the cache-aware engine on the same
stream (mixed PAS/full plans, prompts drawn from a small pool of popular
base prompts with per-request jitter — the workload shape where requests
actually share features) and reports the cache hit rate, the FULL U-Net
step reduction vs the cache-off continuous baseline, and the throughputs.

``--shards N`` additionally runs the mesh-sharded engine on the same
stream at the same *total* lane count (the ``--lanes`` budget split over N
device shards, one jitted GSPMD micro-step, per-shard branch votes) and
reports the sharded/single-device throughput speedup, per-shard lane
occupancy balance and — with ``--cache`` — per-shard hit rates.  Needs N
visible devices: on CPU run under
``XLA_FLAGS=--xla_force_host_platform_device_count=8``.

``--kernels pallas`` switches to the Pallas-backend trajectory: a small
closed-loop stream served by a ``backend="pallas"`` engine (interpret mode
off-TPU — a correctness/viability line, not a speed line) and parity-checked
against the xla engine on identical requests.  Its gates are stability
ratios (completion, xla agreement within the differential tolerance), so
the line stays machine-portable even though interpreted kernels are slow.

``--json PATH`` writes the machine-readable benchmark trajectory
(`BENCH_serving.json`, or `BENCH_serving_pallas.json` under ``--kernels
pallas``): headline throughput/latency numbers plus the machine-portable
ratio gates the CI benchmark job compares against the checked-in baseline
(see ``tools/compare_bench.py``).

Usage:
  PYTHONPATH=src:. python benchmarks/bench_serving.py            # full sweep
  PYTHONPATH=src:. python benchmarks/bench_serving.py --smoke    # CI-sized
  PYTHONPATH=src:. python benchmarks/bench_serving.py --pas      # + PAS plans
  PYTHONPATH=src:. python benchmarks/bench_serving.py --cache cross  # + cache
  XLA_FLAGS=--xla_force_host_platform_device_count=8 \
    PYTHONPATH=src:. python benchmarks/bench_serving.py --shards 4 --lanes 8
"""
from __future__ import annotations

import argparse
import json

import jax
import numpy as np

from benchmarks.common import emit
from repro.common.types import DiffusionConfig, PASPlan
from repro.configs import get_unet_config
from repro.models import unet as U
from repro.serving import (
    CacheAwareScheduler,
    DiffusionEngine,
    EngineConfig,
    GenRequest,
    PlanAwareScheduler,
    ShardedDiffusionEngine,
    StaticServer,
)


def pas_plan_for(timesteps: int, n_up: int) -> PASPlan:
    return PASPlan(
        t_sketch=max(2, timesteps // 2),
        t_complete=max(1, timesteps // 4),
        t_sparse=2,
        l_sketch=min(3, n_up),
        l_refine=min(2, n_up),
    )


def make_stream(
    ucfg,
    n_requests: int,
    rate_req_s: float,
    t_lo: int,
    t_hi: int,
    pas: bool,
    seed: int,
    *,
    mixed: bool = False,
    prompt_pool: int = 0,
    prompt_jitter: float = 0.0,
) -> list[GenRequest]:
    """Poisson arrivals, step counts uniform in [t_lo, t_hi].

    ``mixed`` alternates PAS and all-FULL plans per request (the cache
    bench's workload).  ``prompt_pool > 0`` draws each prompt as one of
    ``prompt_pool`` shared base embeddings plus ``prompt_jitter`` noise —
    the "popular prompt" regime where cross-request feature reuse exists.
    """
    n_up = U.n_up_steps(ucfg)
    L = ucfg.latent_size**2
    rng = np.random.default_rng(seed)
    arrivals = np.cumsum(rng.exponential(1.0 / rate_req_s, size=n_requests))
    base = (
        rng.normal(size=(prompt_pool, ucfg.ctx_len, ucfg.ctx_dim)).astype(np.float32) * 0.2
        if prompt_pool > 0
        else None
    )
    reqs = []
    for i in range(n_requests):
        t = int(rng.integers(t_lo, t_hi + 1))
        if base is not None:
            ctx = base[int(rng.integers(prompt_pool))] + prompt_jitter * rng.normal(
                size=(ucfg.ctx_len, ucfg.ctx_dim)
            ).astype(np.float32)
        else:
            ctx = rng.normal(size=(ucfg.ctx_len, ucfg.ctx_dim)).astype(np.float32) * 0.2
        use_pas = (i % 2 == 0) if mixed else pas
        reqs.append(
            GenRequest(
                rid=i,
                ctx=ctx,
                noise=rng.normal(size=(L, ucfg.in_channels)).astype(np.float32),
                timesteps=t,
                plan=pas_plan_for(t, n_up) if use_pas else None,
                arrival_s=float(arrivals[i]),
            )
        )
    return reqs


def bench_rate(engine, static, ucfg, args, rate, pas) -> dict:
    reqs = make_stream(ucfg, args.requests, rate, args.t_lo, args.t_hi, pas, args.seed)
    tag = f"pas={int(pas)}/rate={rate:g}"
    _, s_static = static.run(reqs, realtime=True)
    _, s_cont = engine.run(reqs, realtime=True)
    speedup = s_cont["throughput_req_s"] / max(s_static["throughput_req_s"], 1e-9)
    for mode, s in (("static", s_static), ("continuous", s_cont)):
        emit("serving", f"{tag}/{mode}/throughput_req_s", s["throughput_req_s"], "req/s")
        emit("serving", f"{tag}/{mode}/p50_latency_s", s["p50_latency_s"], "s")
        emit("serving", f"{tag}/{mode}/p99_latency_s", s["p99_latency_s"], "s")
    emit("serving", f"{tag}/static/idle_lane_frac", s_static["idle_lane_frac"], "")
    emit("serving", f"{tag}/continuous/mean_occupancy", s_cont["mean_occupancy"], "")
    emit("serving", f"{tag}/speedup", round(speedup, 3), "x", "continuous vs static")
    return {
        "rate": rate,
        "pas": pas,
        "speedup": speedup,
        "idle_lane_frac": s_static["idle_lane_frac"],
        "continuous_throughput_req_s": s_cont["throughput_req_s"],
        "continuous_p50_latency_s": s_cont["p50_latency_s"],
        "continuous_p99_latency_s": s_cont["p99_latency_s"],
    }


def bench_cache(engine_off, engine_on, ucfg, args, rate) -> dict:
    """Cache-off vs cache-on continuous serving on one mixed-plan stream."""
    reqs = make_stream(
        ucfg, args.requests, rate, args.t_lo, args.t_hi, False, args.seed,
        mixed=True, prompt_pool=args.prompt_pool, prompt_jitter=args.prompt_jitter,
    )
    tag = f"cache={args.cache}/rate={rate:g}"
    _, s_off = engine_off.run(reqs, realtime=True)
    _, s_on = engine_on.run(reqs, realtime=True)
    full_red = 1.0 - s_on["full_steps"] / max(s_off["full_steps"], 1)
    speedup = s_on["throughput_req_s"] / max(s_off["throughput_req_s"], 1e-9)
    emit("serving", f"{tag}/off/full_steps", s_off["full_steps"], "steps")
    emit("serving", f"{tag}/on/full_steps", s_on["full_steps"], "steps")
    emit("serving", f"{tag}/on/demoted_full_steps", s_on["demoted_full_steps"], "steps")
    emit("serving", f"{tag}/on/hit_rate", s_on["cache_hit_rate"], "")
    emit("serving", f"{tag}/full_step_reduction", round(full_red, 3), "")
    emit("serving", f"{tag}/off/throughput_req_s", s_off["throughput_req_s"], "req/s")
    emit("serving", f"{tag}/on/throughput_req_s", s_on["throughput_req_s"], "req/s")
    emit("serving", f"{tag}/throughput_speedup", round(speedup, 3), "x", "cache on vs off")
    return {
        "rate": rate,
        "hit_rate": s_on["cache_hit_rate"],
        "full_step_reduction": full_red,
        "speedup": speedup,
    }


def bench_sharded(engine_1, engine_n, engine_n_cache, ucfg, args, rate) -> dict:
    """Single-device vs mesh-sharded continuous serving, same total lanes,
    same mixed-plan stream.

    The headline speedup compares cache-off against cache-off (pure
    sharding win: per-shard branch votes + device parallelism).  When the
    cache-armed sharded engine is supplied, the same stream also measures
    shard-local reuse: per-shard hit rates and the FULL-step reduction vs
    the cache-off sharded run.
    """
    reqs = make_stream(
        ucfg, args.requests, rate, args.t_lo, args.t_hi, False, args.seed,
        mixed=True, prompt_pool=args.prompt_pool, prompt_jitter=args.prompt_jitter,
    )
    tag = f"shards={args.shards}/rate={rate:g}"
    _, s_1 = engine_1.run(reqs, realtime=True)
    _, s_n = engine_n.run(reqs, realtime=True)
    speedup = s_n["throughput_req_s"] / max(s_1["throughput_req_s"], 1e-9)
    for mode, s in (("single", s_1), ("sharded", s_n)):
        emit("serving", f"{tag}/{mode}/throughput_req_s", s["throughput_req_s"], "req/s")
        emit("serving", f"{tag}/{mode}/p50_latency_s", s["p50_latency_s"], "s")
        emit("serving", f"{tag}/{mode}/p99_latency_s", s["p99_latency_s"], "s")
        emit("serving", f"{tag}/{mode}/mean_advance_eff", s["mean_advance_eff"], "")
    emit(
        "serving", f"{tag}/sharded/occupancy_balance",
        s_n.get("shard_occupancy_balance", 0.0), "", "min/max shard occupancy",
    )
    emit("serving", f"{tag}/speedup", round(speedup, 3), "x", "sharded vs single device")
    row = {
        "rate": rate,
        "speedup": speedup,
        "single_throughput_req_s": s_1["throughput_req_s"],
        "sharded_throughput_req_s": s_n["throughput_req_s"],
        "sharded_p50_latency_s": s_n["p50_latency_s"],
        "sharded_p99_latency_s": s_n["p99_latency_s"],
        "shard_occupancy_balance": s_n.get("shard_occupancy_balance", 0.0),
        "shard_mean_active": s_n.get("shard_mean_active", []),
    }
    if engine_n_cache is not None:
        _, s_c = engine_n_cache.run(reqs, realtime=True)
        full_red = 1.0 - s_c["full_steps"] / max(s_n["full_steps"], 1)
        row["shard_hit_rates"] = s_c.get("shard_hit_rates", [])
        row["cache_hit_rate"] = s_c["cache_hit_rate"]
        row["cache_full_step_reduction"] = full_red
        emit("serving", f"{tag}/sharded-cache/hit_rate", s_c["cache_hit_rate"], "")
        emit(
            "serving", f"{tag}/sharded-cache/shard_hit_rates",
            s_c.get("shard_hit_rates", []), "",
        )
        emit("serving", f"{tag}/sharded-cache/full_step_reduction", round(full_red, 3), "")
    return row


#: documented pallas-vs-xla tolerance (see tests/test_serving_differential.py)
PALLAS_ATOL = 5e-4


def mean_tick_s(summary: dict) -> float:
    """Mean host seconds of one ``engine.step`` call (the ``engine.tick``
    span in the serving summary)."""
    tick = summary["spans"]["engine.tick"]
    return round(tick["s"] / max(tick["n"], 1), 6)


def bench_pallas(args) -> None:
    """Pallas-backend trajectory: a small closed-loop stream served by a
    ``backend="pallas"`` engine, parity-checked against the xla engine on
    identical requests.

    Off-TPU the Pallas kernels run in interpret mode — orders of magnitude
    slower than compiled XLA — so this line gates on *stability* ratios
    (completion, xla agreement within ``PALLAS_ATOL``) rather than speed.
    Absolute per-step times ride along under ``headline`` so the trajectory
    still shows the interpret/compiled gap (and, on TPU, the real one).
    """
    n_req, lanes, t_lo, t_hi = (4, 2, 3, 5) if args.smoke else (6, 2, 3, 6)
    ucfg = get_unet_config("sd_toy")
    n_up = U.n_up_steps(ucfg)
    dcfg = DiffusionConfig(timesteps_sample=t_hi)
    params = U.init_unet(jax.random.key(args.seed), ucfg)
    # closed loop (rate=1e9 => everything queued up front): wall time is pure
    # serving time, and both backends see the identical request sequence
    reqs = make_stream(ucfg, n_req, 1e9, t_lo, t_hi, False, args.seed, mixed=True)

    def build(backend: str) -> DiffusionEngine:
        cfg = EngineConfig(
            n_lanes=lanes, max_steps=t_hi, l_sketch=min(3, n_up),
            l_refine=min(2, n_up), decode_images=False, backend=backend,
        )
        return DiffusionEngine(
            ucfg, dcfg, params, None, cfg, scheduler=PlanAwareScheduler(window=4)
        )

    lat: dict[str, dict] = {}
    summaries: dict[str, dict] = {}
    for backend in ("xla", "pallas"):
        done, s = build(backend).run(reqs, realtime=False)
        lat[backend] = {d.rid: d.latent for d in done}
        summaries[backend] = s
        emit("serving", f"kernels={backend}/completed", len(done), "req")
        emit("serving", f"kernels={backend}/mean_step_s", mean_tick_s(s), "s")
        emit("serving", f"kernels={backend}/throughput_req_s", s["throughput_req_s"], "req/s")

    completed = len(lat["pallas"])
    max_diff = (
        max(
            float(np.max(np.abs(lat["pallas"][rid] - lat["xla"][rid])))
            for rid in lat["xla"]
            if rid in lat["pallas"]
        )
        if completed
        else float("inf")
    )
    agreement = 1.0 if (completed == n_req and max_diff <= PALLAS_ATOL) else 0.0
    emit(
        "serving", "kernels=pallas/max_abs_diff_vs_xla", round(max_diff, 8), "",
        f"tolerance {PALLAS_ATOL:g}",
    )
    emit(
        "serving", "acceptance/pallas_xla_agreement", agreement, "",
        "1.0 = every request completed within tolerance of the xla engine",
    )

    if args.json:
        out = {
            "bench": "serving_pallas",
            "config": {
                "requests": n_req, "lanes": lanes, "t_lo": t_lo, "t_hi": t_hi,
                "seed": args.seed, "atol": PALLAS_ATOL,
            },
            "gates": {
                "pallas_completed_ratio": round(completed / n_req, 3),
                "pallas_xla_agreement": agreement,
            },
            "headline": {
                "pallas_max_abs_diff_vs_xla": max_diff,
                "pallas_mean_step_s": mean_tick_s(summaries["pallas"]),
                "xla_mean_step_s": mean_tick_s(summaries["xla"]),
                "pallas_throughput_req_s": summaries["pallas"]["throughput_req_s"],
            },
        }
        with open(args.json, "w") as f:
            json.dump(out, f, indent=2, sort_keys=True)
        emit("serving", "trajectory_json", args.json, "", "written")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--requests", type=int, default=42)
    ap.add_argument("--lanes", type=int, default=4)
    ap.add_argument("--t-lo", type=int, default=4)
    ap.add_argument("--t-hi", type=int, default=16)
    ap.add_argument(
        "--rates", type=float, nargs="+", default=None,
        help="Poisson arrival rates in req/s (default: calibrated to the machine)",
    )
    ap.add_argument("--pas", action="store_true", help="also sweep phase-aware plans")
    ap.add_argument(
        "--cache", choices=["off", "intra", "cross"], default="off",
        help="also bench the feature cache (mixed-plan pooled-prompt stream)",
    )
    ap.add_argument("--cache-threshold", type=float, default=0.3)
    ap.add_argument("--cache-slots", type=int, default=24)
    ap.add_argument("--cache-bucket", type=int, default=125)
    ap.add_argument(
        "--prompt-pool", type=int, default=4,
        help="number of shared base prompts in the cache workload",
    )
    ap.add_argument("--prompt-jitter", type=float, default=0.02)
    ap.add_argument(
        "--shards", type=int, default=1,
        help="also bench the mesh-sharded engine: --lanes total lanes split "
        "over this many device shards (needs that many visible devices; on "
        "CPU set XLA_FLAGS=--xla_force_host_platform_device_count=8)",
    )
    ap.add_argument(
        "--kernels", choices=["xla", "pallas"], default="xla",
        help="kernel backend; pallas runs the dedicated small parity/"
        "stability trajectory instead of the throughput sweep (interpret "
        "mode is orders of magnitude slower than compiled XLA on CPU)",
    )
    ap.add_argument(
        "--json", type=str, default=None, metavar="PATH",
        help="write the benchmark-trajectory JSON (BENCH_serving.json)",
    )
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--smoke", action="store_true", help="tiny CI-sized run")
    args = ap.parse_args()

    if args.kernels == "pallas":
        bench_pallas(args)
        return

    if args.smoke:
        args.requests, args.lanes, args.t_lo, args.t_hi = 6, 2, 3, 5
        if args.shards > 1:
            args.lanes = max(args.lanes, args.shards)
    if args.shards > 1 and args.lanes % args.shards:
        raise SystemExit(f"--lanes {args.lanes} must divide over --shards {args.shards}")

    ucfg = get_unet_config("sd_toy")
    n_up = U.n_up_steps(ucfg)
    dcfg = DiffusionConfig(timesteps_sample=args.t_hi)
    params = U.init_unet(jax.random.key(args.seed), ucfg)

    cfg = EngineConfig(
        n_lanes=args.lanes,
        max_steps=args.t_hi,
        l_sketch=min(3, n_up),
        l_refine=min(2, n_up),
        decode_images=False,
    )
    engine = DiffusionEngine(
        ucfg, dcfg, params, None, cfg, scheduler=PlanAwareScheduler(window=4)
    )

    results = []
    pas_modes = (False, True) if args.pas else (False,)
    for pas in pas_modes:
        plan_fn = (lambda t: pas_plan_for(t, n_up)) if pas else (lambda t: None)
        static = StaticServer(
            ucfg, dcfg, params, None, args.lanes, plan_fn=plan_fn, decode_images=False
        )
        static.warmup(range(args.t_lo, args.t_hi + 1))
        warm = make_stream(ucfg, 2 * args.lanes, 1e9, args.t_lo, args.t_hi, pas, 7)
        engine.run(warm, realtime=False)  # compile micro-step + admission

        rates = args.rates
        if rates is None:
            # place rates around the static baseline's *measured* capacity:
            # the stream's step counts are rate-independent (same seed), so
            # its exact FIFO lockstep step total is computable up front.
            step_s = static.time_step_s(args.t_hi)
            probe = make_stream(ucfg, args.requests, 1.0, args.t_lo, args.t_hi, pas, args.seed)
            t_seq = [r.timesteps for r in probe]
            lockstep = sum(
                max(t_seq[i : i + args.lanes]) for i in range(0, len(t_seq), args.lanes)
            )
            static_cap = args.requests / (lockstep * step_s)
            rates = [round(static_cap * f, 4) for f in (0.9, 1.4, 2.2)]
            emit("serving", f"pas={int(pas)}/static_step_s", round(step_s, 4), "s")
            emit("serving", f"pas={int(pas)}/static_capacity_req_s", round(static_cap, 3), "req/s")
        for rate in rates:
            results.append(bench_rate(engine, static, ucfg, args, rate, pas))

    gate = [r for r in results if r["idle_lane_frac"] >= 0.25]
    if gate:
        best = max(gate, key=lambda r: r["speedup"])
        emit(
            "serving", "acceptance/speedup_at_idle>=0.25", round(best["speedup"], 3), "x",
            f"idle={best['idle_lane_frac']}",
        )

    cache_results: list[dict] = []
    sharded_results: list[dict] = []
    sharded_capacity: dict = {}
    if args.cache != "off":
        engine_off = engine  # the already-warmed cache-off continuous engine
        cache_cfg = EngineConfig(
            n_lanes=args.lanes,
            max_steps=args.t_hi,
            l_sketch=min(3, n_up),
            l_refine=min(2, n_up),
            decode_images=False,
            cache_mode=args.cache,
            cache_slots=args.cache_slots,
            cache_threshold=args.cache_threshold,
            cache_t_bucket=args.cache_bucket,
        )
        engine_on = DiffusionEngine(
            ucfg, dcfg, params, None, cache_cfg, scheduler=CacheAwareScheduler(window=4)
        )
        warm = make_stream(
            ucfg, 2 * args.lanes, 1e9, args.t_lo, args.t_hi, False, 7,
            mixed=True, prompt_pool=args.prompt_pool, prompt_jitter=args.prompt_jitter,
        )
        engine_on.run(warm)  # compile the cached micro-step + insert scatter
        # default: the two mid/high calibrated rates — the saturation region
        # where FULL-step savings translate into throughput
        cache_rates = args.rates if args.rates is not None else sorted(
            {r["rate"] for r in results}
        )[-2:]
        cache_results = [
            bench_cache(engine_off, engine_on, ucfg, args, rate) for rate in cache_rates
        ]
        best = max(cache_results, key=lambda r: r["full_step_reduction"])
        emit(
            "serving", "acceptance/cache_hit_rate", round(best["hit_rate"], 3), "",
            f"mode={args.cache}",
        )
        emit(
            "serving",
            "acceptance/cache_full_step_reduction",
            round(best["full_step_reduction"], 3),
            "",
            f"target>=0.10 mode={args.cache} threshold={args.cache_threshold}",
        )

    if args.shards > 1:
        def sharded_cfg(cache: bool) -> EngineConfig:
            return EngineConfig(
                n_lanes=args.lanes,
                max_steps=args.t_hi,
                l_sketch=min(3, n_up),
                l_refine=min(2, n_up),
                decode_images=False,
                n_shards=args.shards,
                cache_mode=args.cache if cache else "off",
                cache_slots=args.cache_slots,
                cache_threshold=args.cache_threshold,
                cache_t_bucket=args.cache_bucket,
                # this bench pins the SHARD-LOCAL baseline (emptiest-shard
                # admission, no spill); bench_cache_tier.py measures what
                # gossip + the host-RAM spill ring buy on the same stream
                cache_gossip=False,
            )

        engine_sh = ShardedDiffusionEngine(
            ucfg, dcfg, params, None, sharded_cfg(False),
            scheduler=PlanAwareScheduler(window=4),
        )
        engine_sh_cache = None
        if args.cache != "off":
            engine_sh_cache = ShardedDiffusionEngine(
                ucfg, dcfg, params, None, sharded_cfg(True),
                scheduler=CacheAwareScheduler(window=4),
            )
        warm = make_stream(
            ucfg, 2 * args.lanes, 1e9, args.t_lo, args.t_hi, False, 7,
            mixed=True, prompt_pool=args.prompt_pool, prompt_jitter=args.prompt_jitter,
        )
        engine_sh.run(warm)  # compile the GSPMD micro-step + sharded admit
        if engine_sh_cache is not None:
            engine_sh_cache.run(warm)
        # saturation rates: device parallelism only shows once the single-
        # device engine is the bottleneck
        sharded_rates = args.rates if args.rates is not None else sorted(
            {r["rate"] for r in results}
        )[-2:]
        sharded_results = [
            bench_sharded(engine, engine_sh, engine_sh_cache, ucfg, args, rate)
            for rate in sharded_rates
        ]
        # closed-loop capacity: everything queued up front, wall = pure
        # serving time — the arrival-floor-free measure of what the shards
        # actually buy (open-loop speedups above saturate toward this)
        cap_reqs = make_stream(
            ucfg, args.requests, max(sharded_rates), args.t_lo, args.t_hi, False,
            args.seed, mixed=True, prompt_pool=args.prompt_pool,
            prompt_jitter=args.prompt_jitter,
        )
        _, c_1 = engine.run(cap_reqs, realtime=False)
        _, c_n = engine_sh.run(cap_reqs, realtime=False)
        cap_speedup = c_n["throughput_req_s"] / max(c_1["throughput_req_s"], 1e-9)
        sharded_capacity = {
            "single_capacity_req_s": c_1["throughput_req_s"],
            "sharded_capacity_req_s": c_n["throughput_req_s"],
            "capacity_speedup": cap_speedup,
            "single_advance_eff": c_1["mean_advance_eff"],
            "sharded_advance_eff": c_n["mean_advance_eff"],
            "shard_occupancy_balance": c_n.get("shard_occupancy_balance", 0.0),
            "shard_mean_active": c_n.get("shard_mean_active", []),
        }
        emit(
            "serving", f"shards={args.shards}/capacity/single_req_s",
            c_1["throughput_req_s"], "req/s", "closed loop",
        )
        emit(
            "serving", f"shards={args.shards}/capacity/sharded_req_s",
            c_n["throughput_req_s"], "req/s", "closed loop",
        )
        emit(
            "serving", f"acceptance/sharded_capacity_speedup_shards={args.shards}",
            round(cap_speedup, 3), "x",
            f"target>2x lanes={args.lanes} (scales with cores, >= {args.shards} ideal)",
        )
        emit(
            "serving", "acceptance/shard_occupancy_balance",
            round(sharded_capacity["shard_occupancy_balance"], 3), "",
            "1.0 = perfectly balanced",
        )

    if args.json:
        _write_trajectory(args, results, cache_results, sharded_results, sharded_capacity)


def _write_trajectory(
    args,
    results: list[dict],
    cache_results: list[dict],
    sharded_results: list[dict],
    sharded_capacity: dict,
) -> None:
    """Serialize the run into the benchmark-trajectory JSON.

    ``gates`` holds the metrics the CI benchmark job compares against the
    checked-in baseline (``tools/compare_bench.py``).  Gated metrics are
    *ratios* (speedups, reductions, balance) rather than absolute req/s so
    the gate is portable across machines of different speeds; absolute
    numbers ride along under ``headline`` for trend inspection.
    """
    out: dict = {
        "bench": "serving",
        "config": {
            "requests": args.requests,
            "lanes": args.lanes,
            "shards": args.shards,
            "t_lo": args.t_lo,
            "t_hi": args.t_hi,
            "cache": args.cache,
            "cache_threshold": args.cache_threshold,
            "prompt_pool": args.prompt_pool,
            "seed": args.seed,
        },
        "rates": results,
        "cache": cache_results,
        "sharded": sharded_results,
        "sharded_capacity": sharded_capacity,
        "gates": {},
        "headline": {},
    }
    gates = out["gates"]
    headline = out["headline"]
    if results:
        best = max(results, key=lambda r: r["speedup"])
        gates["continuous_vs_static_speedup"] = round(best["speedup"], 3)
    if cache_results:
        best = max(cache_results, key=lambda r: r["full_step_reduction"])
        gates["cache_full_step_reduction"] = round(best["full_step_reduction"], 3)
        headline["cache_hit_rate"] = round(best["hit_rate"], 3)
    if sharded_results:
        best = max(sharded_results, key=lambda r: r["speedup"])
        gates["sharded_vs_single_speedup"] = round(best["speedup"], 3)
        headline["sharded_throughput_req_s"] = best["sharded_throughput_req_s"]
        headline["sharded_p50_latency_s"] = best["sharded_p50_latency_s"]
        headline["sharded_p99_latency_s"] = best["sharded_p99_latency_s"]
        if "shard_hit_rates" in best:
            headline["shard_hit_rates"] = best["shard_hit_rates"]
            # the global-cache-tier trend pair: the fleet-level pooled hit
            # rate on the sharded run, and how unevenly warmth landed
            # across shards (max - min per-shard hit rate; 0 = even)
            headline["pooled_shard_hit_rate"] = round(best.get("cache_hit_rate", 0.0), 3)
            shard_rates = [float(r) for r in best["shard_hit_rates"]]
            headline["shard_warmth_imbalance"] = (
                round(max(shard_rates) - min(shard_rates), 3) if shard_rates else 0.0
            )
    if sharded_capacity:
        gates["sharded_capacity_speedup"] = round(sharded_capacity["capacity_speedup"], 3)
        gates["shard_occupancy_balance"] = round(
            sharded_capacity["shard_occupancy_balance"], 3
        )
        headline["sharded_capacity_req_s"] = sharded_capacity["sharded_capacity_req_s"]
        headline["single_capacity_req_s"] = sharded_capacity["single_capacity_req_s"]
    if results:
        fastest = max(results, key=lambda r: r["continuous_throughput_req_s"])
        headline["continuous_throughput_req_s"] = fastest["continuous_throughput_req_s"]
        headline["continuous_p50_latency_s"] = fastest["continuous_p50_latency_s"]
        headline["continuous_p99_latency_s"] = fastest["continuous_p99_latency_s"]
    with open(args.json, "w") as f:
        json.dump(out, f, indent=2, sort_keys=True)
    emit("serving", "trajectory_json", args.json, "", "written")


if __name__ == "__main__":
    main()
