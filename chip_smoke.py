"""Smoke test of the served diffusion path on TPU.

One chip (the default) — the published-width sd_v14 U-Net (860M
parameters, bf16 weights, 64x64 latent) through the normal entry points:

* **serve**: ``config.build_engine`` -> ``EngineDriver`` -> ``HTTPFrontend``
  on the XLA backend with the cross-request cache and 2 lanes, driven by
  ``FrontendClient`` with exact, balanced, draft, img2img and repeated
  requests, then ``/stats`` and a ``/shutdown`` that must drain clean;
* **compare**: the exact requests' latents against the straight-line
  sampler (``core.sampler.pas_denoise``) at the same weights, and against
  a float32 reference (weights cast to f32, matmuls at "highest");
* **pallas**: the same engine on the Pallas kernels; its compiled
  micro-step must hold ``tpu_custom_call`` and its latents must agree
  with the XLA engine's.

``--chips 4`` runs only the paths that need four chips: the sd_v14 engine
with 4 lane shards against the one-device engine, then 4 router replicas
each pinned to its own chip.  The parent never imports JAX; the sharded
phase runs in a child process that exits before the replicas start.

Every phase checks its results and raises on a mismatch.  The last line of
stdout is ``{"ok": true, "device": {"platform", "kind", "count"}}``; with
no TPU visible the script fails before any phase.

Usage: ``python chip_smoke.py [--chips 4]``
"""
from __future__ import annotations

import argparse
import asyncio
import dataclasses
import json
import multiprocessing
import os
import re
import sys
import time

CHECKOUT = os.path.dirname(os.path.abspath(__file__))
SRC = os.path.join(CHECKOUT, "src")
sys.path.insert(0, SRC)

#: engine vs straight-line sampler (and sharded vs one-device engine):
#: same weights and math, but each program rounds its matmul inputs to
#: bf16 at its own fusion points, and each lands ~1.5e-2 from the float32
#: reference on a v5e, so two programs may differ by about their sum
SAMPLER_RTOL = 5e-2
#: served path vs float32 weights at "highest" precision: the served
#: matmuls round activations to bf16 once (~3 digits), over 8 steps
F32_RTOL = 1e-1
#: Pallas vs XLA engine: different kernels (f32 Mosaic matmuls, online
#: softmax) against XLA's bf16-pass matmuls
PALLAS_RTOL = 1e-1

UNET = "sd_v14"
TIMESTEPS = 8  # enough for PAS plans with SKETCH and REFINE steps


def log(msg: str) -> None:
    print(f"[chip_smoke] {msg}", flush=True)


def rel_err(got, want) -> float:
    import numpy as np

    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    if not (np.isfinite(got).all() and np.isfinite(want).all()):
        return float("inf")
    return float(np.linalg.norm(got - want) / np.linalg.norm(want))


def check(name: str, err: float, bound: float, failed: list) -> None:
    """Log one comparison; a miss goes to ``failed`` so that every
    comparison of a phase is printed before the phase fails."""
    log(f"{name}: relative error {err!r} (bound {bound})")
    if not err <= bound:
        failed.append(f"{name}: relative error {err!r} exceeds {bound}")


def raise_failed(failed: list) -> None:
    if failed:
        raise AssertionError("; ".join(failed))


def tpu_info(min_count: int) -> dict:
    """Device identity; raises unless JAX sees at least ``min_count`` TPUs."""
    from repro.runtime.device import device_info

    info = device_info()
    if info["platform"] != "tpu" or info["count"] < min_count:
        raise SystemExit(
            f"no TPU found: JAX sees {info['count']} {info['platform']} device(s); "
            f"this smoke test needs {min_count} TPU chip(s)"
        )
    log(f"device {info}{host_rss()}")
    return info


def host_rss() -> str:
    """The process's host RSS now and at its peak so far, as a log suffix."""
    import resource

    with open("/proc/self/status") as f:
        now = next(int(line.split()[1]) for line in f if line.startswith("VmRSS:"))
    peak = resource.getrusage(resource.RUSAGE_SELF).ru_maxrss
    return f" (host RSS {now / 2**20:.2f} GiB, peak {peak / 2**20:.2f} GiB)"


def payload(prompt: str, seed: int, quality: str, task: str = "txt2img", **extra) -> dict:
    return dict(task=task, prompt=prompt, seed=seed, timesteps=TIMESTEPS, quality=quality, **extra)


#: (name, payload) in three waves: one cold exact request, then three
#: mixed ones, then a repeated prompt (a cache hit) with a second exact one
WAVES = [
    [("exact", payload("a lighthouse at dusk", 1, "exact"))],
    [
        ("balanced", payload("a fox in the snow", 2, "balanced")),
        ("draft", payload("a bowl of ramen", 3, "draft")),
        ("img2img", payload("a watercolor harbor", 4, "balanced", task="img2img",
                            init={"seed": 7}, strength=0.5)),
    ],
    [
        ("repeat", payload("a fox in the snow", 2, "balanced")),
        ("exact2", payload("a red bicycle", 5, "exact")),
    ],
]
EXACT = ("exact", "exact2")


def serve_config(unet: str, **overrides):
    from repro.serving import config as CFG

    args = argparse.Namespace(unet=unet, batch=2, timesteps=TIMESTEPS, cache="cross", seed=0)
    return dataclasses.replace(CFG.from_args(args, decode_images=False), **overrides)


def record_latents(engine) -> dict:
    """rid -> finished latent, collected as the engine retires lanes."""
    latents: dict = {}
    step = engine.step

    def recording_step(*args, **kwargs):
        done = step(*args, **kwargs)
        latents.update((c.rid, c.latent) for c in done)
        return done

    engine.step = recording_step
    return latents


async def drive_http(driver, factory) -> tuple[dict, dict, dict]:
    """Serve the waves over HTTP; returns (name -> done event, /stats,
    drain summary)."""
    from repro.serving import HTTPFrontend
    from repro.serving.client import FrontendClient

    driver.start()
    frontend = await HTTPFrontend(driver, factory, "127.0.0.1", 0).start()
    server = asyncio.create_task(frontend.serve_until_shutdown())
    client = FrontendClient("127.0.0.1", frontend.port)
    events: dict = {}
    for wave in WAVES:
        t0 = time.perf_counter()
        done = await asyncio.gather(*(client.generate(**p) for _, p in wave))
        for (name, _), ev in zip(wave, done):
            if ev.get("event") != "done":
                raise AssertionError(f"request {name} ended with {ev}")
            events[name] = ev
        log(f"wave {[n for n, _ in wave]} done in {time.perf_counter() - t0:.3f} s")
    stats = await client.stats()
    await client.shutdown()
    summary = await server
    return events, stats, summary


def micro_step_args(engine) -> tuple:
    """Concrete arguments for lowering the engine's cached micro-step."""
    import jax.numpy as jnp
    import numpy as np

    n = engine.config.n_lanes
    return (
        engine._state, engine._params, jnp.int32(0), jnp.asarray(np.zeros((n,), bool)),
        jnp.asarray(np.full((n,), -1, np.int32)), jnp.asarray(np.full((n,), np.inf, np.float32)),
        engine.cache.state,
    )


def one_chip(unet: str = UNET, require_kernels: bool = True) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core import sampler as SM
    from repro.serving import EngineDriver, RequestFactory
    from repro.serving import config as CFG
    from repro.serving.driver import latent_digest

    # Programs compile one at a time: one sd_v14 compile alone peaks at
    # 11-16 GB of host RAM, beside the ~14 GiB the process holds once it
    # has the chip, and a one-chip host has 40 GiB.
    t0 = time.perf_counter()
    cfg = serve_config(unet)
    bundle = CFG.build_engine(cfg)
    jax.block_until_ready(bundle.params)
    log(f"built {unet} engine ({cfg.n_lanes} lanes, cache {cfg.cache_mode}) in "
        f"{time.perf_counter() - t0:.3f} s{host_rss()}")
    ucfg, dcfg, params = bundle.ucfg, bundle.dcfg, bundle.params

    # -- serve ----------------------------------------------------------------
    latents = record_latents(bundle.engine)
    driver = EngineDriver(bundle.engine, max_inflight=cfg.max_inflight)
    factory = RequestFactory(ucfg, dcfg, cfg, policy=bundle.policy, default_quality=cfg.quality)
    events, stats, summary = asyncio.run(drive_http(driver, factory))
    if not summary.get("drained") or summary.get("completed") != sum(map(len, WAVES)):
        raise AssertionError(f"server did not drain clean: {summary}")
    for name, ev in events.items():
        if latent_digest(latents[ev["rid"]]) != ev["latent_digest"]:
            raise AssertionError(f"{name}: streamed digest does not match the latent")
    if stats["full_steps"] == 0 or stats["sketch_steps"] == 0 or stats["refine_steps"] == 0:
        raise AssertionError(f"not every branch class ran: {stats}")
    if stats["hbm_hits"] == 0:
        raise AssertionError(f"the repeated prompt hit no cached features: {stats}")
    log(
        f"served: completed {stats['completed']}, steps full {stats['full_steps']} "
        f"sketch {stats['sketch_steps']} refine {stats['refine_steps']}, cache hits "
        f"{stats['hbm_hits']} (hit rate {stats['cache_hit_rate']}), device {stats['device']}"
    )
    log(f"set-up: cold first request (micro-step compile included) "
        f"{events['exact']['latency_s']:.3f} s; drained {summary['drained']}{host_rss()}")

    # -- compare: the straight-line sampler on the two exact requests --------
    named = {n: p for wave in WAVES for n, p in wave}
    exact_reqs = [factory.build(named[n])[0][0] for n in EXACT]
    x_t = jnp.asarray(np.stack([r.noise for r in exact_reqs]))
    ctx = jnp.asarray(np.stack([r.ctx for r in exact_reqs]))
    uncond = jnp.zeros_like(ctx)
    sampler = jax.jit(lambda p, x, c, u: SM.pas_denoise(ucfg, dcfg, p, None, x, c, u))
    t1 = time.perf_counter()
    ref = np.asarray(sampler(params, x_t, ctx, uncond))
    log(f"straight-line sampler compiled and ran in {time.perf_counter() - t1:.3f} s{host_rss()}")
    params32 = jax.tree.map(lambda a: a.astype(jnp.float32), params)
    t1 = time.perf_counter()
    with jax.default_matmul_precision("highest"):
        ref32 = np.asarray(sampler(params32, x_t, ctx, uncond))
    log(f"float32 reference compiled and ran in {time.perf_counter() - t1:.3f} s{host_rss()}")
    del params32
    served = [latents[events[n]["rid"]] for n in EXACT]
    failed: list = []
    for i, name in enumerate(EXACT):
        check(f"{name} vs straight-line sampler", rel_err(served[i], ref[i]), SAMPLER_RTOL, failed)
        check(f"{name} vs float32 reference", rel_err(served[i], ref32[i]), F32_RTOL, failed)
        check(f"{name} straight-line sampler vs float32 reference", rel_err(ref[i], ref32[i]),
              F32_RTOL, failed)

    # -- pallas: the same weights and requests on the kernel backend ---------
    pallas = CFG.build_engine(
        dataclasses.replace(cfg, backend="pallas"), models=(ucfg, dcfg, params, None)
    )
    t1 = time.perf_counter()
    compiled = pallas.engine._micro.lower(*micro_step_args(pallas.engine)).compile()
    n_calls = compiled.as_text().count("tpu_custom_call")
    log(f"pallas micro-step compiled in {time.perf_counter() - t1:.3f} s: "
        f"{n_calls} tpu_custom_call sites{host_rss()}")
    if require_kernels and n_calls == 0:
        raise AssertionError("the Pallas micro-step holds no compiled kernel")
    pallas.engine._micro = compiled  # run exactly the program checked above
    done, _ = pallas.engine.run([factory.build(named[n])[0][0] for n in EXACT])
    for c, name in zip(sorted(done, key=lambda c: c.rid), EXACT):
        check(f"{name} pallas vs xla engine", rel_err(c.latent, served[EXACT.index(name)]),
              PALLAS_RTOL, failed)
    peak = jax.devices()[0].memory_stats() or {}
    log(f"peak HBM bytes in use: {peak.get('peak_bytes_in_use', 'not reported')}")
    raise_failed(failed)


# ---------------------------------------------------------------------------
# four chips: the lane mesh, then pinned router replicas
# ---------------------------------------------------------------------------

#: four requests with mixed plans, served with the cache off so every lane
#: runs exactly its own plan in both engines
SHARD_PAYLOADS = [
    payload("a lighthouse at dusk", 1, "exact"),
    payload("a fox in the snow", 2, "balanced"),
    payload("a bowl of ramen", 3, "draft"),
    payload("a red bicycle", 5, "exact"),
]


def sharded_phase(unet: str, require_tpu: bool, queue) -> None:
    """Child process: the 4-shard engine against the one-device engine."""
    import concurrent.futures as cf

    from repro.runtime.device import configure_compile_cache, device_info
    from repro.serving import RequestFactory
    from repro.serving import config as CFG

    configure_compile_cache()
    info = tpu_info(4) if require_tpu else device_info()
    cfg = serve_config(unet, n_lanes=4, n_shards=4, cache_mode="off")
    sharded = CFG.build_engine(cfg)
    models = (sharded.ucfg, sharded.dcfg, sharded.params, None)
    single = CFG.build_engine(dataclasses.replace(cfg, n_shards=1), models=models)
    factory = RequestFactory(sharded.ucfg, sharded.dcfg, cfg, policy=sharded.policy)

    landed = [s.device for s in sharded.engine._state.x.addressable_shards]
    log(f"lane shards on devices {[(d.id, getattr(d, 'coords', None)) for d in landed]}")
    if len({d.id for d in landed}) != 4:
        raise AssertionError(f"lane shards share devices: {landed}")

    def serve(name, bundle, reqs):
        t0 = time.perf_counter()
        done, summary = bundle.engine.run(reqs)
        by_rid = {c.rid: c.latent for c in done}
        log(f"{name} engine: {summary['requests']} requests, steps full "
            f"{summary['full_steps']} sketch {summary['sketch_steps']} refine "
            f"{summary['refine_steps']}, {time.perf_counter() - t0:.3f} s incl. compile")
        return [by_rid[r.rid] for r in reqs]

    # both engines compile and run at once (one program each)
    with cf.ThreadPoolExecutor(2) as pool:
        futs = [
            pool.submit(serve, name, bundle, [factory.build(p)[0][0] for p in SHARD_PAYLOADS])
            for name, bundle in (("sharded", sharded), ("one-device", single))
        ]
        out_sharded, out_single = (f.result() for f in futs)
    failed: list = []
    for i, (a, b) in enumerate(zip(out_sharded, out_single)):
        check(f"request {i} sharded vs one-device", rel_err(a, b), SAMPLER_RTOL, failed)
    raise_failed(failed)
    queue.put(info)


def chip_files(pid: int) -> set:
    """Accelerator device files process ``pid`` holds open (a TPU chip is
    ``/dev/accel<N>`` or ``/dev/vfio/<N>``)."""
    fd_dir = f"/proc/{pid}/fd"
    held = set()
    for fd in os.listdir(fd_dir):
        try:
            target = os.readlink(os.path.join(fd_dir, fd))
        except OSError:  # closed since the listing
            continue
        if re.fullmatch(r"/dev/(accel|vfio/)\d+", target):
            held.add(target)
    return held


async def router_phase(replica_unet: str, require_tpu: bool) -> list:
    """Parent (no JAX): 4 replicas behind the router, one chip each."""
    import tempfile

    from repro.launch.router import replica_command
    from repro.serving.client import FrontendClient
    from repro.serving.router import ReplicaHandle, ReplicaRouter

    args = argparse.Namespace(
        unet=replica_unet, batch=2, timesteps=4, window=4, kernels="xla", max_inflight=8,
        cache="off", cache_threshold=0.15, cache_slots=4, cache_bucket=125,
        cache_spill_mb=0.0, seed=0, cache_gossip=True, pas=False, quality=None,
        profile=None, shards=1,
    )
    run_dir = tempfile.mkdtemp(prefix="replicas-", dir=os.path.join(CHECKOUT, "chiprun_out"))
    replicas = [ReplicaHandle(i, replica_command(args), run_dir, chip=i) for i in range(4)]
    router = ReplicaRouter(replicas, warmth_weight=0.0, respawn=False, log=log)
    try:
        t0 = time.perf_counter()
        await router.start()
        log(f"4 replicas ready in {time.perf_counter() - t0:.3f} s")
        server = asyncio.create_task(router.serve_until_shutdown())
        client = FrontendClient("127.0.0.1", router.port)

        async def generate(prompt: str, queued: asyncio.Event) -> dict:
            last: dict = {}
            async for ev in client.generate_stream(task="txt2img", prompt=prompt, timesteps=4):
                queued.set()
                last = ev
            queued.set()
            return last

        # the router counts a replica's load once it has queued a request:
        # admit one at a time so least-loaded routing spreads them
        tasks = []
        for i in range(4):
            queued = asyncio.Event()
            tasks.append(asyncio.create_task(generate(f"replica smoke {i}", queued)))
            await queued.wait()
        done = await asyncio.gather(*tasks)
        if any(ev.get("event") != "done" for ev in done):
            raise AssertionError(f"router requests failed: {done}")
        held = [chip_files(r.proc.pid) for r in replicas]
        stats = await client.stats()
        await client.shutdown()
        summary = await server
    finally:
        router.kill_all()
    per = [(r["idx"], r["stats"]["completed"], r["stats"]["device"]) for r in stats["replicas"]]
    for (idx, completed, device), files in zip(per, held):
        log(f"replica {idx}: completed {completed} on {device}, device files {sorted(files)}")
    if [c for _, c, _ in per] != [1, 1, 1, 1]:
        raise AssertionError(f"each replica should answer one request: {per}")
    # a pinned process may number its one chip as the origin, so the
    # device files each replica holds open also prove the chips distinct
    coords = {json.dumps(d.get("coords")) for _, _, d in per}
    disjoint = all(held) and len(set().union(*held)) == sum(map(len, held))
    if require_tpu and (
        any(d["count"] != 1 for _, _, d in per) or not (len(coords) == 4 or disjoint)
    ):
        raise AssertionError(f"replicas are not on one distinct chip each: {per} {held}")
    if not summary.get("drained"):
        raise AssertionError(f"router did not drain clean: {summary}")
    return per


def four_chips(unet: str = UNET, replica_unet: str = "sd_toy", require_tpu: bool = True) -> dict:
    ctx = multiprocessing.get_context("spawn")
    queue = ctx.Queue()
    child = ctx.Process(target=sharded_phase, args=(unet, require_tpu, queue))
    child.start()
    child.join()
    if child.exitcode != 0:
        raise SystemExit(f"sharded phase failed (exit {child.exitcode})")
    info = queue.get(timeout=10)
    os.environ["PYTHONPATH"] = SRC + os.pathsep + os.environ.get("PYTHONPATH", "")
    asyncio.run(router_phase(replica_unet, require_tpu))
    return info


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4 = run only the sharded-engine and pinned-replica paths")
    args = ap.parse_args()
    t0 = time.perf_counter()
    if args.chips == 4:
        os.makedirs(os.path.join(CHECKOUT, "chiprun_out"), exist_ok=True)
        info = four_chips()
    else:
        from repro.runtime.device import configure_compile_cache

        log(f"compile cache {configure_compile_cache()}")
        info = tpu_info(1)
        one_chip()
    log(f"all phases passed in {time.perf_counter() - t0:.3f} s")
    device = {k: info[k] for k in ("platform", "kind", "count")}
    print(json.dumps({"ok": True, "device": device}), flush=True)


if __name__ == "__main__":
    main()
