"""Set-up of the system under test, and what the benchmark reads from it.

The served path in one process: the benchmark's HTTP client ->
``HTTPFrontend`` -> ``EngineDriver`` (its own thread) ->
``DiffusionEngine.step`` -> the lane micro-step -> the U-Net through the
configured kernel backend.  The benchmark makes the weights itself (one
jitted call from the seed, in the served dtypes) and hands them to the
program's construction path; it keeps JAX's compile cache at a fixed path
inside the checkout.

What the benchmark takes from the program besides the results: the
finished latents (by wrapping ``engine.step``, as they retire), one
counter snapshot per tick (the branch class and lanes it advanced), and
``/stats`` counters.  The wrapper also opens a ``bench.engine_step``
profiler span around each tick, so that device idle gaps can be told
apart by what the host was doing.
"""
from __future__ import annotations

import asyncio
import dataclasses
import os
import time

import numpy as np

from benchmarks.chip import spec

#: JAX's persistent compile cache: a fixed path inside the checkout
CACHE_DIR = spec.HERE / ".jax_cache"
#: scratch for one run's profiler trace (removed once it is read)
RUNS_DIR = spec.HERE / ".runs"


def configure_jax() -> dict:
    """Compile cache at ``CACHE_DIR`` whatever the environment says, every
    program kept; and listeners that record each compile or cache load as
    (when, seconds, program, "hit" | "miss"), so that compiles inside the
    window can be counted and set-up read program by program."""
    import jax

    jax.config.update("jax_enable_compilation_cache", True)
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    # no size cap: under a cap, the reference's programs evict the set-up's
    # and each run compiles everything again
    jax.config.update("jax_compilation_cache_max_size", -1)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    compiles: list[tuple[float, float, str, str]] = []
    cache = {"hits": 0, "misses": 0, "last": "?"}

    def on_event(event: str, **_):
        # fired inside the compile whose duration is reported next
        if event == "/jax/compilation_cache/cache_hits":
            cache["hits"] += 1
            cache["last"] = "hit"
        elif event == "/jax/compilation_cache/cache_misses":
            cache["misses"] += 1
            cache["last"] = "miss"

    def on_duration(event: str, duration: float, fun_name: str = "?", **_):
        if event == "/jax/core/compile/backend_compile_duration":
            compiles.append((time.perf_counter(), duration, fun_name, cache["last"]))
            cache["last"] = "?"

    jax.monitoring.register_event_duration_secs_listener(on_duration)
    jax.monitoring.register_event_listener(on_event)
    return {"compiles": compiles, "cache": cache}


def compile_summary(compiles: list, since: float = 0.0) -> str:
    """Seconds spent compiling or loading since ``since``, and the
    slowest programs with whether the persistent cache had them."""
    cs = [c for c in compiles if c[0] >= since]
    slow = sorted(cs, key=lambda c: -c[1])[:6]
    return (f"{len(cs)} programs, {sum(c[1] for c in cs):.3f} s; slowest: "
            + ", ".join(f"{name} {secs:.3f} s ({how})" for _, secs, name, how in slow))


def device_stamp(chips: int, require_tpu: bool = True) -> dict:
    """Platform, kind and count as JAX reports them; exits non-zero where
    JAX finds no TPU or fewer chips than the cell asks for."""
    import jax

    devs = jax.devices()
    info = {"platform": devs[0].platform, "kind": devs[0].device_kind, "count": len(devs)}
    if require_tpu and (info["platform"] != "tpu" or info["count"] < chips):
        raise SystemExit(
            f"this cell needs {chips} TPU chip(s); JAX sees {info['count']} "
            f"{info['platform']} device(s)"
        )
    return info


def make_weights(ucfg, seed: int):
    """Random weights in the served parameter tree and dtypes, made on the
    device by one jitted call from ``seed``: one stream of standard
    normals, cut in turn into every leaf; matrices and convolution kernels
    scaled to N(0, 1/fan_in), biases N(0, 0.05^2), norm scales
    1 + N(0, 0.05^2), norm shifts N(0, 0.05^2).  Only the tree's layout
    comes from the program (its shapes, by ``jax.eval_shape``).  One
    stream, not one draw per leaf, keeps the program small to compile."""
    import jax
    import jax.numpy as jnp

    from repro.models import unet as U

    shapes = jax.eval_shape(lambda k: U.init_unet(k, ucfg), jax.random.key(0))
    leaves, treedef = jax.tree_util.tree_flatten_with_path(shapes)
    sizes = [int(np.prod(leaf.shape)) for _, leaf in leaves]

    def make(key):
        z = jax.random.normal(key, (sum(sizes),), jnp.float32)
        out, off = [], 0
        for (path, leaf), n in zip(leaves, sizes):
            name = str(getattr(path[-1], "key", path[-1]))
            x = jax.lax.slice(z, (off,), (off + n,)).reshape(leaf.shape)
            off += n
            if len(leaf.shape) >= 2:
                v = x / np.sqrt(np.prod(leaf.shape[:-1]))
            elif name == "scale":
                v = 1.0 + 0.05 * x
            else:
                v = 0.05 * x
            out.append(v.astype(leaf.dtype))
        return jax.tree_util.tree_unflatten(treedef, out)

    key_seed = int(np.random.default_rng(seed).integers(0, 2**31 - 1))
    return jax.jit(make)(jax.random.key(key_seed))


@dataclasses.dataclass
class Tick:
    """One ``engine.step`` call as the host saw it."""

    t0: float
    t1: float
    cls: str | None  # branch class run ("full" | "sketch" | "refine"), None = no tick
    advanced: int


class Served:
    """The system under test for one cell, built and warmed up."""

    def __init__(self, config: dict, seed: int):
        import jax

        from repro.common.types import DiffusionConfig
        from repro.serving import EngineDriver, HTTPFrontend, RequestFactory
        from repro.serving import config as CFG
        from repro.serving.engine import EngineConfig

        s = config["serving"]
        sched = config["scheduler"]
        self.ucfg = spec.arch(config).program_config(config)
        self.dcfg = DiffusionConfig(
            timesteps_train=sched["num_train_timesteps"], timesteps_sample=s["max_steps"],
            scheduler="pndm", beta_start=sched["beta_start"], beta_end=sched["beta_end"],
            beta_schedule=sched["beta_schedule"], guidance_scale=s["guidance_scale"],
        )
        self.params = make_weights(self.ucfg, seed)
        jax.block_until_ready(self.params)
        self.engine_config = EngineConfig(
            n_lanes=s["lanes"], max_steps=s["max_steps"], l_sketch=s["l_sketch"],
            l_refine=s["l_refine"], decode_images=False, cache_mode=s["cache"],
            backend=s["kernels"], unet=config["name"], seed=seed,
        )
        bundle = CFG.build_engine(self.engine_config,
                                  models=(self.ucfg, self.dcfg, self.params, None))
        self.engine = bundle.engine
        self.latents: dict[int, np.ndarray] = {}
        self.ticks: list[Tick] = []
        self._wrap_step()
        self.driver = EngineDriver(self.engine, max_inflight=self.engine_config.max_inflight)
        self.factory = RequestFactory(self.ucfg, self.dcfg, self.engine_config,
                                      policy=bundle.policy)
        self._frontend_cls = HTTPFrontend
        self.frontend = None
        self.port = None

    def _wrap_step(self) -> None:
        import jax

        engine, step = self.engine, self.engine.step

        def bench_step(*args, **kwargs):
            m = engine.metrics
            before = (m.full_steps, m.sketch_steps, m.refine_steps)
            t0 = time.perf_counter()
            with jax.profiler.TraceAnnotation("bench.engine_step"):
                done = step(*args, **kwargs)
            after = (engine.metrics.full_steps, engine.metrics.sketch_steps,
                     engine.metrics.refine_steps)
            moved = [a - b for a, b in zip(after, before)]
            cls = next((c for c, n in zip(("full", "sketch", "refine"), moved) if n), None)
            self.ticks.append(Tick(t0, time.perf_counter(), cls, sum(moved)))
            self.latents.update((c.rid, c.latent) for c in done)
            return done

        engine.step = bench_step

    async def start(self) -> None:
        self.driver.start()
        self.frontend = await self._frontend_cls(self.driver, self.factory, "127.0.0.1", 0).start()
        self.port = self.frontend.port
        self._server = asyncio.create_task(self.frontend.serve_until_shutdown())

    async def stop(self) -> dict:
        from benchmarks.chip import load

        await load.http_json(self.port, "POST", "/shutdown")
        return await self._server

    async def stats(self) -> dict:
        from benchmarks.chip import load

        return await load.http_json(self.port, "GET", "/stats")

    def memory_peak_bytes(self) -> int | None:
        import jax

        peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use") for d in jax.local_devices()]
        peaks = [p for p in peaks if p is not None]
        return max(peaks) if peaks else None

    def release(self) -> None:
        """Drop every device array the program holds except the weights."""
        import gc

        self.engine._state = None
        self.engine._micro = None
        self.engine = None
        self.driver = None
        self.frontend = None
        gc.collect()


def env_summary() -> str:
    return ", ".join(f"{k}={os.environ.get(k)!r}" for k in
                     ("JAX_COMPILATION_CACHE_DIR", "JAX_COMPILATION_CACHE_MAX_SIZE"))
