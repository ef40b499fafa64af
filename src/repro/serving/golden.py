"""Deterministic tiny-config workload for the golden-latent harness.

One canonical (config, params, request-stream) triple, shared by the tier-1
regression test (``tests/test_golden_latents.py``) and the regeneration
script (``tools/regen_golden_latents.py``), so the two can never drift.
The workload is sized to run in seconds on CPU: the ``sd_toy`` U-Net, two
lanes, three requests mixing PAS plans, a shorter plan, and an all-FULL
request — enough to exercise admission, backfill, branch grouping, and
every micro-step branch class.

The golden file pins three executions:

* the straight-line ``core.sampler.pas_denoise`` scan (``line_rid*`` keys),
* the continuous engine with the cache off (``engine_rid*`` keys), and
* the engine with the cache on at ``threshold=0`` (which must never hit —
  the lookup inequality is strict — and must stay bit-exact with the
  cache-off engine latents).

Each execution is asserted *bit-exactly* against its own golden family.
The two families are additionally cross-checked within a small tolerance:
they run different XLA programs (scan + scalar timestep vs gathered lane
micro-steps), which fuse differently, so cross-family bit equality is not
achievable — empirically they agree to ~1e-4 on the toy config.  Any
refactor of the sampler, lanes, engine, or cache that moves a single bit
of either family's output fails the harness.
"""
from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from repro.common.types import DiffusionConfig, PASPlan
from repro.configs import get_unet_config
from repro.core import sampler as SM
from repro.models import unet as U
from repro.serving.engine import (
    DiffusionEngine,
    EngineConfig,
    GenRequest,
    ShardedDiffusionEngine,
)

GOLDEN_FILE = "golden_latents_sd_toy.npz"
PARAMS_SEED = 0
_REQ_SEED = 1234

UCFG = get_unet_config("sd_toy")
N_UP = U.n_up_steps(UCFG)
L_SKETCH, L_REFINE = min(3, N_UP), min(2, N_UP)
DCFG = DiffusionConfig(timesteps_sample=6)
N_LANES = 2
MAX_STEPS = 8

#: (timesteps, has_pas_plan) per request — heterogeneous on purpose
REQUEST_SPECS: tuple[tuple[int, bool], ...] = ((6, True), (5, True), (6, False))


def _plan(timesteps: int) -> PASPlan:
    return PASPlan(
        t_sketch=max(2, timesteps // 2 + 1),
        t_complete=2,
        t_sparse=2,
        l_sketch=L_SKETCH,
        l_refine=L_REFINE,
    )


def golden_params() -> dict[str, Any]:
    return U.init_unet(jax.random.key(PARAMS_SEED), UCFG)


def golden_requests() -> list[GenRequest]:
    reqs = []
    for rid, (t, pas) in enumerate(REQUEST_SPECS):
        rng = np.random.default_rng(_REQ_SEED + rid)
        reqs.append(
            GenRequest(
                rid=rid,
                ctx=rng.normal(size=(UCFG.ctx_len, UCFG.ctx_dim)).astype(np.float32) * 0.2,
                noise=rng.normal(size=(UCFG.latent_size**2, UCFG.in_channels)).astype(
                    np.float32
                ),
                timesteps=t,
                plan=_plan(t) if pas else None,
            )
        )
    return reqs


def run_engine(
    params: dict[str, Any] | None = None,
    *,
    cache_mode: str = "off",
    cache_threshold: float = 0.0,
    backend: str = "xla",
) -> dict[int, np.ndarray]:
    """Serve the golden stream through the continuous engine -> {rid: latent}.

    ``backend="xla"`` (the default, and the only backend the golden file
    pins) is bit-identical to pre-backend-switch engines.  ``"pallas"``
    runs the Pallas kernel path — its flash-attention online softmax is
    mathematically but not bitwise equal to the XLA softmax, so pallas
    outputs are compared against the xla family within the differential
    suite's documented tolerance, never against the golden file.
    """
    params = golden_params() if params is None else params
    cfg = EngineConfig(
        n_lanes=N_LANES,
        max_steps=MAX_STEPS,
        l_sketch=L_SKETCH,
        l_refine=L_REFINE,
        decode_images=False,
        cache_mode=cache_mode,
        cache_threshold=cache_threshold,
        backend=backend,
    )
    engine = DiffusionEngine(UCFG, DCFG, params, None, cfg)
    done, _ = engine.run(golden_requests())
    return {d.rid: d.latent for d in done}


def run_sharded_engine(
    params: dict[str, Any] | None = None,
    *,
    n_shards: int = 1,
    cache_mode: str = "off",
    cache_threshold: float = 0.0,
) -> dict[int, np.ndarray]:
    """Serve the golden stream through the mesh-sharded engine.

    The sharded micro-step is a different XLA program (shard_map over the
    lane mesh), so callers compare against the golden ``engine`` family
    within the cross-program tolerance, not bit-exactly — except *between*
    sharded runs (e.g. cache threshold 0 vs cache off), which share a
    program family and must agree bit-for-bit.
    """
    params = golden_params() if params is None else params
    cfg = EngineConfig(
        n_lanes=N_LANES,
        max_steps=MAX_STEPS,
        l_sketch=L_SKETCH,
        l_refine=L_REFINE,
        decode_images=False,
        cache_mode=cache_mode,
        cache_threshold=cache_threshold,
        n_shards=n_shards,
    )
    engine = ShardedDiffusionEngine(UCFG, DCFG, params, None, cfg)
    done, _ = engine.run(golden_requests())
    return {d.rid: d.latent for d in done}


def run_straight_line(params: dict[str, Any] | None = None) -> dict[int, np.ndarray]:
    """Each request alone through the scan-based PAS sampler -> {rid: latent}."""
    params = golden_params() if params is None else params
    out = {}
    for req in golden_requests():
        dcfg = dataclasses.replace(DCFG, timesteps_sample=req.timesteps)
        x0 = SM.pas_denoise(
            UCFG, dcfg, params, req.plan,
            jnp.asarray(req.noise)[None], jnp.asarray(req.ctx)[None],
            jnp.zeros((1, UCFG.ctx_len, UCFG.ctx_dim), jnp.float32),
        )
        out[req.rid] = np.asarray(x0[0])
    return out


#: the JAX version a golden file was written under: seeded weights and
#: noise change across JAX releases, so a file holds only for its own
VERSION_KEY = "jax_version"


def check_version(z, path: str) -> None:
    """Refuse a golden file written under another JAX release."""
    written = str(z[VERSION_KEY]) if VERSION_KEY in z.files else "an unrecorded version"
    if written != jax.__version__:
        raise RuntimeError(
            f"{path} was written under jax {written}, this is jax {jax.__version__}: "
            "regenerate it with tools/regen_golden_latents.py / tools/regen_golden_scenarios.py"
        )


def save_golden(path: str) -> tuple[dict[int, np.ndarray], dict[int, np.ndarray]]:
    """Regenerate the golden file (both execution families) -> (line, engine)."""
    params = golden_params()
    line = run_straight_line(params)
    engine = run_engine(params, cache_mode="off")
    arrays = {f"line_rid{rid}": lat for rid, lat in line.items()}
    arrays |= {f"engine_rid{rid}": lat for rid, lat in engine.items()}
    np.savez_compressed(path, **arrays, **{VERSION_KEY: np.asarray(jax.__version__)})
    return line, engine


def load_golden(path: str) -> tuple[dict[int, np.ndarray], dict[int, np.ndarray]]:
    """Load the golden file -> ({rid: straight-line}, {rid: engine})."""
    line, engine = {}, {}
    with np.load(path) as z:
        check_version(z, path)
        for k in z.files:
            if k == VERSION_KEY:
                continue
            fam, rid = k.rsplit("_rid", 1)
            (line if fam == "line" else engine)[int(rid)] = z[k]
    return line, engine
