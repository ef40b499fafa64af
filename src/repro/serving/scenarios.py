"""Deterministic conditioned-pipeline scenarios for the golden harness.

The conditioned counterpart of :mod:`repro.serving.golden`: one canonical
(config, params, request-stream) triple covering every v2 task the serving
stack can run — img2img at two strengths (a strength-truncated schedule and
an almost-full one), inpainting with a full-ones mask (structurally the
txt2img identity) and a half mask, and a K=3 variation fan-out sharing one
prompt.  Shared by the regression test (``tests/test_serving_scenarios.py``)
and the regeneration script (``tools/regen_golden_scenarios.py``) so the two
can never drift.  The model/config constants are imported from
``repro.serving.golden`` — same ``sd_toy`` U-Net, same params seed — so the
scenarios exercise the same compiled families as the txt2img goldens.

Golden families (all bit-exact against their own family, cross-checked
within the cross-program tolerance):

* ``line_*``  — the straight-line :func:`repro.core.sampler.
  pas_denoise_scheduled` reference: explicit truncated schedules, q_sampled
  img2img entries, per-step inpaint blends;
* ``engine_*`` — the continuous engine with the cache off, plus a
  cache-on-at-threshold-0 run that must stay bit-exact with cache-off.
"""
from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from repro.common.types import PASPlan
from repro.core import sampler as SM
from repro.models import diffusion as D
from repro.serving.engine import (
    DiffusionEngine,
    EngineConfig,
    GenRequest,
    ShardedDiffusionEngine,
)
from repro.serving.golden import (
    DCFG,
    L_REFINE,
    L_SKETCH,
    MAX_STEPS,
    N_LANES,
    UCFG,
    VERSION_KEY,
    check_version,
    golden_params,
)

GOLDEN_FILE = "golden_latents_scenarios_sd_toy.npz"
_REQ_SEED = 4321

#: base (untruncated) schedule length every scenario is cut from
BASE_T = DCFG.timesteps_sample

#: the two img2img strengths the fixtures pin (truncated / nearly full)
STRENGTHS = (0.4, 0.75)

#: variation fan-out width
N_VARIANTS = 3


def _n_exec(strength: float) -> int:
    """The executed step count ``strength`` resolves to (schema contract)."""
    return max(1, round(strength * BASE_T))


def _plan(timesteps: int) -> PASPlan:
    return PASPlan(
        t_sketch=max(2, timesteps // 2 + 1),
        t_complete=2,
        t_sparse=2,
        l_sketch=L_SKETCH,
        l_refine=L_REFINE,
    )


def _half_mask(length: int) -> np.ndarray:
    """First half kept from the init latent, second half generated."""
    m = np.ones((length, 1), np.float32)
    m[: length // 2] = 0.0
    return m


def scenario_requests() -> list[tuple[str, GenRequest]]:
    """The named scenario stream -> [(name, request)].

    Names double as golden-file keys (``line_<name>`` / ``engine_<name>``).
    Request ids follow list order.  The three ``var_*`` requests share one
    prompt context and differ only in their noise seeds — the engine-level
    shape of a K=3 variation group.
    """
    latent = (UCFG.latent_size**2, UCFG.in_channels)
    out: list[tuple[str, GenRequest]] = []

    def draw(rng):
        ctx = rng.normal(size=(UCFG.ctx_len, UCFG.ctx_dim)).astype(np.float32) * 0.2
        noise = rng.normal(size=latent).astype(np.float32)
        return ctx, noise

    # img2img at two strengths: 0.4 truncates hard (all-FULL plan — the
    # truncated schedule is too short for a PAS plan), 0.75 keeps a PAS plan
    for i, strength in enumerate(STRENGTHS):
        rng = np.random.default_rng(_REQ_SEED + i)
        ctx, noise = draw(rng)
        init = rng.normal(size=latent).astype(np.float32)
        n_exec = _n_exec(strength)
        out.append((
            f"img2img_s{int(round(strength * 100)):03d}",
            GenRequest(
                rid=len(out), ctx=ctx, noise=noise,
                timesteps=n_exec, base_timesteps=BASE_T,
                plan=_plan(n_exec) if n_exec >= 4 else None,
                init_latent=init,
            ),
        ))

    # inpainting: full-ones mask (structural txt2img identity) and half mask
    for name, mask in (
        ("inpaint_ones", np.ones((latent[0], 1), np.float32)),
        ("inpaint_half", _half_mask(latent[0])),
    ):
        rng = np.random.default_rng(_REQ_SEED + 10 + len(out))
        ctx, noise = draw(rng)
        init = rng.normal(size=latent).astype(np.float32)
        out.append((
            name,
            GenRequest(
                rid=len(out), ctx=ctx, noise=noise,
                timesteps=BASE_T,
                plan=_plan(BASE_T) if name == "inpaint_half" else None,
                init_latent=init, mask=mask,
            ),
        ))

    # K=3 variation fan-out: one prompt ctx, per-variant noise
    rng = np.random.default_rng(_REQ_SEED + 100)
    ctx, noise = draw(rng)
    noises = [noise] + [rng.normal(size=latent).astype(np.float32)
                        for _ in range(N_VARIANTS - 1)]
    for v, n in enumerate(noises):
        out.append((
            f"var_{v}",
            GenRequest(
                rid=len(out), ctx=ctx, noise=n,
                timesteps=BASE_T, plan=_plan(BASE_T),
            ),
        ))
    return out


def _engine_cfg(*, cache_mode: str, cache_threshold: float, n_shards: int = 1):
    return EngineConfig(
        n_lanes=N_LANES,
        max_steps=MAX_STEPS,
        l_sketch=L_SKETCH,
        l_refine=L_REFINE,
        decode_images=False,
        cache_mode=cache_mode,
        cache_threshold=cache_threshold,
        n_shards=n_shards,
    )


def run_engine(
    params: dict[str, Any] | None = None,
    *,
    cache_mode: str = "off",
    cache_threshold: float = 0.0,
) -> dict[str, np.ndarray]:
    """Serve the scenario stream through the continuous engine -> {name: latent}."""
    params = golden_params() if params is None else params
    cfg = _engine_cfg(cache_mode=cache_mode, cache_threshold=cache_threshold)
    engine = DiffusionEngine(UCFG, DCFG, params, None, cfg)
    named = scenario_requests()
    done, _ = engine.run([req for _, req in named])
    by_rid = {d.rid: d.latent for d in done}
    return {name: by_rid[req.rid] for name, req in named}


def run_sharded_engine(
    params: dict[str, Any] | None = None,
    *,
    n_shards: int = 1,
    cache_mode: str = "off",
    cache_threshold: float = 0.0,
) -> dict[str, np.ndarray]:
    """Serve the scenario stream through the mesh-sharded engine."""
    params = golden_params() if params is None else params
    cfg = _engine_cfg(
        cache_mode=cache_mode, cache_threshold=cache_threshold, n_shards=n_shards
    )
    engine = ShardedDiffusionEngine(UCFG, DCFG, params, None, cfg)
    named = scenario_requests()
    done, _ = engine.run([req for _, req in named])
    by_rid = {d.rid: d.latent for d in done}
    return {name: by_rid[req.rid] for name, req in named}


def run_straight_line(params: dict[str, Any] | None = None) -> dict[str, np.ndarray]:
    """Each scenario alone through ``pas_denoise_scheduled`` -> {name: latent}.

    Mirrors the engine's conditioning exactly: the strength-truncated
    schedule, the q_sampled img2img entry at ``ts[0]``, and the per-step
    inpaint blend with the request's own noise as the known-region noise.
    """
    params = golden_params() if params is None else params
    sched = D.make_schedule(DCFG)
    zeros_ctx = jnp.zeros((1, UCFG.ctx_len, UCFG.ctx_dim), jnp.float32)
    out = {}
    for name, req in scenario_requests():
        base = req.timesteps if req.base_timesteps is None else req.base_timesteps
        ts = SM.truncated_timesteps(DCFG, base, req.timesteps)
        noise = jnp.asarray(req.noise)[None]
        if req.init_latent is not None and req.timesteps < base:
            t0 = jnp.full((1,), int(ts[0]), jnp.int32)
            x_t = D.q_sample(sched, jnp.asarray(req.init_latent)[None], t0, noise)
        else:
            x_t = noise
        if req.mask is not None:
            mask = jnp.asarray(req.mask, jnp.float32).reshape(1, -1, 1)
            x_init = jnp.asarray(req.init_latent)[None]
            noise0 = noise
        else:
            mask = x_init = noise0 = None
        x0 = SM.pas_denoise_scheduled(
            UCFG, DCFG, params, req.plan,
            x_t, jnp.asarray(req.ctx)[None], zeros_ctx,
            ts=ts, mask=mask, x_init=x_init, noise0=noise0,
        )
        out[name] = np.asarray(x0[0])
    return out


def save_golden(path: str) -> tuple[dict[str, np.ndarray], dict[str, np.ndarray]]:
    """Regenerate the scenarios golden file -> (line, engine) families."""
    params = golden_params()
    line = run_straight_line(params)
    engine = run_engine(params, cache_mode="off")
    arrays = {f"line_{name}": lat for name, lat in line.items()}
    arrays |= {f"engine_{name}": lat for name, lat in engine.items()}
    np.savez_compressed(path, **arrays, **{VERSION_KEY: np.asarray(jax.__version__)})
    return line, engine


def load_golden(path: str) -> tuple[dict[str, np.ndarray], dict[str, np.ndarray]]:
    """Load the scenarios golden file -> ({name: line}, {name: engine})."""
    line, engine = {}, {}
    with np.load(path) as z:
        check_version(z, path)
        for k in z.files:
            if k == VERSION_KEY:
                continue
            fam, name = k.split("_", 1)
            (line if fam == "line" else engine)[name] = z[k]
    return line, engine
