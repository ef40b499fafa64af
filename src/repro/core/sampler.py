"""Phase-aware sampling executor (paper Sec. III-B, Fig. 5).

The whole denoising loop — scheduler step, classifier-free guidance, and
the full/partial U-Net switch — is a single ``lax.scan`` whose per-step
branch is selected by a precomputed plan vector, so the entire PAS sampler
jits, shards and dry-runs as one XLA program:

    branch 0: full U-Net, refresh the sketch-feature cache
    branch 1: partial run with the top L_sketch blocks  (sketching phase)
    branch 2: partial run with the top L_refine blocks  (refinement phase)

The cached entry features are the CFG-doubled main-branch activations of
the relevant up-steps, reused exactly as in the paper's Fig. 5 zoom-in.
"""
from __future__ import annotations

import functools
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np

from repro.common.types import DiffusionConfig, PASPlan, UNetConfig
from repro.models import diffusion as D
from repro.models import unet as U

Params = dict[str, Any]

FULL, SKETCH, REFINE = 0, 1, 2


def plan_to_branches(plan: PASPlan, total_steps: int) -> jnp.ndarray:
    sched = plan.schedule(total_steps)
    br = [FULL if l < 0 else (SKETCH if l == plan.l_sketch else REFINE) for l in sched]
    # disambiguate when l_sketch == l_refine: phase decides the label
    for t in range(total_steps):
        if sched[t] >= 0 and t >= plan.t_sketch:
            br[t] = REFINE
    return jnp.asarray(br, jnp.int32)


def _entry_steps(ucfg: UNetConfig, plan: PASPlan) -> tuple[int, int]:
    n_up = U.n_up_steps(ucfg)
    return n_up - plan.l_sketch, n_up - plan.l_refine


def uncond_added(ucfg: UNetConfig, added):
    """The unconditional rows' added conditioning for conditional rows'
    ``added`` [..., added_dim] (numpy or jax): the pooled embedding zeroed
    (SDXL's ``force_zeros_for_empty_prompt``), the time ids kept."""
    keep = np.concatenate([np.zeros(ucfg.pooled_dim, np.float32),
                           np.ones(len(ucfg.time_ids), np.float32)])
    return added * keep


def cfg_unet_step(
    ucfg: UNetConfig,
    params: Params,
    guidance: float,
    x: jax.Array,  # [B, L, C]
    t: jax.Array,  # scalar or [B] timesteps
    ctx2: jax.Array,  # [2B, ctx_len, ctx_dim] = [cond; uncond]
    *,
    entry_step: int = 0,
    entry_feat: jax.Array | None = None,  # [2B, ...] cached main-branch feature
    capture: tuple[int, ...] = (),
    backend=None,  # KernelBackend instance or name; None = "xla"
    added2: jax.Array | None = None,  # [2B, added_dim] = [cond; uncond]
) -> tuple[jax.Array, dict[int, jax.Array]]:
    """One classifier-free-guided U-Net invocation on the CFG-doubled batch.

    Shared by the scan-based :func:`pas_denoise` (scalar ``t``) and the
    serving engine's micro-step (per-lane ``t`` vector).  Returns the guided
    eps prediction [B, L, C] and the captured main-branch features in the
    [2B, ...] cond/uncond-stacked layout.  ``backend`` is forwarded to
    :func:`repro.models.unet.unet_apply` (the kernel-backend chokepoint),
    as is ``added2``, the CFG-doubled added conditioning of a U-Net that
    has one (SDXL's ``text_time``).
    """
    b = x.shape[0]
    x2 = jnp.concatenate([x, x], axis=0)
    tb = jnp.broadcast_to(t, (b,))
    t2 = jnp.concatenate([tb, tb], axis=0)
    eps2, cap = U.unet_apply(
        ucfg, params, x2, t2, ctx2,
        entry_step=entry_step, entry_feat=entry_feat, capture_steps=capture,
        backend=backend, added=added2,
    )
    e_c, e_u = jnp.split(eps2, 2, axis=0)
    return e_u + guidance * (e_c - e_u), cap


def feat_shape(ucfg: UNetConfig, entry_step: int, batch: int) -> tuple[int, ...]:
    """Shape of the main-branch feature entering ``entry_step``.

    This is the tensor the FULL branch captures and the partial branches
    consume — and therefore also the per-slot geometry of the serving
    feature cache (``repro.serving.cache``).
    """
    chans = [ucfg.base_channels * m for m in ucfg.channel_mult]
    plan = U._up_plan(ucfg)
    lvl = plan[entry_step][0]
    # resolution at which the entry step consumes its skip
    size = ucfg.latent_size >> lvl
    if entry_step == 0:
        c = chans[-1]
    else:
        prev_lvl = plan[entry_step - 1][0]
        c = chans[prev_lvl]
    return (batch, size * size, c)


_feat_shape = feat_shape  # back-compat alias (pre-cache callers)


def truncated_timesteps(dcfg: DiffusionConfig, base: int, n_exec: int) -> jnp.ndarray:
    """The last ``n_exec`` timesteps of a ``base``-step sampling schedule.

    This is the img2img schedule resolution: ``strength`` picks how many of
    the base schedule's *final* steps actually execute, while the stride —
    and therefore the train timesteps each executed step sees — stays that
    of the untruncated schedule.  ``n_exec == base`` is the stock schedule.
    """
    if not 1 <= n_exec <= base:
        raise ValueError(f"truncation wants {n_exec} of {base} steps")
    stride = dcfg.timesteps_train // base
    ts = (jnp.arange(base) * stride)[::-1].astype(jnp.int32)
    return ts[base - n_exec:]


def pas_denoise_scheduled(
    ucfg: UNetConfig,
    dcfg: DiffusionConfig,
    params: Params,
    plan: PASPlan | None,
    x_t: jax.Array,  # [B, L, C] entry latent (noise, or a q_sampled init)
    ctx_cond: jax.Array,
    ctx_uncond: jax.Array,
    *,
    ts: jax.Array | None = None,  # explicit descending timestep vector
    mask: jax.Array | None = None,  # [B, L, 1] inpaint mask (1 = generate)
    x_init: jax.Array | None = None,  # [B, L, C] known latent under the mask
    noise0: jax.Array | None = None,  # [B, L, C] fixed noise for the known region
    backend=None,  # kernel backend forwarded to every U-Net call
    added: jax.Array | None = None,  # [B, added_dim] conditional rows' added conditioning
) -> jax.Array:
    """Straight-line PAS sampling over an *explicit* timestep schedule.

    Generalizes :func:`pas_denoise` to the conditioned serving scenarios —
    the reference implementation the engine's differential tests compare
    against:

    * **img2img**: pass the strength-truncated schedule from
      :func:`truncated_timesteps` and an entry latent seeded with
      :func:`repro.models.diffusion.q_sample` at ``ts[0]``;
    * **inpainting**: pass ``mask`` / ``x_init`` / ``noise0`` — after every
      scheduler step the masked-out region is replaced by the known latent
      re-noised to that step's target timestep (``t_prev < 0`` resolves to
      the clean ``x_init``).  The blend selects the denoised latent
      *exactly* where ``mask >= 1``, so a full-ones mask is structurally
      the identity.

    ``ts=None`` with no mask is exactly the :func:`pas_denoise` loop (same
    math; the scan carries two extra — constant — leaves when masked).
    ``added`` is the added conditioning of a U-Net that has one; the
    unconditional rows get :func:`uncond_added` of it.
    """
    sched = D.make_schedule(dcfg)
    if ts is None:
        ts = D.sample_timesteps(dcfg)
    ts = jnp.asarray(ts, jnp.int32)
    total = int(ts.shape[0])
    t_prev = jnp.concatenate([ts[1:], jnp.array([-1], jnp.int32)])

    inpaint = mask is not None
    if inpaint:
        mask = jnp.asarray(mask, x_t.dtype)
        x_init = jnp.zeros_like(x_t) if x_init is None else jnp.asarray(x_init, x_t.dtype)
        noise0 = jnp.zeros_like(x_t) if noise0 is None else jnp.asarray(noise0, x_t.dtype)

    b = x_t.shape[0]
    b2 = 2 * b
    guidance = dcfg.guidance_scale

    refresh_cache = plan is not None
    if plan is None:
        branches = jnp.zeros((total,), jnp.int32)
        plan = PASPlan(total, total, 1, 1, 1)
    else:
        branches = plan_to_branches(plan, total)
    e_sk, e_rf = _entry_steps(ucfg, plan)

    ctx2 = jnp.concatenate([ctx_cond, ctx_uncond], axis=0)
    added2 = None if added is None else jnp.concatenate(
        [added, uncond_added(ucfg, added)], axis=0)

    def run_unet(x, t, entry_step, entry_feat, capture):
        return cfg_unet_step(
            ucfg, params, guidance, x, t, ctx2,
            entry_step=entry_step, entry_feat=entry_feat, capture=capture,
            backend=backend, added2=added2,
        )

    f_sk0 = jnp.zeros(_feat_shape(ucfg, e_sk, b2), x_t.dtype)
    f_rf0 = jnp.zeros(_feat_shape(ucfg, e_rf, b2), x_t.dtype)

    def full_branch(op):
        x, t, f_sk, f_rf = op
        if not refresh_cache:
            eps, _ = run_unet(x, t, 0, None, capture=())
            return eps, f_sk, f_rf
        eps, cap = run_unet(x, t, 0, None, capture=(e_sk, e_rf))
        return eps, cap[e_sk], cap[e_rf]

    def sketch_branch(op):
        x, t, f_sk, f_rf = op
        eps, _ = run_unet(x, t, e_sk, f_sk, capture=())
        return eps, f_sk, f_rf

    def refine_branch(op):
        x, t, f_sk, f_rf = op
        eps, _ = run_unet(x, t, e_rf, f_rf, capture=())
        return eps, f_sk, f_rf

    def step(carry, inp):
        x, pndm, f_sk, f_rf = carry
        t, tp, br = inp
        eps, f_sk, f_rf = jax.lax.switch(
            br, (full_branch, sketch_branch, refine_branch), (x, t, f_sk, f_rf)
        )
        if dcfg.scheduler == "pndm":
            x, pndm = D.pndm_step(sched, pndm, x, eps, t, tp)
        else:
            x = D.ddim_step(sched, x, eps, t, tp)
        if inpaint:
            # re-noise the known region to the step's target timestep and
            # blend; jnp.where keeps a full-ones mask structurally exact
            ab = jnp.where(tp >= 0, sched.alphas_cumprod[jnp.maximum(tp, 0)], 1.0)
            known = jnp.sqrt(ab) * x_init + jnp.sqrt(1.0 - ab) * noise0
            x = jnp.where(mask >= 1.0, x, mask * x + (1.0 - mask) * known)
        return (x, pndm, f_sk, f_rf), None

    pndm0 = D.pndm_init(x_t.shape, x_t.dtype)
    (x0, _, _, _), _ = jax.lax.scan(step, (x_t, pndm0, f_sk0, f_rf0), (ts, t_prev, branches))
    return x0


def pas_denoise(
    ucfg: UNetConfig,
    dcfg: DiffusionConfig,
    params: Params,
    plan: PASPlan | None,
    x_t: jax.Array,  # [B, L, C] initial noise
    ctx_cond: jax.Array,
    ctx_uncond: jax.Array,
    *,
    backend=None,  # kernel backend forwarded to every U-Net call
) -> jax.Array:
    """Run the full PAS sampling loop. ``plan=None`` -> original sampler."""
    sched = D.make_schedule(dcfg)
    ts = D.sample_timesteps(dcfg)
    total = dcfg.timesteps_sample
    t_prev = jnp.concatenate([ts[1:], jnp.array([-1], jnp.int32)])
    b = x_t.shape[0]
    b2 = 2 * b
    guidance = dcfg.guidance_scale

    # plan=None: all-full schedule; dummy plan only sizes the (never-consumed)
    # carry features, and the full branch skips the capture entirely.
    refresh_cache = plan is not None
    if plan is None:
        branches = jnp.zeros((total,), jnp.int32)
        plan = PASPlan(total, total, 1, 1, 1)
    else:
        branches = plan_to_branches(plan, total)
    e_sk, e_rf = _entry_steps(ucfg, plan)

    ctx2 = jnp.concatenate([ctx_cond, ctx_uncond], axis=0)

    def run_unet(x, t, entry_step, entry_feat, capture):
        return cfg_unet_step(
            ucfg, params, guidance, x, t, ctx2,
            entry_step=entry_step, entry_feat=entry_feat, capture=capture,
            backend=backend,
        )

    f_sk0 = jnp.zeros(_feat_shape(ucfg, e_sk, b2), x_t.dtype)
    f_rf0 = jnp.zeros(_feat_shape(ucfg, e_rf, b2), x_t.dtype)

    def full_branch(op):
        x, t, f_sk, f_rf = op
        if not refresh_cache:
            eps, _ = run_unet(x, t, 0, None, capture=())
            return eps, f_sk, f_rf
        eps, cap = run_unet(x, t, 0, None, capture=(e_sk, e_rf))
        return eps, cap[e_sk], cap[e_rf]

    def sketch_branch(op):
        x, t, f_sk, f_rf = op
        eps, _ = run_unet(x, t, e_sk, f_sk, capture=())
        return eps, f_sk, f_rf

    def refine_branch(op):
        x, t, f_sk, f_rf = op
        eps, _ = run_unet(x, t, e_rf, f_rf, capture=())
        return eps, f_sk, f_rf

    def step(carry, inp):
        x, pndm, f_sk, f_rf = carry
        t, tp, br = inp
        eps, f_sk, f_rf = jax.lax.switch(
            br, (full_branch, sketch_branch, refine_branch), (x, t, f_sk, f_rf)
        )
        if dcfg.scheduler == "pndm":
            x, pndm = D.pndm_step(sched, pndm, x, eps, t, tp)
        else:
            x = D.ddim_step(sched, x, eps, t, tp)
        return (x, pndm, f_sk, f_rf), None

    pndm0 = D.pndm_init(x_t.shape, x_t.dtype)
    (x0, _, _, _), _ = jax.lax.scan(step, (x_t, pndm0, f_sk0, f_rf0), (ts, t_prev, branches))
    return x0


def denoise_with_capture(
    ucfg: UNetConfig,
    dcfg: DiffusionConfig,
    params: Params,
    x_t: jax.Array,
    ctx_cond: jax.Array,
    ctx_uncond: jax.Array,
    capture_steps: tuple[int, ...],
) -> tuple[jax.Array, list[dict[int, jax.Array]]]:
    """Full sampling with per-timestep feature capture (calibration path).

    Python loop (T is small) so the trajectory can stream to host memory.
    """
    sched = D.make_schedule(dcfg)
    ts = D.sample_timesteps(dcfg)
    b = x_t.shape[0]
    ctx2 = jnp.concatenate([ctx_cond, ctx_uncond], axis=0)

    @jax.jit
    def one(x, pndm, t, tp):
        x2 = jnp.concatenate([x, x], axis=0)
        t2 = jnp.broadcast_to(t, (2 * b,))
        eps2, cap = U.unet_apply(ucfg, params, x2, t2, ctx2, capture_steps=capture_steps)
        e_c, e_u = jnp.split(eps2, 2, axis=0)
        eps = e_u + dcfg.guidance_scale * (e_c - e_u)
        if dcfg.scheduler == "pndm":
            x, pndm = D.pndm_step(sched, pndm, x, eps, t, tp)
        else:
            x = D.ddim_step(sched, x, eps, t, tp)
        return x, pndm, cap

    traj = []
    x = x_t
    pndm = D.pndm_init(x_t.shape, x_t.dtype)
    for i in range(dcfg.timesteps_sample):
        tp = ts[i + 1] if i + 1 < dcfg.timesteps_sample else jnp.int32(-1)
        x, pndm, cap = one(x, pndm, ts[i], tp)
        traj.append({k: jax.device_get(v) for k, v in cap.items()})
    return x, traj
