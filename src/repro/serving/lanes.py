"""Per-lane sampler state for step-level continuous batching.

``core.sampler.pas_denoise`` carries its whole loop state — latent, PNDM
multistep ring, sketch/refine feature caches, branch vector — inside one
``lax.scan``.  Here that carry is lifted into an explicit per-lane
:class:`LaneState` pytree so a serving engine can:

* advance lanes sitting at *heterogeneous* denoise steps in one jitted
  micro-step (one ``lax.switch``-selected U-Net invocation over a compact
  gather of the lanes the step advances, driven by each lane's
  precomputed branch plan),
* admit a new request into a retired lane by scatter (``admit``), and
* read a finished lane's latent by gather (``gather_latent``).

Layout notes
------------
* Lane arrays carry the lane axis first: ``x`` is [N, L, C], the PNDM ring
  is [N, 4, L, C].
* The sketch/refine feature caches keep the CFG-doubled ``[2N, ...]``
  layout of :func:`repro.core.sampler.cfg_unet_step` — rows ``i`` and
  ``N + i`` belong to lane ``i`` — so the batched partial U-Net consumes a
  cache slot without any transpose.
* Per-lane plans are padded to ``max_steps``; ``step[i] < n_steps[i]``
  defines liveness, so the padded tail never executes.  An empty lane has
  ``n_steps == 0`` and all-zero tensors (zeros keep the compute on a
  padding lane NaN-free).
"""
from __future__ import annotations

from typing import Any, Callable, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.common.types import DiffusionConfig, PASPlan, UNetConfig
from repro.core import sampler as SM
from repro.models import diffusion as D
from repro.serving.cache import select_entry_features

Params = dict[str, Any]


class LaneState(NamedTuple):
    """All per-lane sampler state, as one pytree of lane-major arrays."""

    x: jax.Array  # [N, L, C] current latent
    ets: jax.Array  # [N, 4, L, C] PNDM eps ring
    n_ets: jax.Array  # [N] PNDM warmup count
    f_sk: jax.Array  # [2N, L_sk, C_sk] sketch-entry feature cache
    f_rf: jax.Array  # [2N, L_rf, C_rf] refine-entry feature cache
    ctx2: jax.Array  # [2N, ctx_len, ctx_dim] CFG-doubled conditioning (uncond rows 0)
    branches: jax.Array  # [N, max_steps] FULL/SKETCH/REFINE per step
    ts: jax.Array  # [N, max_steps] timestep per step
    t_prev: jax.Array  # [N, max_steps] successor timestep (-1 at the end)
    step: jax.Array  # [N] current step index into the plan
    n_steps: jax.Array  # [N] plan length; 0 marks an empty lane
    thr: jax.Array  # [N, max_steps] per-step cache threshold (quality policy)
    #: [N, L, 1] inpaint mask (1 = generate, 0 = keep the init latent); a
    #: full-ones mask makes the per-step blend structurally the identity,
    #: so txt2img lanes stay bit-exact with the pre-mask micro-step
    mask: jax.Array
    x_init: jax.Array  # [N, L, C] known latent under the mask (zeros if unused)
    noise0: jax.Array  # [N, L, C] fixed noise re-noising the known region

    @property
    def n_lanes(self) -> int:
        return self.x.shape[0]

    def active_mask(self) -> jax.Array:
        return self.step < self.n_steps


class LanePlan(NamedTuple):
    """Host-side padded plan arrays for one request."""

    branches: np.ndarray  # [max_steps] int32
    ts: np.ndarray  # [max_steps] int32
    t_prev: np.ndarray  # [max_steps] int32
    n_steps: int
    #: [max_steps] float32 per-step cache threshold (the quality policy's
    #: per-request resolution; 0 = never reuse, bit-exact by construction)
    thr: np.ndarray = np.zeros((0,), np.float32)


def make_plan_arrays(
    dcfg: DiffusionConfig,
    timesteps: int,
    plan: PASPlan | None,
    max_steps: int,
    threshold: float | Callable[[np.ndarray], np.ndarray] = 0.0,
    base_timesteps: int | None = None,
) -> LanePlan:
    """Precompute one request's branch/timestep vectors, padded to max_steps.

    ``threshold`` is the request's cache-threshold resolution: a scalar, or
    a callable mapping the step's train timesteps to per-step thresholds
    (how the quality policy expresses calibrated per-bucket thresholds).

    ``base_timesteps`` is the img2img truncation: the schedule stride (and
    the train timesteps each step sees) comes from the *base* schedule and
    only its last ``timesteps`` entries execute — ``None`` (or equal to
    ``timesteps``) is the stock untruncated schedule.
    """
    if timesteps > max_steps:
        raise ValueError(f"request wants {timesteps} steps, engine max is {max_steps}")
    base = timesteps if base_timesteps is None else int(base_timesteps)
    if not 1 <= timesteps <= base:
        raise ValueError(
            f"truncated schedule wants {timesteps} of base {base} steps"
        )
    stride = dcfg.timesteps_train // base
    ts = (np.arange(base, dtype=np.int64) * stride)[::-1].astype(np.int32)
    ts = ts[base - timesteps:]
    t_prev = np.concatenate([ts[1:], np.array([-1], np.int32)])
    if plan is None:
        branches = np.full((timesteps,), SM.FULL, np.int32)
    else:
        branches = np.asarray(SM.plan_to_branches(plan, timesteps))
    thr = np.asarray(threshold(ts) if callable(threshold) else
                     np.full((timesteps,), threshold), np.float32)
    if thr.shape != (timesteps,):
        raise ValueError(f"threshold resolver returned shape {thr.shape}, want ({timesteps},)")

    def pad(a: np.ndarray, dtype=np.int32) -> np.ndarray:
        out = np.zeros((max_steps,), dtype)
        out[:timesteps] = a
        return out

    return LanePlan(pad(branches), pad(ts), pad(t_prev), timesteps, pad(thr, np.float32))


def init_lanes(
    ucfg: UNetConfig,
    n_lanes: int,
    max_steps: int,
    e_sk: int,
    e_rf: int,
    dtype=jnp.float32,
) -> LaneState:
    """All-empty lane state (every lane has ``n_steps == 0``)."""
    L = ucfg.latent_size**2
    c = ucfg.in_channels
    z = jnp.zeros
    return LaneState(
        x=z((n_lanes, L, c), dtype),
        ets=z((n_lanes, 4, L, c), dtype),
        n_ets=z((n_lanes,), jnp.int32),
        f_sk=z(SM.feat_shape(ucfg, e_sk, 2 * n_lanes), dtype),
        f_rf=z(SM.feat_shape(ucfg, e_rf, 2 * n_lanes), dtype),
        ctx2=z((2 * n_lanes, ucfg.ctx_len, ucfg.ctx_dim), dtype),
        branches=z((n_lanes, max_steps), jnp.int32),
        ts=z((n_lanes, max_steps), jnp.int32),
        t_prev=z((n_lanes, max_steps), jnp.int32),
        step=z((n_lanes,), jnp.int32),
        n_steps=z((n_lanes,), jnp.int32),
        thr=z((n_lanes, max_steps), jnp.float32),
        mask=jnp.ones((n_lanes, L, 1), dtype),
        x_init=z((n_lanes, L, c), dtype),
        noise0=z((n_lanes, L, c), dtype),
    )


def admit(
    state: LaneState,
    lane: jax.Array,  # scalar int32 lane index (traced: one compile)
    noise: jax.Array,  # [L, C] request's entry latent (noise or seeded init)
    ctx: jax.Array,  # [ctx_len, ctx_dim]
    branches: jax.Array,  # [max_steps]
    ts: jax.Array,  # [max_steps]
    t_prev: jax.Array,  # [max_steps]
    n_steps: jax.Array,  # scalar int32
    thr: jax.Array | None = None,  # [max_steps] per-step cache threshold
    mask: jax.Array | None = None,  # [L, 1] inpaint mask; None = all-ones
    x_init: jax.Array | None = None,  # [L, C] known latent; None = zeros
    noise0: jax.Array | None = None,  # [L, C] known-region noise; None = zeros
) -> LaneState:
    """Scatter one request into an (empty) lane, resetting its sampler state."""
    n = state.n_lanes
    return LaneState(
        x=state.x.at[lane].set(noise),
        ets=state.ets.at[lane].set(0.0),
        n_ets=state.n_ets.at[lane].set(0),
        f_sk=state.f_sk.at[lane].set(0.0).at[n + lane].set(0.0),
        f_rf=state.f_rf.at[lane].set(0.0).at[n + lane].set(0.0),
        ctx2=state.ctx2.at[lane].set(ctx).at[n + lane].set(0.0),
        branches=state.branches.at[lane].set(branches),
        ts=state.ts.at[lane].set(ts),
        t_prev=state.t_prev.at[lane].set(t_prev),
        step=state.step.at[lane].set(0),
        n_steps=state.n_steps.at[lane].set(n_steps),
        thr=state.thr.at[lane].set(0.0 if thr is None else thr),
        mask=state.mask.at[lane].set(1.0 if mask is None else mask),
        x_init=state.x_init.at[lane].set(0.0 if x_init is None else x_init),
        noise0=state.noise0.at[lane].set(0.0 if noise0 is None else noise0),
    )


def release(state: LaneState, lane: jax.Array) -> LaneState:
    """Mark a lane empty (retirement without immediate backfill)."""
    return state._replace(
        step=state.step.at[lane].set(0),
        n_steps=state.n_steps.at[lane].set(0),
    )


def gather_latent(state: LaneState, lane: int) -> jax.Array:
    return state.x[lane]


#: LaneState leaves in the CFG-doubled ``[2N, ...]`` layout
_CFG_ROWS = frozenset({"f_sk", "f_rf", "ctx2"})
#: the leaves a micro-step writes; every other leaf only changes at admission
_STEPPED = ("x", "ets", "n_ets", "f_sk", "f_rf", "step")


def compact_capacity(n_lanes: int) -> int:
    """Lanes per compact micro-step dispatch: ``ceil(n_lanes / 2)``.

    A tick that advances at most this many lanes runs the U-Net on half
    the rows of the whole lane batch; one that advances more takes two
    dispatches, about the work of one whole-batch pass.  One lane gives
    ``K == N``, the whole batch.
    """
    return -(-n_lanes // 2)


def compact_batches(
    advanced: np.ndarray, n_lanes: int, k: int
) -> list[tuple[np.ndarray, np.ndarray]]:
    """Split a tick's advancing lanes into ``(idx [k], valid [k])`` dispatches.

    ``ceil(len(advanced) / k)`` dispatches, each of ``k`` distinct lanes:
    up to ``k`` advancing lanes (``valid``), padded with lanes the tick
    does not advance, lowest-numbered first.  A padding lane is gathered
    and written back unchanged, so it must not be one the same dispatch
    advances (duplicate scatter indices have no defined order).  With an
    even lane count the non-advancing lanes always suffice; with an odd
    one the last dispatch may also pad with a lane an earlier dispatch of
    the tick advanced, which it then writes back as that dispatch left it.
    """
    adv = {int(lane) for lane in advanced}
    out = []
    for lo in range(0, len(advanced), k):
        chunk = [int(lane) for lane in advanced[lo : lo + k]]
        others = sorted(set(range(n_lanes)) - set(chunk), key=lambda lane: (lane in adv, lane))
        valid = np.zeros((k,), bool)
        valid[: len(chunk)] = True
        out.append((np.asarray(chunk + others[: k - len(chunk)], np.int32), valid))
    return out


def _gather_lanes(state: LaneState, idx: jax.Array) -> LaneState:
    """The ``K`` lanes ``idx`` as a K-lane :class:`LaneState` (their CFG
    rows ``idx`` and ``N + idx``)."""
    rows = jnp.concatenate([idx, state.n_lanes + idx])
    return LaneState(*(
        leaf[rows] if name in _CFG_ROWS else leaf[idx]
        for name, leaf in zip(LaneState._fields, state)
    ))


def _scatter_lanes(state: LaneState, idx: jax.Array, sub: LaneState) -> LaneState:
    """Write a gathered K-lane state's stepped leaves back to lanes ``idx``
    (``idx`` distinct)."""
    rows = jnp.concatenate([idx, state.n_lanes + idx])
    return state._replace(**{
        name: getattr(state, name).at[rows if name in _CFG_ROWS else idx].set(
            getattr(sub, name)
        )
        for name in _STEPPED
    })


def make_micro_step(
    ucfg: UNetConfig,
    dcfg: DiffusionConfig,
    e_sk: int,
    e_rf: int,
    *,
    cached: bool = False,
    backend=None,
):
    """Build the jitted continuous-batching micro-step.

    The returned function advances, by exactly one denoise step, the lanes
    of one compact dispatch: ``idx`` [K] distinct lane indices, of which
    those with ``valid`` [K] set advance (the host's advance choice: lanes
    whose *effective* branch class equals the scalar ``b_star`` chosen by
    the packing policy).  It gathers those K lanes — 2K CFG rows — runs
    one ``lax.switch``-selected U-Net invocation over them, and scatters
    the new state back where ``valid``; padding lanes (``valid`` false)
    and every lane outside ``idx`` are carried through untouched.  The
    engine splits a tick's advancing lanes into ``ceil(n / K)`` such
    dispatches (:func:`compact_batches`), so the U-Net runs on the rows a
    tick advances rather than on the whole lane batch.  The advance
    choice comes from the host because the cache-aware engine may
    *demote* a lane's planned FULL step to SKETCH, which the device-side
    plan alone cannot see.

    ``cached=False`` — signature ``(state, params, b_star, idx, valid)``:
    partial branches consume the lane's own captured features.

    ``cached=True`` — signature ``(state, params, b_star, idx, valid,
    feat_src, feat_dist, cache)``: ``feat_src`` is a per-lane [N] int32
    slot index into the device-resident feature cache (-1 = own features)
    and ``feat_dist`` [N] the probed slot's prompt-signature distance; the
    slot is consumed only where ``feat_dist`` is *strictly* below the
    lane's per-step threshold leaf (``state.thr`` — the quality policy's
    per-request resolution, so the quality comparison happens on device,
    not against a python scalar).  The partial branches consume the
    selected entry; on a SKETCH step the selection also becomes the lane's
    sketch/refine cache (a demoted FULL skipped its own refresh, so the
    slot is its feature source of record), while a REFINE step consumes it
    for that step only and leaves the lane's own captures in place.  With
    ``feat_src`` all -1 (or a threshold-0 lane, for which the strict
    inequality never passes) the selection is an exact passthrough — the
    cache-enabled micro-step with no hits is bit-identical to
    ``cached=False`` (the golden-latent harness pins this).

    The step returns only the new state (no per-step host readback): the
    advance choice is deterministic from the host-known plans + cache
    metadata, so the engine mirrors it host-side and the device stays on
    the async-dispatch fast path.  The input state is donated — callers
    must drop their reference.

    ``backend`` selects the kernel backend (``repro.models.backend``) for
    every U-Net invocation; it is resolved once here and captured in the
    jitted closure — never a traced value.  The weights, by contrast, are
    an argument and never a closure constant: folded into the program, an
    sd_v14 U-Net's 1.7 GB of weights are copied through every compile.
    """
    from repro.models.backend import resolve_backend

    bk = resolve_backend(backend)
    sched = D.make_schedule(dcfg)
    guidance = dcfg.guidance_scale
    use_pndm = dcfg.scheduler == "pndm"

    def _body(
        state: LaneState,  # the K gathered lanes
        params: Params,
        b_star: jax.Array,
        sel: jax.Array,  # [K] bool: which gathered lanes advance
        entry_sk: jax.Array,  # [2K, ...] features the SKETCH branch consumes
        entry_rf: jax.Array,  # [2K, ...] features the REFINE branch consumes
    ) -> LaneState:
        idx = jnp.minimum(state.step, state.branches.shape[1] - 1)
        take = lambda a: jnp.take_along_axis(a, idx[:, None], axis=1)[:, 0]
        t = take(state.ts)
        tp = take(state.t_prev)
        ctx2 = state.ctx2

        def full_branch(_):
            eps, cap = SM.cfg_unet_step(
                ucfg, params, guidance, state.x, t, ctx2, capture=(e_sk, e_rf),
                backend=bk,
            )
            return eps, cap[e_sk], cap[e_rf]

        def sketch_branch(_):
            eps, _ = SM.cfg_unet_step(
                ucfg, params, guidance, state.x, t, ctx2,
                entry_step=e_sk, entry_feat=entry_sk, backend=bk,
            )
            return eps, entry_sk, entry_rf

        def refine_branch(_):
            eps, _ = SM.cfg_unet_step(
                ucfg, params, guidance, state.x, t, ctx2,
                entry_step=e_rf, entry_feat=entry_rf, backend=bk,
            )
            # a REFINE step never becomes the lane's feature source of
            # record: a SKETCH->REFINE demotion consumes the slot for THIS
            # step only, keeping the lane's own last-FULL captures for its
            # later partial steps (each of which re-checks its own
            # threshold) — unlike a demoted FULL, which skipped the refresh
            # and so adopts the slot as its sketch/refine cache
            return eps, state.f_sk, state.f_rf

        eps, f_sk_new, f_rf_new = jax.lax.switch(
            jnp.clip(b_star, 0, 2), (full_branch, sketch_branch, refine_branch), None
        )

        if use_pndm:
            x_new, ets_new, n_new = D.pndm_step_batched(
                sched, state.ets, state.n_ets, state.x, eps, t, tp
            )
        else:
            x_new = D.ddim_step_batched(sched, state.x, eps, t, tp)
            ets_new, n_new = state.ets, state.n_ets

        # inpaint blend: re-noise each lane's known region to its own target
        # timestep and keep it where the mask is 0.  jnp.where selects the
        # denoised latent *exactly* where mask >= 1, so txt2img lanes (all-
        # ones mask) are structurally untouched by this step.
        ab = jnp.where(tp >= 0, sched.alphas_cumprod[jnp.maximum(tp, 0)], 1.0)
        ab = ab[:, None, None]
        known = jnp.sqrt(ab) * state.x_init + jnp.sqrt(1.0 - ab) * state.noise0
        x_new = jnp.where(
            state.mask >= 1.0, x_new, state.mask * x_new + (1.0 - state.mask) * known
        )

        m3 = sel[:, None, None]
        sel2 = jnp.concatenate([sel, sel], axis=0)[:, None, None]
        return state._replace(
            x=jnp.where(m3, x_new, state.x),
            ets=jnp.where(sel[:, None, None, None], ets_new, state.ets),
            n_ets=jnp.where(sel, n_new, state.n_ets),
            f_sk=jnp.where(sel2, f_sk_new, state.f_sk),
            f_rf=jnp.where(sel2, f_rf_new, state.f_rf),
            step=state.step + sel.astype(jnp.int32),
        )

    if not cached:

        def micro_step(
            state: LaneState, params: Params, b_star: jax.Array, idx: jax.Array,
            valid: jax.Array,
        ) -> LaneState:
            sub = _gather_lanes(state, idx)
            return _scatter_lanes(
                state, idx, _body(sub, params, b_star, valid, sub.f_sk, sub.f_rf)
            )

        return jax.jit(micro_step, donate_argnums=(0,))

    def micro_step_cached(
        state: LaneState,
        params: Params,
        b_star: jax.Array,
        idx: jax.Array,
        valid: jax.Array,
        feat_src: jax.Array,  # [N] int32 cache slot per lane, -1 = own
        feat_dist: jax.Array,  # [N] f32 probed slot signature distance (inf = none)
        cache,  # CacheState pytree of [S, 2, ...] slots
    ) -> LaneState:
        sub = _gather_lanes(state, idx)
        at = jnp.minimum(sub.step, sub.thr.shape[1] - 1)
        thr_t = jnp.take_along_axis(sub.thr, at[:, None], axis=1)[:, 0]
        src = feat_src[idx]
        # strict inequality against the lane's own threshold leaf: a
        # threshold-0 lane can never consume a slot, whatever the host says
        use = (src >= 0) & (feat_dist[idx] < thr_t)
        entry_sk = select_entry_features(sub.f_sk, cache.f_sk, src, use)
        entry_rf = select_entry_features(sub.f_rf, cache.f_rf, src, use)
        return _scatter_lanes(
            state, idx, _body(sub, params, b_star, valid, entry_sk, entry_rf)
        )

    return jax.jit(micro_step_cached, donate_argnums=(0,))


# ---------------------------------------------------------------------------
# Mesh-sharded lanes: contiguous lane shards on a ("data",) mesh.
#
# The sharded engine partitions its lane axis over the devices of a
# :func:`repro.common.sharding.lane_mesh`: device ``d`` owns lanes
# ``[d * P, (d + 1) * P)`` with ``P = n_lanes // n_shards``.  Two layout
# changes versus :class:`LaneState` make every per-lane tensor shard
# cleanly on its *leading* axis:
#
# * the CFG-doubled ``[2N, ...]`` arrays become ``[N, 2, ...]`` (pair axis
#   second: index 0 = cond, 1 = uncond), so a lane's cond/uncond pair
#   always lives on the lane's own device, and
# * the prompt conditioning is stored per-lane as ``ctx [N, 2, ...]``.
#
# The micro-step is ONE jitted GSPMD program built with ``shard_map``:
# each shard runs the branch ``lax.switch`` on its *own* scalar branch
# class, so shard A can execute a FULL U-Net batch while shard B executes
# SKETCH in the same program — no collectives appear in the body (the
# U-Net, scheduler step and cache gather are all lane-local), which is
# what lets per-shard control flow coexist with SPMD.
# ---------------------------------------------------------------------------


class ShardedLaneState(NamedTuple):
    """Per-lane sampler state with every leaf lane-major on axis 0.

    Identical information content to :class:`LaneState`; the CFG pair axis
    moves from row-blocked ``[2N]`` to ``[N, 2]`` so the whole pytree
    shards over the lane axis with a single ``P("data")`` spec.
    """

    x: jax.Array  # [N, L, C] current latent
    ets: jax.Array  # [N, 4, L, C] PNDM eps ring
    n_ets: jax.Array  # [N] PNDM warmup count
    f_sk: jax.Array  # [N, 2, L_sk, C_sk] sketch-entry features (cond, uncond)
    f_rf: jax.Array  # [N, 2, L_rf, C_rf] refine-entry features
    ctx: jax.Array  # [N, 2, ctx_len, ctx_dim] conditioning (uncond rows zero)
    branches: jax.Array  # [N, max_steps]
    ts: jax.Array  # [N, max_steps]
    t_prev: jax.Array  # [N, max_steps]
    step: jax.Array  # [N]
    n_steps: jax.Array  # [N]
    thr: jax.Array  # [N, max_steps] per-step cache threshold (quality policy)
    mask: jax.Array  # [N, L, 1] inpaint mask (1 = generate; all-ones = identity)
    x_init: jax.Array  # [N, L, C] known latent under the mask (zeros if unused)
    noise0: jax.Array  # [N, L, C] fixed noise re-noising the known region

    @property
    def n_lanes(self) -> int:
        return self.x.shape[0]

    def active_mask(self) -> jax.Array:
        return self.step < self.n_steps


def init_sharded_lanes(
    ucfg: UNetConfig,
    n_lanes: int,
    max_steps: int,
    e_sk: int,
    e_rf: int,
    mesh,
    dtype=jnp.float32,
) -> ShardedLaneState:
    """All-empty lane state, placed shard-by-shard over the lane mesh."""
    from repro.common.sharding import lane_sharding

    n_shards = mesh.shape["data"]
    if n_lanes % n_shards != 0:
        raise ValueError(f"n_lanes={n_lanes} must divide over {n_shards} shards")
    L = ucfg.latent_size**2
    c = ucfg.in_channels
    sk = SM.feat_shape(ucfg, e_sk, 1)[1:]
    rf = SM.feat_shape(ucfg, e_rf, 1)[1:]
    sh = lane_sharding(mesh)
    z = lambda shape, dt=dtype: jax.device_put(jnp.zeros(shape, dt), sh)
    return ShardedLaneState(
        x=z((n_lanes, L, c)),
        ets=z((n_lanes, 4, L, c)),
        n_ets=z((n_lanes,), jnp.int32),
        f_sk=z((n_lanes, 2) + sk),
        f_rf=z((n_lanes, 2) + rf),
        ctx=z((n_lanes, 2, ucfg.ctx_len, ucfg.ctx_dim)),
        branches=z((n_lanes, max_steps), jnp.int32),
        ts=z((n_lanes, max_steps), jnp.int32),
        t_prev=z((n_lanes, max_steps), jnp.int32),
        step=z((n_lanes,), jnp.int32),
        n_steps=z((n_lanes,), jnp.int32),
        thr=z((n_lanes, max_steps), jnp.float32),
        mask=jax.device_put(jnp.ones((n_lanes, L, 1), dtype), sh),
        x_init=z((n_lanes, L, c)),
        noise0=z((n_lanes, L, c)),
    )


def make_sharded_admit(mesh):
    """Jitted single-request scatter that preserves lane shardings."""
    from repro.common.sharding import lane_sharding

    sh = lane_sharding(mesh)

    def admit_sharded(
        state: ShardedLaneState,
        lane: jax.Array,
        noise: jax.Array,
        ctx: jax.Array,
        branches: jax.Array,
        ts: jax.Array,
        t_prev: jax.Array,
        n_steps: jax.Array,
        thr: jax.Array | None = None,
        mask: jax.Array | None = None,  # [L, 1] inpaint mask; None = all-ones
        x_init: jax.Array | None = None,  # [L, C] known latent; None = zeros
        noise0: jax.Array | None = None,  # [L, C] known-region noise; None = zeros
    ) -> ShardedLaneState:
        return ShardedLaneState(
            x=state.x.at[lane].set(noise),
            ets=state.ets.at[lane].set(0.0),
            n_ets=state.n_ets.at[lane].set(0),
            f_sk=state.f_sk.at[lane].set(0.0),
            f_rf=state.f_rf.at[lane].set(0.0),
            ctx=state.ctx.at[lane, 0].set(ctx).at[lane, 1].set(0.0),
            branches=state.branches.at[lane].set(branches),
            ts=state.ts.at[lane].set(ts),
            t_prev=state.t_prev.at[lane].set(t_prev),
            step=state.step.at[lane].set(0),
            n_steps=state.n_steps.at[lane].set(n_steps),
            thr=state.thr.at[lane].set(0.0 if thr is None else thr),
            mask=state.mask.at[lane].set(1.0 if mask is None else mask),
            x_init=state.x_init.at[lane].set(0.0 if x_init is None else x_init),
            noise0=state.noise0.at[lane].set(0.0 if noise0 is None else noise0),
        )

    return jax.jit(admit_sharded, donate_argnums=(0,), out_shardings=sh)


def make_sharded_release(mesh):
    from repro.common.sharding import lane_sharding

    sh = lane_sharding(mesh)

    def release_sharded(state: ShardedLaneState, lane: jax.Array) -> ShardedLaneState:
        return state._replace(
            step=state.step.at[lane].set(0),
            n_steps=state.n_steps.at[lane].set(0),
        )

    return jax.jit(release_sharded, donate_argnums=(0,), out_shardings=sh)


def _select_local(
    own: jax.Array, slots: jax.Array, src: jax.Array, use: jax.Array | None = None
) -> jax.Array:
    """Shard-local captured-vs-cached selection in the [P, 2, ...] layout.

    ``own`` [P, 2, L, C] lane features, ``slots`` [S_local, 2, L, C] the
    shard's cache ring, ``src`` [P] local slot per lane (-1 = own), ``use``
    an optional per-lane consume mask (defaults to ``src >= 0``) — the
    sharded micro-step passes the device-side threshold comparison here.
    Exact passthrough when nothing is used (the sharded golden test pins
    this).
    """
    pick = slots[jnp.clip(src, 0, slots.shape[0] - 1)]  # [P, 2, L, C]
    if use is None:
        use = src >= 0
    return jnp.where(use[:, None, None, None], pick, own)


def make_sharded_micro_step(
    ucfg: UNetConfig,
    dcfg: DiffusionConfig,
    e_sk: int,
    e_rf: int,
    mesh,
    *,
    cached: bool = False,
    backend=None,
):
    """Build the jitted mesh-sharded micro-step (one GSPMD program).

    Signature (``cached=False``): ``(state, params, b_arr, sel)`` where
    ``b_arr`` is a per-*shard* ``[n_shards]`` int32 branch-class vector —
    each device switches on its own scalar, so different shards execute
    different branch classes in the same program — and ``sel`` is the
    host-mirrored per-lane advance mask (a lane advances iff its
    *effective* class equals its shard's chosen class).

    ``cached=True`` adds ``(feat_src, feat_dist, cache)``: ``feat_src``
    [n_lanes] int32 holds *shard-local* slot indices (-1 = own features),
    ``feat_dist`` [n_lanes] f32 the probed slots' signature distances —
    consumed only strictly below the lane's per-step ``state.thr``
    threshold leaf, mirroring the single-device micro-step — and ``cache``
    is the sharded :class:`~repro.serving.cache.CacheState` whose slot
    axis is partitioned over the same mesh, so the feature gather never
    leaves the shard.

    ``params`` are passed explicitly (replicated spec) rather than closed
    over so the shard_map body stays closure-free over device arrays.

    ``backend`` selects the kernel backend for every U-Net invocation,
    resolved once at build time exactly as in :func:`make_micro_step`.
    """
    from jax.sharding import PartitionSpec as P

    from repro.models.backend import resolve_backend

    bk = resolve_backend(backend)
    sched = D.make_schedule(dcfg)
    guidance = dcfg.guidance_scale
    use_pndm = dcfg.scheduler == "pndm"

    def local_body(params, state, b_local, sel, entry_sk, entry_rf):
        # everything here is shard-local: P lanes, no collectives
        p = state.x.shape[0]
        idx = jnp.minimum(state.step, state.branches.shape[1] - 1)
        take = lambda a: jnp.take_along_axis(a, idx[:, None], axis=1)[:, 0]
        t = take(state.ts)
        tp = take(state.t_prev)
        ctx2 = jnp.concatenate([state.ctx[:, 0], state.ctx[:, 1]], axis=0)
        pair2 = lambda a: jnp.concatenate([a[:, 0], a[:, 1]], axis=0)  # [P,2,..]->[2P,..]
        unpair = lambda a: jnp.stack([a[:p], a[p:]], axis=1)  # [2P,..]->[P,2,..]

        def full_branch(_):
            eps, cap = SM.cfg_unet_step(
                ucfg, params, guidance, state.x, t, ctx2, capture=(e_sk, e_rf),
                backend=bk,
            )
            return eps, unpair(cap[e_sk]), unpair(cap[e_rf])

        def sketch_branch(_):
            eps, _ = SM.cfg_unet_step(
                ucfg, params, guidance, state.x, t, ctx2,
                entry_step=e_sk, entry_feat=pair2(entry_sk), backend=bk,
            )
            return eps, entry_sk, entry_rf

        def refine_branch(_):
            eps, _ = SM.cfg_unet_step(
                ucfg, params, guidance, state.x, t, ctx2,
                entry_step=e_rf, entry_feat=pair2(entry_rf), backend=bk,
            )
            # as in the single-device micro-step: a (possibly demoted)
            # REFINE step consumes the entry features for this step only —
            # the lane's own captures stay its feature source of record
            return eps, state.f_sk, state.f_rf

        eps, f_sk_new, f_rf_new = jax.lax.switch(
            jnp.clip(b_local[0], 0, 2), (full_branch, sketch_branch, refine_branch), None
        )

        if use_pndm:
            x_new, ets_new, n_new = D.pndm_step_batched(
                sched, state.ets, state.n_ets, state.x, eps, t, tp
            )
        else:
            x_new = D.ddim_step_batched(sched, state.x, eps, t, tp)
            ets_new, n_new = state.ets, state.n_ets

        # inpaint blend — shard-local, same formula as the single-device
        # micro-step; jnp.where keeps all-ones-mask lanes structurally exact
        ab = jnp.where(tp >= 0, sched.alphas_cumprod[jnp.maximum(tp, 0)], 1.0)
        ab = ab[:, None, None]
        known = jnp.sqrt(ab) * state.x_init + jnp.sqrt(1.0 - ab) * state.noise0
        x_new = jnp.where(
            state.mask >= 1.0, x_new, state.mask * x_new + (1.0 - state.mask) * known
        )

        m3 = sel[:, None, None]
        m4 = sel[:, None, None, None]
        return state._replace(
            x=jnp.where(m3, x_new, state.x),
            ets=jnp.where(m4, ets_new, state.ets),
            n_ets=jnp.where(sel, n_new, state.n_ets),
            f_sk=jnp.where(m4, f_sk_new, state.f_sk),
            f_rf=jnp.where(m4, f_rf_new, state.f_rf),
            step=state.step + sel.astype(jnp.int32),
        )

    lane = P("data")
    repl = P()

    if not cached:

        def shard_body(params, state, b_arr, sel):
            entry_sk, entry_rf = state.f_sk, state.f_rf
            return local_body(params, state, b_arr, sel, entry_sk, entry_rf)

        mapped = jax.shard_map(
            shard_body, mesh=mesh,
            in_specs=(repl, lane, lane, lane),
            out_specs=lane,
            check_vma=False,
        )

        def micro_step(state, params, b_arr, sel):
            return mapped(params, state, b_arr, sel)

        return jax.jit(micro_step, donate_argnums=(0,))

    def shard_body_cached(params, state, b_arr, sel, feat_src, feat_dist, cache):
        idx = jnp.minimum(state.step, state.thr.shape[1] - 1)
        thr_t = jnp.take_along_axis(state.thr, idx[:, None], axis=1)[:, 0]
        use = (feat_src >= 0) & (feat_dist < thr_t)
        entry_sk = _select_local(state.f_sk, cache.f_sk, feat_src, use)
        entry_rf = _select_local(state.f_rf, cache.f_rf, feat_src, use)
        return local_body(params, state, b_arr, sel, entry_sk, entry_rf)

    mapped_cached = jax.shard_map(
        shard_body_cached, mesh=mesh,
        in_specs=(repl, lane, lane, lane, lane, lane, lane),
        out_specs=lane,
        check_vma=False,
    )

    def micro_step_cached(state, params, b_arr, sel, feat_src, feat_dist, cache):
        return mapped_cached(params, state, b_arr, sel, feat_src, feat_dist, cache)

    return jax.jit(micro_step_cached, donate_argnums=(0,))
