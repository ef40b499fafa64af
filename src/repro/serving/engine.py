"""Continuous-batching diffusion serving engine (+ static lockstep baseline).

The engine advances a fixed set of *lanes* through the PAS denoise loop one
micro-step at a time.  Lanes hold requests at heterogeneous denoise steps;
each micro-step (tick) executes one branch class (FULL / SKETCH / REFINE)
chosen by the packing policy, on the lanes that class advances only: they
are compacted into dispatches of ``ceil(n_lanes / 2)`` lanes, so a tick
that advances few lanes runs the U-Net on half the rows of the whole
batch, and one that advances most of them about the work of one
whole-batch step.  Lanes retire through the VAE decoder the moment their
own schedule finishes and are immediately backfilled from the admission
queue — no lane ever waits for a batch-mate (the lockstep waste
``serve_static`` below exists to measure).

Requests may differ in step count and in phase boundaries (``t_sketch``,
``t_complete``, ``t_sparse``) — the branch *plan* is per-lane.  The feature
-cache geometry (``l_sketch``, ``l_refine``) is engine-level, because cache
slot shapes must be static under jit; requests either match it or run
all-FULL (``plan=None``).
"""
from __future__ import annotations

import dataclasses
import functools
import time
from typing import Any, Callable, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.common import sharding as SH
from repro.common.types import DiffusionConfig, PASPlan, UNetConfig
from repro.core import sampler as SM
from repro.models import diffusion as D
from repro.models import unet as U
from repro.models import vae as V
from repro.serving import lanes as LN
from repro.serving.cache import FeatureCache, ShardedFeatureCache, prompt_signature
from repro.serving.metrics import CLASSES, ServingMetrics
from repro.serving.policy import ResolvedPolicy
from repro.serving.scheduler import FIFOScheduler

Params = dict[str, Any]


# ---------------------------------------------------------------------------
# Requests
# ---------------------------------------------------------------------------


@dataclasses.dataclass(eq=False)  # identity semantics: queues remove by object
class GenRequest:
    """One conditioned generation request (txt2img, img2img, or inpaint).

    ``timesteps`` is always the *executed* step count.  An img2img request
    additionally carries ``base_timesteps`` (the untruncated schedule the
    stride comes from — ``timesteps < base_timesteps`` is a strength
    truncation) and ``init_latent`` (the known image, noised to the entry
    timestep at submission).  An inpaint request carries ``mask`` (1 =
    generate, 0 = keep ``init_latent``; blended every micro-step).
    """

    rid: int
    ctx: np.ndarray  # [ctx_len, ctx_dim] prompt embedding
    noise: np.ndarray  # [L, C] initial latent noise
    timesteps: int
    plan: PASPlan | None = None
    arrival_s: float = 0.0  # offset from stream start
    #: opt-out for quality-critical requests: never serve this request's
    #: FULL steps from cached features (neither another request's slots nor
    #: its own intra-mode captures) — every planned FULL step runs in full
    allow_cache: bool = True
    #: per-request quality resolution (``repro.serving.policy``); carries
    #: the cache-threshold decision threaded down to the jitted micro-step.
    #: None = legacy request: the engine-global threshold applies.
    policy: ResolvedPolicy | None = None
    #: [L, C] known latent for img2img/inpaint; None = txt2img (pure noise)
    init_latent: np.ndarray | None = None
    #: [L] or [L, 1] inpaint mask in [0, 1] (1 = generate); None = no mask
    mask: np.ndarray | None = None
    #: untruncated schedule length; None = ``timesteps`` (no truncation)
    base_timesteps: int | None = None
    #: [added_dim] added conditioning of a U-Net that has one (SDXL's
    #: ``text_time``: the pooled text embedding, then the time ids)
    added: np.ndarray | None = None

    _lane_plan: LN.LanePlan | None = dataclasses.field(default=None, repr=False)
    _sig: np.ndarray | None = dataclasses.field(default=None, repr=False)
    #: [L, C] lane entry latent: the seeded+noised init for truncated
    #: img2img, else ``noise`` (set at submission)
    _entry: np.ndarray | None = dataclasses.field(default=None, repr=False)

    def branch_vector(self) -> np.ndarray:
        assert self._lane_plan is not None, "request not yet submitted"
        return self._lane_plan.branches[: self.timesteps]

    @property
    def sched_offset(self) -> int:
        """Schedule-truncation cache key: base minus executed steps (0 for
        the stock schedule) — warm hits never cross different offsets."""
        base = self.timesteps if self.base_timesteps is None else self.base_timesteps
        return base - self.timesteps

    @property
    def quality_tier(self) -> str:
        """Resolved tier label ("full"/"pas" for legacy requests)."""
        if self.policy is not None:
            return self.policy.tier
        return "pas" if self.plan is not None else "full"

    @property
    def refine_demotions(self) -> bool:
        return self.policy is not None and self.policy.refine_demotions


@dataclasses.dataclass
class CompletedRequest:
    rid: int
    latent: np.ndarray
    image: np.ndarray | None
    submitted_s: float
    admitted_s: float
    completed_s: float

    @property
    def latency_s(self) -> float:
        return self.completed_s - self.submitted_s

    @property
    def queue_wait_s(self) -> float:
        return self.admitted_s - self.submitted_s


# ---------------------------------------------------------------------------
# Engine
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class EngineConfig:
    n_lanes: int = 4
    max_steps: int = 64
    l_sketch: int = 3  # feature-cache geometry (see module docstring)
    l_refine: int = 2
    decode_images: bool = True
    # -- cross-request feature cache (repro.serving.cache) -------------------
    #: "off" | "intra" (hits restricted to the same request — DeepCache-style
    #: self reuse) | "cross" (any request's warm slots)
    cache_mode: str = "off"
    cache_slots: int = 16
    #: shift-score-style relative distance bound on prompt signatures; hits
    #: require distance *strictly* below it, so 0.0 never hits (bit-exact).
    #: This is only the *default* the quality policy resolves per request —
    #: a request carrying a ``GenRequest.policy`` brings its own (possibly
    #: per-timestep-bucket) thresholds, stored per lane-step on device.
    cache_threshold: float = 0.15
    #: timestep bucket width in train-timestep units
    cache_t_bucket: int = 125
    #: never demote a lane's first ``cache_min_step`` plan steps (protects
    #: the PNDM warmup / the paper's semantic-planning phase)
    cache_min_step: int = 1
    #: host-RAM spill tier under the HBM slot ring, in megabytes: ring
    #: evictions demote their features to a byte-capped host LRU
    #: (float32-lossless) and admission prefetches spill-resident matches
    #: back onto the device ring before the lane's first planned FULL
    #: step.  0 disables the tier (evictions drop captures, exactly the
    #: pre-spill behaviour)
    cache_spill_mb: float = 0.0
    #: admission-time warmth migration from gossiped slot keys: the
    #: sharded engine redirects a queued request to the shard whose ring
    #: would serve its FULL steps (instead of the emptiest shard), and the
    #: replica router scores replicas on incrementally-gossiped key tables
    #: (``GET /cache/keys``) instead of full per-probe ``/stats`` polls
    cache_gossip: bool = True
    #: lane shards over a ``("data",)`` device mesh; 1 = single-device
    #: engine (exactly the pre-sharding behaviour), N > 1 = mesh-sharded
    #: engine (``ShardedDiffusionEngine``) with ``n_lanes / N`` lanes and
    #: ``cache_slots`` feature slots per shard
    n_shards: int = 1
    #: kernel backend for the jitted hot path (micro-steps + VAE decode):
    #: "xla" routes through the inline reference ops (bit-identical traced
    #: program to pre-dispatch engines), "pallas" through
    #: ``repro.kernels.KERNEL_REGISTRY`` (interpret mode off-TPU).  Resolved
    #: once at engine build — never per request.
    backend: str = "xla"
    # -- construction-level fields --------------------------------------------
    # Read by `repro.serving.config` when it builds the full serving stack
    # (model init, policy, scheduler, HTTP admission); the engine itself only
    # consumes the lane/cache/backend geometry above.
    #: model/config ref resolved via ``repro.models.unet.get_unet_config``
    unet: str = "sd_toy"
    #: parameter-init PRNG seed
    seed: int = 0
    #: default quality tier for requests that don't carry one (None = the
    #: policy's own default)
    quality: str | None = None
    #: shift-score profile path for the cache policy (None = built-in)
    profile: str | None = None
    #: ``PlanAwareScheduler`` alignment window
    window: int = 4
    #: HTTP admission bound (driver-level, not an engine concern)
    max_inflight: int = 32

    def __post_init__(self):
        if self.cache_mode not in ("off", "intra", "cross"):
            raise ValueError(f"cache_mode must be off|intra|cross, got {self.cache_mode!r}")
        if self.n_shards < 1:
            raise ValueError("n_shards must be >= 1")
        if self.n_lanes % self.n_shards != 0:
            raise ValueError(
                f"n_lanes={self.n_lanes} must divide evenly over n_shards={self.n_shards}"
            )
        if self.backend not in ("xla", "pallas"):
            raise ValueError(f"backend must be xla|pallas, got {self.backend!r}")
        if self.cache_spill_mb < 0:
            raise ValueError("cache_spill_mb must be >= 0")


class DiffusionEngine:
    #: summary tag; the mesh-sharded subclass overrides it
    _mode_name = "continuous"

    def __init__(
        self,
        ucfg: UNetConfig,
        dcfg: DiffusionConfig,
        params: Params,
        vae_params: Params | None = None,
        config: EngineConfig = EngineConfig(),
        scheduler: FIFOScheduler | None = None,
    ):
        n_up = U.n_up_steps(ucfg)
        if not (0 < config.l_refine <= config.l_sketch <= n_up):
            raise ValueError("engine cache geometry violates 0 < l_refine <= l_sketch <= n_up")
        self.ucfg, self.dcfg, self.config = ucfg, dcfg, config
        self.e_sk = n_up - config.l_sketch
        self.e_rf = n_up - config.l_refine
        self.scheduler = scheduler if scheduler is not None else FIFOScheduler()
        self.metrics = ServingMetrics()
        #: BasicTransformerBlocks one row's pass runs, by branch class
        self._tf_blocks = tuple(U.tf_blocks(ucfg, e) for e in (0, self.e_sk, self.e_rf))

        self._build_device_state(params)  # sets self.cache/_state/_micro/_admit
        if hasattr(self.scheduler, "attach_cache"):
            self.scheduler.attach_cache(self.cache)
        self._decoder = None
        if vae_params is not None and config.decode_images:
            lhw = (ucfg.latent_size, ucfg.latent_size)
            self._decoder = jax.jit(
                lambda z: V.vae_decode(vae_params, z, lhw, backend=config.backend)
            )

        # host mirrors (device round-trips per micro-step stay O(n_lanes))
        n = config.n_lanes
        self._lane_req: list[GenRequest | None] = [None] * n
        self._lane_step = np.zeros((n,), np.int64)
        self._lane_admit_s = np.zeros((n,), np.float64)
        self._stall = np.zeros((n,), np.int64)

    def _build_device_state(self, params: Params) -> None:
        """Construct the feature cache, lane state and jitted step/admit
        functions (the mesh-sharded engine overrides exactly this)."""
        config, ucfg = self.config, self.ucfg
        self.cache: FeatureCache | None = None
        if config.cache_mode != "off":
            self.cache = FeatureCache(
                ucfg, self.e_sk, self.e_rf,
                n_slots=config.cache_slots,
                threshold=config.cache_threshold,
                t_bucket=config.cache_t_bucket,
                mode=config.cache_mode,
                spill_mb=config.cache_spill_mb,
            )
        self._state = LN.init_lanes(
            ucfg, config.n_lanes, config.max_steps, self.e_sk, self.e_rf
        )
        self._params = params
        self._micro = LN.make_micro_step(
            ucfg, self.dcfg, self.e_sk, self.e_rf,
            cached=self.cache is not None, backend=config.backend,
        )
        #: lanes per compact micro-step dispatch (``LN.compact_capacity``)
        self._k = LN.compact_capacity(config.n_lanes)
        self._admit = jax.jit(LN.admit, donate_argnums=(0,))

    # -- submission ---------------------------------------------------------

    def submit(self, req: GenRequest) -> None:
        if req.plan is not None:
            req.plan.validate(req.timesteps, U.n_up_steps(self.ucfg))
            if (req.plan.l_sketch, req.plan.l_refine) != (
                self.config.l_sketch,
                self.config.l_refine,
            ):
                raise ValueError(
                    "request plan cache geometry (l_sketch, l_refine) = "
                    f"({req.plan.l_sketch}, {req.plan.l_refine}) does not match "
                    f"engine ({self.config.l_sketch}, {self.config.l_refine})"
                )
        threshold = (
            self.config.cache_threshold
            if req.policy is None
            else req.policy.threshold_spec(self.config.cache_threshold)
        )
        base = req.timesteps if req.base_timesteps is None else int(req.base_timesteps)
        req._lane_plan = LN.make_plan_arrays(
            self.dcfg, req.timesteps, req.plan, self.config.max_steps,
            threshold=threshold, base_timesteps=base,
        )
        L, c = req.noise.shape
        if req.mask is not None:
            m = np.asarray(req.mask, np.float32)
            if m.ndim == 1:
                m = m[:, None]
            if m.shape != (L, 1):
                raise ValueError(
                    f"mask shape {np.asarray(req.mask).shape} does not match "
                    f"latent [{L}] (want [{L}] or [{L}, 1])"
                )
            if float(m.min()) < 0.0 or float(m.max()) > 1.0:
                raise ValueError("mask values must lie in [0, 1]")
            req.mask = m
        if req.init_latent is not None and np.asarray(req.init_latent).shape != (L, c):
            raise ValueError(
                f"init latent shape {np.asarray(req.init_latent).shape} does not "
                f"match noise shape {(L, c)}"
            )
        if req.init_latent is not None and req.timesteps < base:
            # strength-truncated img2img: the lane enters mid-schedule, so
            # seed it with the known image noised to the entry timestep —
            # the same q_sample the straight-line reference uses
            sched = D.make_schedule(self.dcfg)
            t0 = jnp.full((1,), int(req._lane_plan.ts[0]), jnp.int32)
            entry = D.q_sample(
                sched,
                jnp.asarray(req.init_latent, jnp.float32)[None],
                t0,
                jnp.asarray(req.noise, jnp.float32)[None],
            )[0]
            req._entry = np.asarray(entry)
        else:
            req._entry = req.noise
        want = (self.ucfg.added_dim,) if self.ucfg.added_dim else None
        got = None if req.added is None else np.shape(req.added)
        if got != want:
            raise ValueError(f"{self.ucfg.name} wants added conditioning of shape {want}, "
                             f"the request has {got}")
        req._sig = prompt_signature(req.ctx)
        self.metrics.record_submission(req.quality_tier)
        self.scheduler.add(req)

    def _admit_extras(self, req: GenRequest) -> tuple[jax.Array, jax.Array, jax.Array]:
        """Concrete (mask, x_init, noise0) lane tensors for one request —
        always-arrays so the jitted admit compiles once for every task
        (txt2img gets the all-ones mask + zeros, structurally the identity)."""
        L, c = req.noise.shape
        if req.mask is None:
            mask = jnp.ones((L, 1), jnp.float32)
            x_init = jnp.zeros((L, c), jnp.float32)
            noise0 = jnp.zeros((L, c), jnp.float32)
        else:
            mask = jnp.asarray(req.mask, jnp.float32)
            x_init = (
                jnp.zeros((L, c), jnp.float32)
                if req.init_latent is None
                else jnp.asarray(req.init_latent, jnp.float32)
            )
            noise0 = jnp.asarray(req.noise, jnp.float32)
        return mask, x_init, noise0

    # -- introspection ------------------------------------------------------

    @property
    def n_active(self) -> int:
        return sum(r is not None for r in self._lane_req)

    @property
    def n_pending(self) -> int:
        return len(self.scheduler)

    def progress(self) -> list[tuple[int, int, int]]:
        """``(rid, completed steps, total steps)`` per in-flight lane."""
        return [
            (r.rid, int(self._lane_step[i]), r.timesteps)
            for i, r in enumerate(self._lane_req)
            if r is not None
        ]

    # -- cancellation -------------------------------------------------------

    def cancel(self, rid: int) -> bool:
        """Abort one request wherever it currently is.

        A still-queued request is removed from the admission queue; an
        in-flight request's lane is released immediately, so the next
        :meth:`step`'s backfill can hand the lane to a queued request.
        Returns ``False`` when the rid is unknown here (already completed,
        never submitted, or cancelled before).  Like every other engine
        method, this must run on the thread that owns the engine (the
        driver thread under ``repro.serving.driver``).
        """
        if self.scheduler.remove(rid):
            return True
        for lane, req in enumerate(self._lane_req):
            if req is not None and req.rid == rid:
                self._release_lane(lane)
                self._lane_req[lane] = None
                self._stall[lane] = 0
                return True
        return False

    def _release_lane(self, lane: int) -> None:
        """Mark a lane empty on device (host mirrors are the caller's job)."""
        self._state = LN.release(self._state, jnp.int32(lane))

    def _active_lanes(self) -> list[int]:
        return [i for i, r in enumerate(self._lane_req) if r is not None]

    def _remaining_branches(self) -> list[np.ndarray]:
        out = []
        for i in self._active_lanes():
            req = self._lane_req[i]
            out.append(req._lane_plan.branches[self._lane_step[i] : req.timesteps])
        return out

    # -- event loop ---------------------------------------------------------

    def _prefetch_spill(self, req: GenRequest, shard: int | None = None) -> None:
        """Admission-time spill prefetch: for each of the request's planned
        FULL steps that no device slot would serve yet, probe the host
        spill tier and promote a match onto the device ring (shard ``shard``
        for the sharded engine) — so the lane's first planned FULL step
        already finds its features in HBM.  Threshold-0 steps never probe
        (the bit-exactness guarantee extends through the spill tier)."""
        cache = self.cache
        if cache is None or getattr(cache, "spill", None) is None or not req.allow_cache:
            return
        lp, sig, off = req._lane_plan, req._sig, req.sched_offset
        for i in range(lp.n_steps):
            if lp.branches[i] != SM.FULL or i < self.config.cache_min_step:
                continue
            thr = float(lp.thr[i])
            if thr <= 0:
                continue
            t = int(lp.ts[i])
            if shard is None:
                if cache.probe(t, sig, req.rid, thr, off) is not None:
                    continue  # already warm on the device ring
                slot = cache.promote(t, sig, req.rid, thr, off)
            else:
                if cache.probe(shard, t, sig, req.rid, thr, off) is not None:
                    continue
                slot = cache.promote(shard, t, sig, req.rid, thr, off)
            if slot is not None:
                self.metrics.spill_promotions += 1

    def _backfill(self, now_s: float) -> None:
        for lane, holder in enumerate(self._lane_req):
            if holder is not None:
                continue
            req = self.scheduler.next_request(self._remaining_branches())
            if req is None:
                return
            self._admit_lane(lane, req, now_s)

    def _admit_lane(self, lane: int, req: GenRequest, now_s: float,
                    shard: int | None = None) -> None:
        """Put ``req`` into the empty ``lane`` (of ``shard``, for the
        sharded engine): spill prefetch, lane tensors to the device, the
        admit program, host mirrors."""
        with self.metrics.span("engine.admit", rid=req.rid, lane=lane):
            self._prefetch_spill(req, shard)
            lp = req._lane_plan
            mask, x_init, noise0 = self._admit_extras(req)
            self._state = self._admit(
                self._state,
                jnp.int32(lane),
                jnp.asarray(req._entry),
                jnp.asarray(req.ctx),
                jnp.asarray(lp.branches),
                jnp.asarray(lp.ts),
                jnp.asarray(lp.t_prev),
                jnp.int32(lp.n_steps),
                jnp.asarray(lp.thr),
                mask, x_init, noise0,
                **self._admit_added(req),
            )
        self._lane_req[lane] = req
        self._lane_step[lane] = 0
        self._lane_admit_s[lane] = now_s
        self._stall[lane] = 0

    def _admit_added(self, req: GenRequest) -> dict:
        """The admit program's ``added2`` [2, added_dim] (the request's added
        conditioning and its unconditional row), or nothing."""
        if req.added is None:
            return {}
        added = np.asarray(req.added, np.float32)
        return {"added2": jnp.asarray(np.stack([added, SM.uncond_added(self.ucfg, added)]))}

    def _probe_eligible(self, req: GenRequest, lane: int, planned: int) -> bool:
        """Whether a lane's next planned step may be served from the cache.

        Planned FULL steps always probe (the FULL->SKETCH demotion);
        planned SKETCH steps probe only when the request's quality policy
        opted into the deeper SKETCH->REFINE demotion.
        """
        if not req.allow_cache or self._lane_step[lane] < self.config.cache_min_step:
            return False
        if planned == SM.FULL:
            return True
        return planned == SM.SKETCH and req.refine_demotions

    def _probe_cache(
        self, active: list[int], planned: np.ndarray
    ) -> dict[int, tuple[int, float]]:
        """Warm-slot probe for active lanes whose next planned step is
        cache-servable (FULL always; SKETCH when the request's policy
        allows REFINE demotions).

        Returns {lane: (slot, signature distance)} for the lanes servable
        this micro-step (host metadata only — the feature tensors stay on
        device; the distance rides along so the jitted micro-step can
        re-compare it against the lane's device-resident threshold leaf).
        Each probe uses the *request's own* per-step threshold.  Probes are
        read-only: hit/miss counters and LRU touches settle in :meth:`step`
        for the lanes that actually advance, so a lane stuck behind the
        branch vote neither inflates the stats nor keeps its candidate slot
        artificially warm.
        """
        hits: dict[int, tuple[int, float]] = {}
        if self.cache is None:
            return hits
        for k, lane in enumerate(active):
            req = self._lane_req[lane]
            if not self._probe_eligible(req, lane, int(planned[k])):
                continue
            step = self._lane_step[lane]
            t = int(req._lane_plan.ts[step])
            hit = self.cache.probe_distance(
                t, req._sig, req.rid, float(req._lane_plan.thr[step]),
                req.sched_offset,
            )
            if hit is not None:
                hits[lane] = hit
        return hits

    def step(self, now_s: float = 0.0, clock: Callable[[], float] | None = None) -> list[CompletedRequest]:
        """Backfill, run one micro-step, retire finished lanes.

        ``clock`` (same origin as ``now_s``) re-reads the time *after* the
        retirement device sync so completion stamps include the queued
        async compute; without it ``now_s`` is used as-is.  The whole call
        is the ``engine.tick`` span.
        """
        with self.metrics.span("engine.tick"):
            return self._tick(now_s, clock)

    def _tick(self, now_s: float, clock: Callable[[], float] | None) -> list[CompletedRequest]:
        self._backfill(now_s)
        active = self._active_lanes()
        if not active:
            return []

        with self.metrics.span("engine.plan"):
            planned = np.array(
                [self._lane_req[i]._lane_plan.branches[self._lane_step[i]] for i in active],
                np.int64,
            )
            # cache demotion: a planned FULL step with a warm, close-enough
            # slot executes as SKETCH consuming the cached features of
            # another (or an earlier) FULL step; a planned SKETCH step whose
            # quality policy allows it demotes one further, to REFINE on the
            # slot's refine features.  The packing policy votes over the
            # *effective* classes so demoted lanes amortize with cheaper
            # planned lanes.
            hit_slots = self._probe_cache(active, planned)
            planned_of = {int(lane): int(planned[k]) for k, lane in enumerate(active)}
            effective = planned.copy()
            for k, lane in enumerate(active):
                if lane in hit_slots:
                    effective[k] = SM.SKETCH if planned[k] == SM.FULL else SM.REFINE
            b_star = self.scheduler.pick_branch(effective, self._stall[active])

            # the advance mask is deterministic from the host-known plans +
            # cache metadata — mirror it here instead of syncing on the
            # device (keeps dispatch async)
            sel = np.zeros((self.config.n_lanes,), bool)
            advanced = np.asarray(active)[effective == b_star]
            sel[advanced] = True
            n_demoted = n_demoted_rf = 0
            if self.cache is not None:
                feat_src = np.full((self.config.n_lanes,), -1, np.int32)
                feat_dist = np.full((self.config.n_lanes,), np.inf, np.float32)
                if b_star in (SM.SKETCH, SM.REFINE):
                    for lane in advanced:
                        hit = hit_slots.get(int(lane))
                        if hit is None:
                            # a planned (un-demoted) partial step that probed
                            # and missed settles its accounting here
                            req = self._lane_req[lane]
                            if self._probe_eligible(req, int(lane), planned_of[int(lane)]):
                                self.cache.note_miss()
                            continue
                        slot, dist = hit
                        feat_src[lane] = slot
                        feat_dist[lane] = dist
                        self.cache.note_hit(slot)
                        if planned_of[int(lane)] == SM.FULL:
                            n_demoted += 1
                        else:
                            n_demoted_rf += 1

        batches = LN.compact_batches(advanced, self.config.n_lanes, self._k)
        with self.metrics.span("engine.dispatch", cls=CLASSES[b_star], lanes=len(advanced)):
            self.metrics.mark_dispatch()
            extra = () if self.cache is None else (
                jnp.asarray(feat_src), jnp.asarray(feat_dist), self.cache.state
            )
            for idx, valid in batches:
                self._state = self._micro(
                    self._state, self._params, jnp.int32(b_star),
                    jnp.asarray(idx), jnp.asarray(valid), *extra,
                )
            if self.cache is not None and b_star == SM.FULL:
                self._insert_captures(advanced)

        self._lane_step[sel] += 1
        self._stall[active] += 1
        self._stall[sel] = 0
        n_adv = len(advanced)
        self.metrics.record_step(
            self.config.n_lanes, len(active), int(sel.sum()),
            n_full=n_adv if b_star == SM.FULL else 0,
            n_sketch=n_adv if b_star == SM.SKETCH else 0,
            n_refine=n_adv if b_star == SM.REFINE else 0,
            n_demoted=n_demoted, n_demoted_refine=n_demoted_rf,
            dispatches=len(batches), unet_rows=2 * self._k * len(batches),
            tf_block_rows=2 * self._k * len(batches) * self._tf_blocks[b_star],
        )

        return self._retire(active, now_s, clock)

    def _insert_captures(self, advanced: np.ndarray) -> None:
        """Fresh FULL captures become warm slots: reserve host-side
        (conflict-free within the batch), then fill every slot in one
        batched device scatter (padded to n_lanes so the scatter compiles
        once)."""
        lanes = np.zeros((self.config.n_lanes,), np.int32)
        slots = np.full((self.config.n_lanes,), self.cache.n_slots, np.int32)
        taken: set[int] = set()
        for k, lane in enumerate(advanced):
            req = self._lane_req[lane]
            t = int(req._lane_plan.ts[self._lane_step[lane]])
            if req.allow_cache and self._lane_step[lane] >= self.config.cache_min_step:
                self.cache.note_miss()  # probed FULL executed as FULL
            if self.config.cache_mode == "intra" and not req.allow_cache:
                # only this request could ever consume the capture, and it
                # opted out — don't evict useful slots for it
                continue
            slot = self.cache.reserve(
                t, req._sig, req.rid, exclude=taken, offset=req.sched_offset
            )
            if slot is None:  # ring smaller than the FULL batch
                continue
            taken.add(slot)
            lanes[k] = int(lane)
            slots[k] = slot
        if taken:
            self.cache.insert_many(self._state.f_sk, self._state.f_rf, lanes, slots)

    def _retire(
        self, active: list[int], now_s: float, clock: Callable[[], float] | None
    ) -> list[CompletedRequest]:
        """Read back, release and report every lane that finished its
        schedule; each is one ``engine.retire`` span."""
        done: list[CompletedRequest] = []
        for lane in active:
            req = self._lane_req[lane]
            if self._lane_step[lane] < req.timesteps:
                continue
            with self.metrics.span("engine.retire", rid=req.rid):
                latent = self._state.x[lane]
                image = None
                if self._decoder is not None:
                    image = np.asarray(self._decoder(latent[None])[0])
                latent = np.asarray(latent)  # syncs the queued micro-steps
                self.metrics.mark_sync()
                done.append(
                    CompletedRequest(
                        rid=req.rid,
                        latent=latent,
                        image=image,
                        submitted_s=req.arrival_s,
                        admitted_s=self._lane_admit_s[lane],
                        completed_s=clock() if clock is not None else now_s,
                    )
                )
                self._release_lane(lane)
            self._lane_req[lane] = None
            self.metrics.record_completion(done[-1].latency_s, done[-1].queue_wait_s)
        return done

    def run(
        self, requests: Sequence[GenRequest], *, realtime: bool = False
    ) -> tuple[list[CompletedRequest], dict]:
        """Serve a request stream to completion.

        ``realtime=False`` ignores arrival offsets (everything is queued up
        front).  ``realtime=True`` replays ``arrival_s`` against the wall
        clock — the benchmark's Poisson open-loop mode.  The engine is
        reusable: compiled micro-steps persist across calls; metrics and the
        feature cache reset per call (a cold cache keeps ``run`` outputs a
        deterministic function of the request stream — drive :meth:`step`
        directly to serve with cross-call warmth).
        """
        self.metrics = ServingMetrics()
        if self.cache is not None:
            self.cache.reset()
        pending = sorted(requests, key=lambda r: r.arrival_s)
        t0 = time.perf_counter()
        clock = lambda: time.perf_counter() - t0
        done: list[CompletedRequest] = []
        if not realtime:
            for req in pending:
                self.submit(req)
            pending = []
        while pending or self.n_pending or self.n_active:
            now = clock()
            while pending and pending[0].arrival_s <= now:
                self.submit(pending.pop(0))
            if not self.n_pending and not self.n_active and pending:
                time.sleep(min(pending[0].arrival_s - now, 0.05))
                continue
            done.extend(self.step(now_s=clock(), clock=clock))
        self.metrics.wall_s = time.perf_counter() - t0
        summary = dict(
            self.metrics.summary(),
            mode=self._mode_name,
            lanes=self.config.n_lanes,
            kernels=self.config.backend,
            **self._summary_extra(),
        )
        if self.cache is not None:
            summary.update(self.cache.stats())
        return done, summary

    def _summary_extra(self) -> dict:
        return {}


# ---------------------------------------------------------------------------
# Mesh-sharded continuous batching: contiguous lane shards, one GSPMD
# micro-step, shard-local feature rings.
# ---------------------------------------------------------------------------


class ShardedDiffusionEngine(DiffusionEngine):
    """Continuous batching with the lane axis sharded over a device mesh.

    Device ``d`` of a :func:`repro.common.sharding.lane_mesh` owns lanes
    ``[d * P, (d + 1) * P)`` (``P = n_lanes / n_shards``).  The micro-step
    stays ONE jitted GSPMD program (``shard_map`` over ``("data",)``), but
    the branch vote is *per shard*: each shard's scheduler-chosen class
    drives its own ``lax.switch``, so one shard can run a FULL U-Net batch
    while another runs SKETCH in the same dispatch — lane grouping no
    longer has to agree across the whole machine, only within a shard.

    Admission fills the emptiest shard first and retirement/backfill touch
    only the retiring lane's shard — there is no cross-shard barrier
    anywhere in the event loop.  The PR 2 feature cache partitions into
    shard-local rings (:class:`~repro.serving.cache.ShardedFeatureCache`):
    captures are only reusable within the shard that produced them, so
    serving a warm hit is a device-local gather, and the cache-aware
    scheduler routes warm requests to the shard holding their slots.

    ``n_shards=1`` on a one-device mesh reproduces the unsharded engine's
    results (different XLA program, same math — the sharded golden test
    pins the agreement); ``--shards 1`` at the CLIs short-circuits to
    :class:`DiffusionEngine` itself, which stays bit-exact by construction.
    """

    _mode_name = "sharded-continuous"

    def __init__(
        self,
        ucfg: UNetConfig,
        dcfg: DiffusionConfig,
        params: Params,
        vae_params: Params | None = None,
        config: EngineConfig = EngineConfig(),
        scheduler: FIFOScheduler | None = None,
        mesh=None,
    ):
        self._mesh_arg = mesh
        super().__init__(ucfg, dcfg, params, vae_params, config, scheduler=scheduler)

    def _build_device_state(self, params: Params) -> None:
        config, ucfg = self.config, self.ucfg
        self.mesh = self._mesh_arg if self._mesh_arg is not None else SH.lane_mesh(
            config.n_shards
        )
        self.n_shards = self.mesh.shape["data"]
        if self.n_shards != config.n_shards:
            raise ValueError(
                f"mesh has {self.n_shards} data shards but config.n_shards="
                f"{config.n_shards}"
            )
        self.lanes_per_shard = config.n_lanes // self.n_shards

        self.cache: ShardedFeatureCache | None = None
        if config.cache_mode != "off":
            self.cache = ShardedFeatureCache(
                ucfg, self.e_sk, self.e_rf, self.mesh,
                slots_per_shard=config.cache_slots,
                threshold=config.cache_threshold,
                t_bucket=config.cache_t_bucket,
                mode=config.cache_mode,
                spill_mb=config.cache_spill_mb,
            )
        self._params = jax.device_put(params, SH.replicated_sharding(self.mesh))
        self._state = LN.init_sharded_lanes(
            ucfg, config.n_lanes, config.max_steps, self.e_sk, self.e_rf, self.mesh
        )
        self._micro = LN.make_sharded_micro_step(
            ucfg, self.dcfg, self.e_sk, self.e_rf, self.mesh,
            cached=self.cache is not None, backend=config.backend,
        )
        self._admit = LN.make_sharded_admit(self.mesh)
        self._release = LN.make_sharded_release(self.mesh)

    # -- shard geometry -------------------------------------------------------

    def _shard_of(self, lane: int) -> int:
        return int(lane) // self.lanes_per_shard

    def _shard_active_counts(self) -> list[int]:
        counts = [0] * self.n_shards
        for i, r in enumerate(self._lane_req):
            if r is not None:
                counts[self._shard_of(i)] += 1
        return counts

    def _shard_remaining_branches(self, shard: int) -> list[np.ndarray]:
        """Remaining branch vectors of the shard's own in-flight lanes —
        the alignment scope for admission, since branch grouping is now
        per shard."""
        lo = shard * self.lanes_per_shard
        out = []
        for i in range(lo, lo + self.lanes_per_shard):
            req = self._lane_req[i]
            if req is not None:
                out.append(req._lane_plan.branches[self._lane_step[i] : req.timesteps])
        return out

    def _summary_extra(self) -> dict:
        return {"shards": self.n_shards, "lanes_per_shard": self.lanes_per_shard}

    def _release_lane(self, lane: int) -> None:
        self._state = self._release(self._state, jnp.int32(lane))

    # -- event loop -----------------------------------------------------------

    def _backfill(self, now_s: float) -> None:
        """Admit queued requests, into the emptiest shard by default — or,
        with ``cache_gossip``, into the shard whose ring would actually
        serve a windowed request's FULL steps.

        Each admission re-ranks the shards, so a burst spreads evenly
        instead of piling into the lowest-numbered lanes; within a shard
        the lowest empty lane wins (deterministic placement).  The warmth
        redirect is the admission-time migration half of the global cache
        tier: shard-local rings mean a warm request admitted to the wrong
        shard hits nothing, so when the scheduler's fleet-wide warmth map
        (:meth:`~repro.serving.scheduler.CacheAwareScheduler.peek_warm_shard`)
        names a warm shard with a free lane, placement follows the warmth
        instead of the load.
        """
        while True:
            empty = [i for i, r in enumerate(self._lane_req) if r is None]
            if not empty:
                return
            counts = self._shard_active_counts()
            lane = min(empty, key=lambda i: (counts[self._shard_of(i)], i))
            shard = self._shard_of(lane)
            if self.config.cache_gossip and hasattr(self.scheduler, "peek_warm_shard"):
                open_shards = sorted({self._shard_of(i) for i in empty})
                warm = self.scheduler.peek_warm_shard(open_shards)
                if warm is not None and warm != shard:
                    lane = min(i for i in empty if self._shard_of(i) == warm)
                    shard = warm
                    self.metrics.gossip_routed += 1
            req = self.scheduler.next_request(
                self._shard_remaining_branches(shard), shard=shard
            )
            if req is None:
                return
            self._admit_lane(lane, req, now_s, shard)

    def _probe_cache(
        self, active: list[int], planned: np.ndarray
    ) -> dict[int, tuple[int, float]]:
        """{lane: (*shard-local* slot, signature distance)} for cache-
        servable steps on the lane's own shard ring (reuse never crosses a
        shard); probes use the request's own per-step threshold."""
        hits: dict[int, tuple[int, float]] = {}
        if self.cache is None:
            return hits
        for k, lane in enumerate(active):
            req = self._lane_req[lane]
            if not self._probe_eligible(req, lane, int(planned[k])):
                continue
            step = self._lane_step[lane]
            t = int(req._lane_plan.ts[step])
            hit = self.cache.probe_distance(
                self._shard_of(lane), t, req._sig, req.rid,
                float(req._lane_plan.thr[step]), req.sched_offset,
            )
            if hit is not None:
                hits[lane] = hit
        return hits

    def _tick(self, now_s: float, clock: Callable[[], float] | None) -> list[CompletedRequest]:
        """Backfill, run one sharded micro-step, retire finished lanes.

        Mirrors :meth:`DiffusionEngine.step` with the branch vote taken
        independently per shard: ``b_arr[s]`` is shard ``s``'s class and a
        lane advances iff its effective class matches its own shard's
        vote.  Shards with no active lanes are parked on REFINE (the
        cheapest branch) with an all-false advance mask.
        """
        self._backfill(now_s)
        active = self._active_lanes()
        if not active:
            return []

        n = self.config.n_lanes
        with self.metrics.span("engine.plan"):
            planned = np.array(
                [self._lane_req[i]._lane_plan.branches[self._lane_step[i]] for i in active],
                np.int64,
            )
            hit_slots = self._probe_cache(active, planned)
            planned_of = {int(lane): int(planned[k]) for k, lane in enumerate(active)}
            effective = planned.copy()
            for k, lane in enumerate(active):
                if lane in hit_slots:
                    effective[k] = SM.SKETCH if planned[k] == SM.FULL else SM.REFINE

            active_arr = np.asarray(active)
            shard_ids = active_arr // self.lanes_per_shard
            b_arr = np.full((self.n_shards,), SM.REFINE, np.int32)  # idle shards: cheapest
            sel = np.zeros((n,), bool)
            votes: list[tuple[int, int, np.ndarray]] = []  # (shard, b, advanced lanes)
            for s in range(self.n_shards):
                m = shard_ids == s
                if not m.any():
                    continue
                lanes_s = active_arr[m]
                b = self.scheduler.pick_branch(effective[m], self._stall[lanes_s])
                b_arr[s] = b
                adv = lanes_s[effective[m] == b]
                sel[adv] = True
                votes.append((s, b, adv))

            n_full = sum(len(adv) for _, b, adv in votes if b == SM.FULL)
            n_sketch = sum(len(adv) for _, b, adv in votes if b == SM.SKETCH)
            n_refine = sum(len(adv) for _, b, adv in votes if b == SM.REFINE)
            n_demoted = n_demoted_rf = 0
            if self.cache is not None:
                feat_src = np.full((n,), -1, np.int32)
                feat_dist = np.full((n,), np.inf, np.float32)
                for s, b, adv in votes:
                    if b not in (SM.SKETCH, SM.REFINE):
                        continue
                    for lane in adv:
                        hit = hit_slots.get(int(lane))
                        if hit is None:
                            req = self._lane_req[lane]
                            if self._probe_eligible(req, int(lane), planned_of[int(lane)]):
                                self.cache.note_miss(s)  # probed partial, no warm slot
                            continue
                        slot, dist = hit
                        feat_src[lane] = slot
                        feat_dist[lane] = dist
                        self.cache.note_hit(s, slot)
                        if planned_of[int(lane)] == SM.FULL:
                            n_demoted += 1
                        else:
                            n_demoted_rf += 1

        cls = ",".join(CLASSES[b] for b in b_arr)
        with self.metrics.span("engine.dispatch", cls=cls, lanes=int(sel.sum())):
            self.metrics.mark_dispatch()
            if self.cache is None:
                self._state = self._micro(
                    self._state, self._params, jnp.asarray(b_arr), jnp.asarray(sel)
                )
            else:
                self._state = self._micro(
                    self._state, self._params, jnp.asarray(b_arr), jnp.asarray(sel),
                    jnp.asarray(feat_src), jnp.asarray(feat_dist), self.cache.state,
                )
                self._insert_shard_captures(votes)

        self._lane_step[sel] += 1
        self._stall[active] += 1
        self._stall[sel] = 0
        shard_active = [int((shard_ids == s).sum()) for s in range(self.n_shards)]
        self.metrics.record_step(
            n, len(active), int(sel.sum()),
            n_full=n_full, n_sketch=n_sketch, n_refine=n_refine,
            n_demoted=n_demoted, n_demoted_refine=n_demoted_rf,
            shard_active=shard_active, dispatches=1, unet_rows=2 * n,
            tf_block_rows=2 * self.lanes_per_shard * sum(self._tf_blocks[b] for b in b_arr),
        )

        return self._retire(active, now_s, clock)

    def _insert_shard_captures(self, votes: list[tuple[int, int, np.ndarray]]) -> None:
        """Fresh captures -> shard-local warm slots, one sharded scatter:
        per-shard segments of the padded [n_lanes] index arrays carry local
        lane/slot indices (see ShardedFeatureCache.insert_many)."""
        n = self.config.n_lanes
        ins_lanes = np.zeros((n,), np.int32)
        ins_slots = np.full((n,), self.cache.slots_per_shard, np.int32)
        any_insert = False
        for s, b, adv in votes:
            if b != SM.FULL:
                continue
            base = s * self.lanes_per_shard
            pos = base
            taken: set[int] = set()
            for lane in adv:
                req = self._lane_req[lane]
                t = int(req._lane_plan.ts[self._lane_step[lane]])
                if req.allow_cache and self._lane_step[lane] >= self.config.cache_min_step:
                    self.cache.note_miss(s)  # probed FULL executed as FULL
                if self.config.cache_mode == "intra" and not req.allow_cache:
                    continue
                slot = self.cache.reserve(
                    s, t, req._sig, req.rid, exclude=taken,
                    offset=req.sched_offset,
                )
                if slot is None:  # shard ring smaller than the FULL batch
                    continue
                taken.add(slot)
                ins_lanes[pos] = int(lane) - base  # shard-local lane index
                ins_slots[pos] = slot
                pos += 1
                any_insert = True
        if any_insert:
            self.cache.insert_many(self._state.f_sk, self._state.f_rf, ins_lanes, ins_slots)


def make_serving_engine(
    ucfg: UNetConfig,
    dcfg: DiffusionConfig,
    params: Params,
    vae_params: Params | None = None,
    config: EngineConfig = EngineConfig(),
    scheduler: FIFOScheduler | None = None,
) -> DiffusionEngine:
    """Engine for ``config.n_shards``: the single-device engine at 1 (bit-
    exact with the pre-sharding code path), the mesh-sharded engine above 1."""
    cls = ShardedDiffusionEngine if config.n_shards > 1 else DiffusionEngine
    return cls(ucfg, dcfg, params, vae_params, config, scheduler=scheduler)


# ---------------------------------------------------------------------------
# Static fixed-size lockstep batching (the seed `serve.py` behaviour),
# kept as the baseline that `benchmarks/bench_serving.py` measures against.
# ---------------------------------------------------------------------------


class StaticServer:
    """Fixed-size FIFO batches running the PAS sampler in lockstep.

    The whole batch runs ``max(timesteps)`` of its members (lockstep cannot
    do otherwise), short batches are padded by repeating the last request,
    and a batch only launches once all its members have arrived.  The run
    summary reports ``idle_lane_frac`` — the fraction of lane-steps spent on
    padding or lockstep overshoot — which is exactly the waste continuous
    batching exists to reclaim.  Compiled samplers are cached per
    (step count, plan), so a warmup run amortizes jit for later runs.
    """

    def __init__(
        self,
        ucfg: UNetConfig,
        dcfg: DiffusionConfig,
        params: Params,
        vae_params: Params | None,
        batch: int,
        *,
        plan_fn: Callable[[int], PASPlan | None] = lambda t: None,
        decode_images: bool = True,
    ):
        self.ucfg, self.dcfg, self.batch, self.plan_fn = ucfg, dcfg, batch, plan_fn
        lhw = (ucfg.latent_size, ucfg.latent_size)

        @functools.lru_cache(maxsize=None)
        def compiled(total_steps: int, plan: PASPlan | None):
            d = dataclasses.replace(dcfg, timesteps_sample=total_steps)

            @jax.jit
            def gen(noise, ctx):
                x0 = SM.pas_denoise(ucfg, d, params, plan, noise, ctx, jnp.zeros_like(ctx))
                if vae_params is not None and decode_images:
                    return x0, V.vae_decode(vae_params, x0, lhw)
                return x0, None

            return gen

        self._compiled = compiled

    def _dummy_inputs(self):
        L = self.ucfg.latent_size**2
        noise = jnp.zeros((self.batch, L, self.ucfg.in_channels), jnp.float32)
        ctx = jnp.zeros((self.batch, self.ucfg.ctx_len, self.ucfg.ctx_dim), jnp.float32)
        return noise, ctx

    def warmup(self, timesteps: Sequence[int]) -> None:
        """Pre-compile the lockstep sampler for every listed step count."""
        noise, ctx = self._dummy_inputs()
        for t in timesteps:
            x0, _ = self._compiled(t, self.plan_fn(t))(noise, ctx)
            x0.block_until_ready()

    def time_step_s(self, timesteps: int, iters: int = 3) -> float:
        """Median per-denoise-step wall seconds of the compiled sampler
        (used by benchmarks to pick arrival rates around saturation)."""
        noise, ctx = self._dummy_inputs()
        fn = self._compiled(timesteps, self.plan_fn(timesteps))
        fn(noise, ctx)[0].block_until_ready()
        walls = []
        for _ in range(iters):
            t0 = time.perf_counter()
            fn(noise, ctx)[0].block_until_ready()
            walls.append(time.perf_counter() - t0)
        walls.sort()
        return walls[len(walls) // 2] / timesteps

    def run(
        self, requests: Sequence[GenRequest], *, realtime: bool = False
    ) -> tuple[list[CompletedRequest], dict]:
        batch = self.batch
        pending = sorted(requests, key=lambda r: r.arrival_s)
        metrics = ServingMetrics()
        done: list[CompletedRequest] = []
        total_lane_steps = 0
        useful_lane_steps = 0
        t0 = time.perf_counter()
        i = 0
        while i < len(pending):
            group = pending[i : i + batch]
            i += len(group)
            if realtime:
                wait = group[-1].arrival_s - (time.perf_counter() - t0)
                if wait > 0:
                    time.sleep(wait)
            admit_s = time.perf_counter() - t0
            t_max = max(r.timesteps for r in group)
            pad = batch - len(group)
            noise = np.stack([r.noise for r in group] + [group[-1].noise] * pad)
            ctx = np.stack([r.ctx for r in group] + [group[-1].ctx] * pad)
            x0, imgs = self._compiled(t_max, self.plan_fn(t_max))(
                jnp.asarray(noise), jnp.asarray(ctx)
            )
            x0.block_until_ready()
            now = time.perf_counter() - t0
            total_lane_steps += batch * t_max
            useful_lane_steps += sum(r.timesteps for r in group)
            for _ in range(t_max):
                metrics.record_step(batch, len(group), len(group))
            for lane, req in enumerate(group):
                done.append(
                    CompletedRequest(
                        rid=req.rid,
                        latent=np.asarray(x0[lane]),
                        image=None if imgs is None else np.asarray(imgs[lane]),
                        submitted_s=req.arrival_s,
                        admitted_s=admit_s,
                        completed_s=now,
                    )
                )
                metrics.record_completion(done[-1].latency_s, done[-1].queue_wait_s)
        metrics.wall_s = time.perf_counter() - t0
        idle = 1.0 - useful_lane_steps / max(total_lane_steps, 1)
        summary = dict(
            metrics.summary(),
            mode="static",
            lanes=batch,
            idle_lane_frac=round(idle, 3),
        )
        return done, summary


def serve_static(
    ucfg: UNetConfig,
    dcfg: DiffusionConfig,
    params: Params,
    vae_params: Params | None,
    requests: Sequence[GenRequest],
    batch: int,
    *,
    plan_fn: Callable[[int], PASPlan | None] = lambda t: None,
    decode_images: bool = True,
    realtime: bool = False,
) -> tuple[list[CompletedRequest], dict]:
    """One-shot convenience wrapper around :class:`StaticServer`."""
    server = StaticServer(
        ucfg, dcfg, params, vae_params, batch,
        plan_fn=plan_fn, decode_images=decode_images,
    )
    return server.run(requests, realtime=realtime)
