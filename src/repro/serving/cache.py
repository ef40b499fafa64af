"""Cross-request feature cache for the continuous-batching engine.

The paper's Key Observation 1 — high-level U-Net features barely move
between adjacent denoise steps — is what PAS exploits *within* one request
(the FULL steps refresh a sketch/refine feature pair that the partial steps
consume).  The same similarity holds *across* requests: two requests at
nearby timesteps whose prompts are close produce nearly identical mid-block
features (DeepCache / SADA observation).  This module stores the features
the engine's FULL steps already capture and lets *other* lanes consume them,
turning would-be FULL micro-steps into SKETCH micro-steps.

Split of responsibilities:

* **Device**: a fixed-size ring of feature slots (:class:`CacheState`, one
  pytree of ``[S, 2, L, C]`` arrays — cond/uncond pairs in the engine's
  CFG-doubled layout).  Insert is a jitted scatter from the lane arrays;
  lookup inside the jitted micro-step is a gather by a per-lane slot index
  (``feat_source``; -1 = use the lane's own features).  Feature tensors
  never cross the host boundary.
* **Host**: per-slot keys — timestep bucket + prompt-embedding signature —
  plus validity, owner rid and an LRU clock.  Hit policy is a shift-score
  style relative distance (paper Eq. 1, applied to pooled prompt
  embeddings): ``||sig - slot_sig|| / ||slot_sig|| < threshold``.  The
  inequality is *strict*, so ``threshold=0`` can never hit and is
  guaranteed bit-exact with the cache-off engine (the golden-latent
  harness pins this).

Modes are disjoint reuse scopes: ``"intra"`` restricts hits to slots
inserted by the same request (DeepCache-style self reuse — a lane skips
its own scheduled FULL refreshes, where the signature distance is 0 by
construction and the timestep bucket is the only gate); ``"cross"``
restricts hits to *other* requests' slots, so the threshold genuinely
measures cross-prompt distance — a request can never satisfy it with its
own refreshed slot at distance exactly 0, and reported cross hits are
always real cross-request sharing.
"""
from __future__ import annotations

import functools
from collections import OrderedDict
from dataclasses import dataclass
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.common.types import UNetConfig
from repro.core import sampler as SM

#: slot cap on one ring's published key table (``slots_summary`` /
#: ``key_delta``): an over-provisioned ring must not bloat every ``/stats``
#: poll, so only the most-recently-used slots are reported and consumers
#: must tolerate truncation (the router scores whatever subset it sees)
MAX_SUMMARY_SLOTS = 64


class CacheState(NamedTuple):
    """Device-resident feature slots, lane-cache layout per slot.

    Row 0 of the pair axis is the cond feature, row 1 the uncond feature
    (matching rows ``i`` / ``N + i`` of the engine's CFG-doubled lane
    caches), so a slot drops into a lane without any transpose.
    """

    f_sk: jax.Array  # [S, 2, L_sk, C_sk] sketch-entry features
    f_rf: jax.Array  # [S, 2, L_rf, C_rf] refine-entry features

    @property
    def n_slots(self) -> int:
        return self.f_sk.shape[0]


def prompt_signature(ctx: np.ndarray) -> np.ndarray:
    """Pooled prompt-embedding signature used as the cache key ([ctx_dim])."""
    return np.asarray(ctx, np.float32).mean(axis=0)


def signature_distance(sig: np.ndarray, ref: np.ndarray) -> float:
    """Shift-score-style relative distance (paper Eq. 1 on pooled prompts)."""
    ref = np.asarray(ref, np.float32)
    return float(np.linalg.norm(np.asarray(sig, np.float32) - ref) / (np.linalg.norm(ref) + 1e-12))


@functools.partial(jax.jit, donate_argnums=(0,))
def _insert_slots(
    cache: CacheState,
    f_sk: jax.Array,  # [2N, L_sk, C_sk] lane sketch cache
    f_rf: jax.Array,  # [2N, L_rf, C_rf] lane refine cache
    lanes: jax.Array,  # [K] int32 source lanes
    slots: jax.Array,  # [K] int32 target slots; >= n_slots marks padding
) -> CacheState:
    """Batched slot fill: one scatter dispatch for all of a micro-step's
    FULL captures.  Padding entries carry an out-of-range slot and are
    dropped by the scatter."""
    n = f_sk.shape[0] // 2
    pair = lambda a: jnp.stack([a[lanes], a[n + lanes]], axis=1)  # [K, 2, L, C]
    return CacheState(
        f_sk=cache.f_sk.at[slots].set(pair(f_sk), mode="drop"),
        f_rf=cache.f_rf.at[slots].set(pair(f_rf), mode="drop"),
    )


@functools.partial(jax.jit, donate_argnums=(0,))
def _upload_slot(
    cache: CacheState,
    slot: jax.Array,  # int32 scalar target slot
    f_sk: jax.Array,  # [2, L_sk, C_sk] spilled sketch features
    f_rf: jax.Array,  # [2, L_rf, C_rf] spilled refine features
) -> CacheState:
    """Promote one spill-resident capture back onto the device ring.

    The reverse of the eviction demote: a single-slot scatter of host
    (numpy) features, so a spill round-trip is float32-lossless — the
    promoted slot serves hits bit-identically to the original capture.
    """
    return CacheState(
        f_sk=cache.f_sk.at[slot].set(f_sk),
        f_rf=cache.f_rf.at[slot].set(f_rf),
    )


def select_entry_features(
    own: jax.Array,  # [2N, L, C] lane-cache features
    cached: jax.Array,  # [S, 2, L, C] cache slots
    src: jax.Array,  # [N] int32 slot index per lane; -1 = own
    use: jax.Array | None = None,  # [N] bool consume mask (default: src >= 0)
) -> jax.Array:
    """Per-lane captured-vs-cached feature selection (inside the jitted
    micro-step).  Pure gather + where: exact passthrough when nothing is
    used, so the cache-enabled micro-step with no hits stays bit-identical.
    ``use`` lets the micro-step add the device-side threshold comparison
    (probed distance strictly below the lane's per-step threshold leaf)."""
    n = own.shape[0] // 2
    pick = cached[jnp.clip(src, 0, cached.shape[0] - 1)]  # [N, 2, L, C]
    if use is None:
        use = src >= 0
    use = use[:, None, None]
    cond = jnp.where(use, pick[:, 0], own[:n])
    unc = jnp.where(use, pick[:, 1], own[n:])
    return jnp.concatenate([cond, unc], axis=0)


class _GenClock:
    """Monotone generation counter, shareable across rings.

    Every key-table mutation (reserve / refresh / evict-overwrite) ticks
    it; the sharded cache hands one clock to all of its rings so slot
    generations are totally ordered engine-wide and one scalar ``since``
    cursor can drive the incremental ``/cache/keys`` delta protocol.
    """

    __slots__ = ("value",)

    def __init__(self) -> None:
        self.value = 0


@dataclass
class SpillEntry:
    """One demoted capture parked in host RAM (features included)."""

    bucket: int
    offset: int
    rid: int
    sig: np.ndarray  # [sig_dim] float32
    f_sk: np.ndarray  # [2, L_sk, C_sk] float32
    f_rf: np.ndarray  # [2, L_rf, C_rf] float32
    nbytes: int


class SpillRing:
    """Host-RAM spill tier under the HBM slot ring: a byte-capped LRU of
    demoted feature captures.

    HBM-ring evictions :meth:`put` the victim's features (numpy copies —
    float32-lossless) here instead of dropping them; cache-aware admission
    probes the spill with the same key policy as the device ring and
    promotes matches back onto a device slot before the lane's first
    planned FULL step.  Effective cache capacity thus scales with
    ``capacity_bytes`` (host RAM) rather than device slot count.  Entries
    are keyed by ``(rid, bucket, offset)`` — a newer demotion of the same
    capture refreshes in place.
    """

    def __init__(self, capacity_bytes: int, *, mode: str = "cross"):
        if capacity_bytes < 0:
            raise ValueError("spill capacity must be >= 0 bytes")
        self.capacity_bytes = int(capacity_bytes)
        self.mode = mode
        self._entries: OrderedDict[tuple, SpillEntry] = OrderedDict()
        self.bytes = 0
        self.demotions = 0
        self.promotions = 0
        self.spill_evictions = 0

    def __len__(self) -> int:
        return len(self._entries)

    def reset(self) -> None:
        self._entries.clear()
        self.bytes = 0
        self.demotions = 0
        self.promotions = 0
        self.spill_evictions = 0

    def put(
        self, bucket: int, offset: int, rid: int, sig: np.ndarray,
        f_sk: np.ndarray, f_rf: np.ndarray,
    ) -> bool:
        """Admit (or refresh) one demoted capture; False = too big to hold."""
        f_sk = np.ascontiguousarray(f_sk, np.float32)
        f_rf = np.ascontiguousarray(f_rf, np.float32)
        nbytes = f_sk.nbytes + f_rf.nbytes
        if nbytes > self.capacity_bytes:
            return False
        key = (int(rid), int(bucket), int(offset))
        old = self._entries.pop(key, None)
        if old is not None:
            self.bytes -= old.nbytes
        while self.bytes + nbytes > self.capacity_bytes and self._entries:
            _, victim = self._entries.popitem(last=False)
            self.bytes -= victim.nbytes
            self.spill_evictions += 1
        self._entries[key] = SpillEntry(
            bucket=int(bucket), offset=int(offset), rid=int(rid),
            sig=np.asarray(sig, np.float32).copy(),
            f_sk=f_sk, f_rf=f_rf, nbytes=nbytes,
        )
        self.bytes += nbytes
        self.demotions += 1
        return True

    def probe(
        self, bucket: int, sig: np.ndarray, rid: int, threshold: float,
        offset: int = 0,
    ) -> SpillEntry | None:
        """Best spill entry for (bucket, signature, offset) under the same
        strict-inequality hit policy as the device ring (mode-scoped rid
        filter included), with an LRU touch on the match."""
        if threshold <= 0 or not self._entries:
            return None
        best_key, best_d = None, np.inf
        for key, e in self._entries.items():
            if e.bucket != bucket or e.offset != offset:
                continue
            if (e.rid == rid) != (self.mode == "intra"):
                continue
            d = signature_distance(sig, e.sig)
            if d < best_d:
                best_key, best_d = key, d
        if best_key is None or not best_d < threshold:
            return None
        self._entries.move_to_end(best_key)
        return self._entries[best_key]

    def stats(self) -> dict:
        return {
            "cache_spill_capacity_bytes": self.capacity_bytes,
            "cache_spill_bytes": self.bytes,
            "cache_spill_entries": len(self._entries),
            "cache_spill_demotions": self.demotions,
            "cache_spill_promotions": self.promotions,
            "cache_spill_evictions": self.spill_evictions,
        }


class SlotRing:
    """Host-side slot metadata + hit/eviction policy for one feature ring.

    Holds everything *except* the device feature tensors: per-slot keys
    (timestep bucket + prompt signature), validity, owner rid, the LRU
    clock, and hit/miss counters.  :class:`FeatureCache` pairs one ring
    with one device :class:`CacheState`; :class:`ShardedFeatureCache`
    pairs one ring *per shard* with a single mesh-sharded state.  All
    methods are host-cheap: O(S) numpy over the slot metadata.
    """

    def __init__(
        self,
        n_slots: int,
        sig_dim: int,
        *,
        threshold: float = 0.15,
        t_bucket: int = 125,
        mode: str = "cross",
    ):
        if mode not in ("intra", "cross"):
            raise ValueError(f"cache mode must be 'intra' or 'cross', got {mode!r}")
        if n_slots < 1:
            raise ValueError("cache needs at least one slot")
        if threshold < 0:
            raise ValueError("cache threshold must be >= 0")
        if t_bucket < 1:
            raise ValueError("timestep bucket width must be >= 1")
        self.mode = mode
        self.n_slots = n_slots
        self.threshold = threshold
        self.t_bucket = t_bucket
        self.sig_dim = sig_dim
        #: eviction hook: called with the victim slot index *before* its
        #: metadata is overwritten (features still on device) — the spill
        #: tier demotes here; None = evictions simply drop the capture
        self.on_evict = None
        #: generation clock ticked by every key-table mutation; the sharded
        #: cache replaces it with one clock shared across its rings
        self._clock = _GenClock()
        self.reset_meta()

    def reset_meta(self) -> None:
        """Drop all slot keys and counters (cold ring)."""
        s = self.n_slots
        self.bucket = np.full((s,), -1, np.int64)
        self.sig = np.zeros((s, self.sig_dim), np.float32)
        self.rid = np.full((s,), -1, np.int64)
        #: schedule offset (base - executed steps) the slot was captured
        #: under: a truncated img2img schedule visits the same train
        #: timesteps as the stock one but with different PNDM history, so
        #: warm hits never cross incompatible truncations
        self.offset = np.zeros((s,), np.int64)
        self.valid = np.zeros((s,), bool)
        self.last_use = np.zeros((s,), np.int64)
        #: per-slot generation stamp (clock value of the last key write);
        #: strictly increasing across writes, so ``key_delta(since)`` can
        #: ship only the slots that changed after a consumer's cursor
        self.gen = np.zeros((s,), np.int64)
        self._clock.value = 0
        self._tick = 0
        self.probes = 0
        self.probe_hits = 0
        self.inserts = 0
        self.evictions = 0

    @property
    def version(self) -> int:
        """Clock value of the newest key write (0 = cold ring)."""
        return self._clock.value

    # -- keys ----------------------------------------------------------------

    def bucket_of(self, t: int) -> int:
        return int(t) // self.t_bucket

    @property
    def n_warm(self) -> int:
        return int(self.valid.sum())

    def _touch(self, slot: int) -> None:
        self._tick += 1
        self.last_use[slot] = self._tick

    # -- lookup --------------------------------------------------------------

    def probe_distance(
        self, t: int, sig: np.ndarray, rid: int, threshold: float | None = None,
        offset: int = 0,
    ) -> tuple[int, float] | None:
        """Best matching warm slot for (timestep, signature, schedule
        offset) with its float32 signature distance, or None.

        ``threshold`` is the *per-request* hit bound (the quality policy's
        resolution); None falls back to the ring default.  ``offset`` is
        the request's schedule truncation key — only slots captured under
        the same truncation match.  Read-only: no counters, no LRU touch
        (the admission policy uses this to score queued requests without
        perturbing eviction order).
        """
        thr = self.threshold if threshold is None else threshold
        mask = self.valid & (self.bucket == self.bucket_of(t)) & (self.offset == offset)
        # disjoint scopes: intra = own slots only, cross = other requests'
        # slots only (a request's own slot sits at distance 0 and would
        # trivially pass any positive threshold)
        mask &= (self.rid == rid) if self.mode == "intra" else (self.rid != rid)
        if not mask.any():
            return None
        d = np.linalg.norm(self.sig - np.asarray(sig, np.float32), axis=1)
        d = d / (np.linalg.norm(self.sig, axis=1) + 1e-12)
        d = np.where(mask, d, np.inf).astype(np.float32)
        best = int(np.argmin(d))
        # strict: threshold 0 never hits (bit-exactness guarantee); the
        # float32 distance is also what the jitted micro-step re-compares
        # against the lane's threshold leaf, so host and device agree
        return (best, float(d[best])) if d[best] < thr else None

    def probe(
        self, t: int, sig: np.ndarray, rid: int, threshold: float | None = None,
        offset: int = 0,
    ) -> int | None:
        """Slot-only convenience over :meth:`probe_distance`."""
        hit = self.probe_distance(t, sig, rid, threshold, offset)
        return None if hit is None else hit[0]

    def lookup(
        self, t: int, sig: np.ndarray, rid: int, threshold: float | None = None,
        offset: int = 0,
    ) -> int | None:
        """Probe + hit/miss accounting + LRU touch, as one call.

        For callers that serve a request immediately on a hit.  The engine
        instead probes speculatively (:meth:`probe`) and settles accounting
        only for decisions that *execute* (:meth:`note_hit` /
        :meth:`note_miss`), so branch-vote losers neither skew the stats
        nor keep slots artificially warm.
        """
        slot = self.probe(t, sig, rid, threshold, offset)
        if slot is not None:
            self.note_hit(slot)
        else:
            self.note_miss()
        return slot

    def note_hit(self, slot: int) -> None:
        """An executed demotion consumed ``slot``: count it + touch LRU."""
        self.probes += 1
        self.probe_hits += 1
        self._touch(slot)

    def note_miss(self) -> None:
        """A probed FULL step executed as FULL (no warm slot matched)."""
        self.probes += 1

    def plan_warmth(self, req, shard: int | None = None) -> float:
        """Fraction of a queued request's FULL steps that would hit now,
        probed at the request's *own* per-step thresholds (the quality
        policy's resolution — a draft request scores warmer than an exact
        one against the same slots, and a threshold-0 request always
        scores 0).

        ``shard`` is accepted (and ignored) so single-ring and sharded
        caches expose one signature to the cache-aware scheduler.

        Duck-typed on the engine's ``GenRequest`` (needs ``_lane_plan`` and
        ``_sig``); anything else scores 0 — schedulers stay usable with
        plain fakes in tests.
        """
        lp = getattr(req, "_lane_plan", None)
        sig = getattr(req, "_sig", None)
        if lp is None or sig is None or not self.valid.any():
            return 0.0
        thr = getattr(lp, "thr", None)
        off = int(getattr(req, "sched_offset", 0))
        hits, fulls = 0, 0
        for i in range(lp.n_steps):
            if lp.branches[i] != SM.FULL:
                continue
            fulls += 1
            step_thr = None if thr is None or i >= len(thr) else float(thr[i])
            if self.probe(
                int(lp.ts[i]), sig, getattr(req, "rid", -1), step_thr, off
            ) is not None:
                hits += 1
        return hits / max(fulls, 1)

    # -- insert --------------------------------------------------------------

    def reserve(
        self, t: int, sig: np.ndarray, rid: int, exclude: set[int] | tuple = (),
        offset: int = 0,
    ) -> int | None:
        """Claim a slot for (t, sig, rid, offset) and update the host keys.

        Slot choice: a valid slot already holding (rid, bucket) is refreshed
        in place (a request's newer capture supersedes its older one in the
        same bucket); otherwise the first empty slot; otherwise evict the
        LRU slot.  Metadata-only — pair with :meth:`insert_many` (or use
        :meth:`insert`) to fill the device slot.

        ``exclude`` holds slots already claimed by *this* micro-step's batch
        — a batched scatter with duplicate indices has unspecified winner
        order, so a caller reserving several slots before one
        :meth:`insert_many` must thread the claimed set through.  Returns
        None when every slot is excluded (ring smaller than the batch):
        that capture simply goes uncached.
        """
        b = self.bucket_of(t)
        free = np.ones((self.n_slots,), bool)
        for s in exclude:
            free[s] = False
        same = np.nonzero(
            free & self.valid & (self.rid == rid) & (self.bucket == b)
            & (self.offset == offset)
        )[0]
        if same.size:
            slot = int(same[0])
        else:
            empty = np.nonzero(free & ~self.valid)[0]
            if empty.size:
                slot = int(empty[0])
            else:
                avail = np.nonzero(free)[0]
                if not avail.size:
                    return None
                slot = int(avail[np.argmin(self.last_use[avail])])
                self.evictions += 1
                if self.on_evict is not None:
                    # victim's keys (and device features) are still intact:
                    # the spill tier copies them out before the overwrite
                    self.on_evict(slot)
        self.bucket[slot] = b
        self.sig[slot] = np.asarray(sig, np.float32)
        self.rid[slot] = rid
        self.offset[slot] = offset
        self.valid[slot] = True
        self._clock.value += 1
        self.gen[slot] = self._clock.value
        self.inserts += 1
        self._touch(slot)
        return slot

    # -- reporting -----------------------------------------------------------

    def counters(self) -> dict:
        return {
            "cache_probes": self.probes,
            "cache_probe_hits": self.probe_hits,
            "cache_inserts": self.inserts,
            "cache_evictions": self.evictions,
        }

    def _slot_row(self, s: int, ndigits: int) -> dict:
        return {
            "slot": int(s),
            "gen": int(self.gen[s]),
            "bucket": int(self.bucket[s]),
            "offset": int(self.offset[s]),
            "rid": int(self.rid[s]),
            "sig": [round(float(x), ndigits) for x in self.sig[s]],
        }

    def slot_summary(
        self, ndigits: int = 4, max_slots: int | None = MAX_SUMMARY_SLOTS
    ) -> list[dict]:
        """Wire-friendly keys of the warm slots — slot index, generation
        stamp, bucket, schedule offset, owner rid and the (rounded) prompt
        signature, never the feature tensors.  This is what a replica
        publishes in ``GET /stats`` so the router can score incoming
        requests against another process's ring
        (:func:`signature_distance` on the payload's synthesized
        signature).  ``max_slots`` bounds the payload: when the ring holds
        more warm slots, only the most-recently-used ones are reported
        (consumers must treat the table as a best-effort subset).
        """
        warm = np.nonzero(self.valid)[0]
        if max_slots is not None and warm.size > max_slots:
            keep = warm[np.argsort(self.last_use[warm])][-max_slots:]
            warm = np.sort(keep)
        return [self._slot_row(int(s), ndigits) for s in warm]

    def key_delta(self, since: int = 0, ndigits: int = 4) -> list[dict]:
        """Warm-slot rows written after generation ``since`` (same row
        shape as :meth:`slot_summary` — each row carries its slot index,
        so consumers merge deltas by replacing prior rows per slot).
        Capped at :data:`MAX_SUMMARY_SLOTS` newest generations."""
        fresh = np.nonzero(self.valid & (self.gen > int(since)))[0]
        if fresh.size > MAX_SUMMARY_SLOTS:
            keep = fresh[np.argsort(self.gen[fresh])][-MAX_SUMMARY_SLOTS:]
            fresh = np.sort(keep)
        return [self._slot_row(int(s), ndigits) for s in fresh]


class FeatureCache(SlotRing):
    """Fixed-size LRU feature cache: device slots + host keys.

    One instance is owned by a :class:`~repro.serving.engine.DiffusionEngine`;
    the engine probes before each micro-step (host metadata only), passes the
    winning slot per lane into the jitted micro-step as ``feat_source``, and
    inserts fresh FULL-step captures afterwards.
    """

    def __init__(
        self,
        ucfg: UNetConfig,
        e_sk: int,
        e_rf: int,
        *,
        n_slots: int = 16,
        threshold: float = 0.15,
        t_bucket: int = 125,
        mode: str = "cross",
        spill_mb: float = 0.0,
        dtype=jnp.float32,
    ):
        self._sk_shape = (n_slots, 2) + SM.feat_shape(ucfg, e_sk, 1)[1:]
        self._rf_shape = (n_slots, 2) + SM.feat_shape(ucfg, e_rf, 1)[1:]
        self._dtype = dtype
        super().__init__(
            n_slots, ucfg.ctx_dim, threshold=threshold, t_bucket=t_bucket, mode=mode
        )
        self.spill: SpillRing | None = None
        if spill_mb > 0:
            self.spill = SpillRing(int(spill_mb * 1024 * 1024), mode=mode)
            self.on_evict = self._demote
        self._reset_state()

    # -- lifecycle -----------------------------------------------------------

    def _reset_state(self) -> None:
        self.state = CacheState(
            f_sk=jnp.zeros(self._sk_shape, self._dtype),
            f_rf=jnp.zeros(self._rf_shape, self._dtype),
        )

    def reset(self) -> None:
        """Drop all slots and counters (cold cache)."""
        self.reset_meta()
        if self.spill is not None:
            self.spill.reset()
        self._reset_state()

    # -- spill tier ----------------------------------------------------------

    def _demote(self, slot: int) -> None:
        """Eviction hook: park the victim's features in host RAM under its
        old key (a float32-lossless numpy copy) before the overwrite."""
        if not self.valid[slot]:
            return
        self.spill.put(
            int(self.bucket[slot]), int(self.offset[slot]), int(self.rid[slot]),
            self.sig[slot],
            np.asarray(self.state.f_sk[slot]), np.asarray(self.state.f_rf[slot]),
        )

    def promote(
        self, t: int, sig: np.ndarray, rid: int, threshold: float | None = None,
        offset: int = 0, exclude: set[int] | tuple = (),
    ) -> int | None:
        """Probe the spill tier for (t, sig, offset) and, on a match, lift
        the entry back onto a device slot (reserve + single-slot upload).

        The device slot keeps the *original* owner's rid — in cross mode a
        hit requires ``slot.rid != requester``, so re-keying the slot to
        the requester would make the promoted features unusable to the very
        request that warranted the promotion.  The entry stays spill-
        resident (LRU-touched), so a later eviction of the promoted slot
        just refreshes it.  Returns the device slot or None.
        """
        if self.spill is None:
            return None
        thr = self.threshold if threshold is None else threshold
        entry = self.spill.probe(self.bucket_of(t), sig, rid, thr, offset)
        if entry is None:
            return None
        slot = self.reserve(
            entry.bucket * self.t_bucket, entry.sig, entry.rid,
            exclude=exclude, offset=entry.offset,
        )
        if slot is None:
            return None
        self.state = _upload_slot(
            self.state, jnp.int32(slot),
            jnp.asarray(entry.f_sk), jnp.asarray(entry.f_rf),
        )
        self.spill.promotions += 1
        return slot

    # -- device insert -------------------------------------------------------

    def insert_many(
        self, f_sk: jax.Array, f_rf: jax.Array, lanes: np.ndarray, slots: np.ndarray
    ) -> None:
        """Fill reserved slots from lane caches in one device scatter.

        ``lanes``/``slots`` must have a fixed per-caller length (the engine
        pads to ``n_lanes`` so the scatter compiles once); padding entries
        carry ``slots[i] >= n_slots`` and are dropped device-side.
        """
        self.state = _insert_slots(
            self.state, f_sk, f_rf,
            jnp.asarray(lanes, jnp.int32), jnp.asarray(slots, jnp.int32),
        )

    def insert(
        self, f_sk: jax.Array, f_rf: jax.Array, lane: int, t: int, sig: np.ndarray, rid: int
    ) -> None:
        """Single-capture convenience wrapper: reserve + fill one slot."""
        slot = self.reserve(t, sig, rid)
        assert slot is not None  # nothing excluded -> a slot always exists
        self.insert_many(
            f_sk, f_rf, np.asarray([lane], np.int32), np.asarray([slot], np.int32)
        )

    # -- reporting -----------------------------------------------------------

    def stats(self) -> dict:
        out = {
            "cache_mode": self.mode,
            "cache_slots": self.n_slots,
            "cache_warm_slots": self.n_warm,
            **self.counters(),
        }
        if self.spill is not None:
            out.update(self.spill.stats())
        return out

    def slots_summary(self) -> dict:
        """Ring geometry + warm-slot keys, as published in ``GET /stats``.

        ``version`` is the ring's newest key generation: a consumer that
        remembers it can ask ``key_delta(since=version)`` for just the
        changes (and treats a version that went *backwards* as a restart,
        replacing its whole mirror).
        """
        return {
            "mode": self.mode,
            "threshold": self.threshold,
            "t_bucket": self.t_bucket,
            "version": self.version,
            "rings": [self.slot_summary()],
        }

    def keys_delta(self, since: int = 0) -> dict:
        """Incremental form of :meth:`slots_summary`: only slots whose key
        generation exceeds ``since`` (the ``GET /cache/keys`` payload)."""
        return {
            "mode": self.mode,
            "threshold": self.threshold,
            "t_bucket": self.t_bucket,
            "version": self.version,
            "since": int(since),
            "rings": [self.key_delta(since)],
        }


# ---------------------------------------------------------------------------
# Shard-local feature rings for the mesh-sharded engine.
# ---------------------------------------------------------------------------


def _make_sharded_insert(mesh):
    """Per-shard batched slot fill as one GSPMD scatter.

    The lane features arrive in the sharded engine's ``[N, 2, L, C]``
    layout and the cache state's slot axis is partitioned over the same
    ``("data",)`` mesh, so each shard scatters its own captures into its
    own local slots — feature tensors never cross a shard boundary.
    """
    from jax.sharding import PartitionSpec as P

    lane = P("data")

    def body(cache: CacheState, f_sk, f_rf, lanes, slots):
        # local: cache [S_local, 2, ...], f_* [P, 2, ...], lanes/slots [P]
        return CacheState(
            f_sk=cache.f_sk.at[slots].set(f_sk[lanes], mode="drop"),
            f_rf=cache.f_rf.at[slots].set(f_rf[lanes], mode="drop"),
        )

    mapped = jax.shard_map(
        body, mesh=mesh,
        in_specs=(lane, lane, lane, lane, lane),
        out_specs=lane,
        check_vma=False,
    )

    def insert(cache, f_sk, f_rf, lanes, slots):
        return mapped(cache, f_sk, f_rf, lanes, slots)

    return jax.jit(insert, donate_argnums=(0,))


class ShardedFeatureCache:
    """Shard-local LRU rings sharing one mesh-sharded device state.

    Partitioning the PR 2 feature cache follows the lane partition: shard
    ``d`` owns slots ``[d * S, (d + 1) * S)`` of the combined
    :class:`CacheState` (slot axis sharded over ``("data",)``), and one
    :class:`SlotRing` of host metadata per shard.  Captures are only
    probed, reserved and consumed *within* a shard — a lane's warm slots
    live on the lane's own device, so serving a hit is a device-local
    gather and reuse never ships feature tensors between shards.  The
    cost is reuse reach: two near-identical prompts on different shards
    cannot share features, which is exactly what the scheduler's
    warm-shard routing (:class:`~repro.serving.scheduler.CacheAwareScheduler`
    with ``shard`` hints) exists to avoid.

    Slot indices at this API are *shard-local* (what the sharded
    micro-step's ``feat_src`` consumes); only the device scatter sees the
    combined slot axis.
    """

    def __init__(
        self,
        ucfg: UNetConfig,
        e_sk: int,
        e_rf: int,
        mesh,
        *,
        slots_per_shard: int = 16,
        threshold: float = 0.15,
        t_bucket: int = 125,
        mode: str = "cross",
        spill_mb: float = 0.0,
        dtype=jnp.float32,
    ):
        self.mesh = mesh
        self.n_shards = mesh.shape["data"]
        self.slots_per_shard = slots_per_shard
        self.mode = mode
        self.threshold = threshold
        self.t_bucket = t_bucket
        self.rings = [
            SlotRing(
                slots_per_shard, ucfg.ctx_dim,
                threshold=threshold, t_bucket=t_bucket, mode=mode,
            )
            for _ in range(self.n_shards)
        ]
        # one generation clock across all rings: slot gens are totally
        # ordered engine-wide, so a single scalar cursor drives key deltas
        for ring in self.rings[1:]:
            ring._clock = self.rings[0]._clock
        # ONE spill ring shared by every shard: demoted captures from any
        # shard can be promoted onto any other, which is where the global
        # (cross-shard) capacity win comes from
        self.spill: SpillRing | None = None
        if spill_mb > 0:
            self.spill = SpillRing(int(spill_mb * 1024 * 1024), mode=mode)
            for d, ring in enumerate(self.rings):
                ring.on_evict = functools.partial(self._demote, d)
        total = self.n_shards * slots_per_shard
        self._sk_shape = (total, 2) + SM.feat_shape(ucfg, e_sk, 1)[1:]
        self._rf_shape = (total, 2) + SM.feat_shape(ucfg, e_rf, 1)[1:]
        self._dtype = dtype
        self._insert = _make_sharded_insert(mesh)
        self.reset()

    # -- lifecycle -----------------------------------------------------------

    def reset(self) -> None:
        from repro.common.sharding import lane_sharding

        for ring in self.rings:
            ring.reset_meta()
        if self.spill is not None:
            self.spill.reset()
        sh = lane_sharding(self.mesh)
        self.state = CacheState(
            f_sk=jax.device_put(jnp.zeros(self._sk_shape, self._dtype), sh),
            f_rf=jax.device_put(jnp.zeros(self._rf_shape, self._dtype), sh),
        )

    # -- spill tier ----------------------------------------------------------

    def _demote(self, shard: int, slot: int) -> None:
        """Ring ``shard``'s eviction hook: copy the victim (global slot
        ``shard * S + slot``) to the shared host spill under its old key."""
        ring = self.rings[shard]
        if not ring.valid[slot]:
            return
        g = shard * self.slots_per_shard + slot
        self.spill.put(
            int(ring.bucket[slot]), int(ring.offset[slot]), int(ring.rid[slot]),
            ring.sig[slot],
            np.asarray(self.state.f_sk[g]), np.asarray(self.state.f_rf[g]),
        )

    def promote(
        self, shard: int, t: int, sig: np.ndarray, rid: int,
        threshold: float | None = None, offset: int = 0,
        exclude: set[int] | tuple = (),
    ) -> int | None:
        """Lift a spill-resident match onto shard ``shard``'s ring.

        Because the spill is shared, this is also the cross-shard feature
        path: a capture demoted off shard A's ring can be promoted onto
        shard B's when B admits a request it would serve.  Keeps the
        original owner rid (see :meth:`FeatureCache.promote`).  Returns the
        *shard-local* slot or None.
        """
        if self.spill is None:
            return None
        ring = self.rings[shard]
        thr = ring.threshold if threshold is None else threshold
        entry = self.spill.probe(ring.bucket_of(t), sig, rid, thr, offset)
        if entry is None:
            return None
        slot = ring.reserve(
            entry.bucket * self.t_bucket, entry.sig, entry.rid,
            exclude=exclude, offset=entry.offset,
        )
        if slot is None:
            return None
        g = shard * self.slots_per_shard + slot
        self.state = _upload_slot(
            self.state, jnp.int32(g),
            jnp.asarray(entry.f_sk), jnp.asarray(entry.f_rf),
        )
        self.spill.promotions += 1
        return slot

    # -- shard-local metadata ops -------------------------------------------

    def probe(
        self, shard: int, t: int, sig: np.ndarray, rid: int,
        threshold: float | None = None, offset: int = 0,
    ) -> int | None:
        return self.rings[shard].probe(t, sig, rid, threshold, offset)

    def probe_distance(
        self, shard: int, t: int, sig: np.ndarray, rid: int,
        threshold: float | None = None, offset: int = 0,
    ) -> tuple[int, float] | None:
        return self.rings[shard].probe_distance(t, sig, rid, threshold, offset)

    def note_hit(self, shard: int, slot: int) -> None:
        self.rings[shard].note_hit(slot)

    def note_miss(self, shard: int) -> None:
        self.rings[shard].note_miss()

    def reserve(
        self, shard: int, t: int, sig: np.ndarray, rid: int,
        exclude: set[int] | tuple = (), offset: int = 0,
    ) -> int | None:
        return self.rings[shard].reserve(t, sig, rid, exclude=exclude, offset=offset)

    def plan_warmth(self, req, shard: int | None = None) -> float:
        """Warmth of one shard's ring, or the best shard's when unpinned."""
        if shard is not None:
            return self.rings[shard].plan_warmth(req)
        return max(ring.plan_warmth(req) for ring in self.rings)

    @property
    def n_warm(self) -> int:
        return sum(ring.n_warm for ring in self.rings)

    # -- device insert -------------------------------------------------------

    def insert_many(
        self, f_sk: jax.Array, f_rf: jax.Array, lanes: np.ndarray, slots: np.ndarray
    ) -> None:
        """Per-shard batched slot fill (one sharded scatter dispatch).

        ``lanes``/``slots`` are padded to ``n_lanes`` with *shard-local*
        indices laid out in per-shard segments: positions
        ``[d * P, (d + 1) * P)`` hold shard ``d``'s entries.  Padding
        entries carry ``slots[i] >= slots_per_shard`` and are dropped
        device-side.
        """
        self.state = self._insert(
            self.state, f_sk, f_rf,
            jnp.asarray(lanes, jnp.int32), jnp.asarray(slots, jnp.int32),
        )

    # -- reporting -----------------------------------------------------------

    def stats(self) -> dict:
        agg = {
            "cache_mode": self.mode,
            "cache_shards": self.n_shards,
            "cache_slots": self.n_shards * self.slots_per_shard,
            "cache_warm_slots": self.n_warm,
            "cache_probes": sum(r.probes for r in self.rings),
            "cache_probe_hits": sum(r.probe_hits for r in self.rings),
            "cache_inserts": sum(r.inserts for r in self.rings),
            "cache_evictions": sum(r.evictions for r in self.rings),
        }
        agg["shard_hit_rates"] = [
            round(r.probe_hits / r.probes, 3) if r.probes else 0.0 for r in self.rings
        ]
        if self.spill is not None:
            agg.update(self.spill.stats())
        return agg

    @property
    def version(self) -> int:
        """Newest key generation across all rings (shared clock)."""
        return self.rings[0].version

    def slots_summary(self) -> dict:
        """Per-shard ring geometry + warm-slot keys (``GET /stats``).

        ``version`` is the shared generation clock — one scalar cursor
        covers every ring, so the aggregated table gossips incrementally
        through :meth:`keys_delta` exactly like the single-ring cache.
        """
        return {
            "mode": self.mode,
            "threshold": self.threshold,
            "t_bucket": self.t_bucket,
            "version": self.version,
            "rings": [ring.slot_summary() for ring in self.rings],
        }

    def keys_delta(self, since: int = 0) -> dict:
        """Incremental form of :meth:`slots_summary` (``GET /cache/keys``)."""
        return {
            "mode": self.mode,
            "threshold": self.threshold,
            "t_bucket": self.t_bucket,
            "version": self.version,
            "since": int(since),
            "rings": [ring.key_delta(since) for ring in self.rings],
        }
