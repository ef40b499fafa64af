"""Serving metrics: the engine's and driver's counters and spans.

The engine records one sample per micro-step (``record_step``: occupancy =
fraction of lanes holding a request, advance efficiency = fraction of
*active* lanes the step actually moved) and one sample per completed
request (queue + service latency).  Per-tick quantities are kept as running
sums, so ``summary()`` costs the same at any uptime and two summaries taken
apart give a window's deltas.  ``summary()`` collapses everything into the
flat dict printed by ``launch/serve.py``, served at ``GET /stats`` and
consumed by ``benchmarks/bench_serving.py``.

``span(name, **ids)`` is the program's one tracing hook: it opens a
``jax.profiler.TraceAnnotation`` (so the span sits on the profiler's host
timeline, ids as its metadata) and adds the interval to ``spans[name]``.
It is always on and never touches a device value, so it adds no sync.
JAX is imported only when a span opens: the router's process imports this
module and stays jax-free.

``drain_gap_s`` / ``drains`` measure the host time between a tick's last
retirement sync (``mark_sync``, after the latent read-back returns: the
device has run out of queued work) and the next micro-step dispatch
(``mark_dispatch``).  A sync followed by the engine running dry
(``mark_idle``) is not counted: that gap is no work, not host time.
"""
from __future__ import annotations

import contextlib
import dataclasses
import time
from typing import Iterator, Sequence

import numpy as np

#: branch class names, costliest first, indexed like the sampler's
#: ``FULL, SKETCH, REFINE = 0, 1, 2``
CLASSES = ("full", "sketch", "refine")


@dataclasses.dataclass
class ServingMetrics:
    latencies_s: list[float] = dataclasses.field(default_factory=list)
    queue_waits_s: list[float] = dataclasses.field(default_factory=list)
    micro_steps: int = 0
    lane_steps_advanced: int = 0
    #: active lanes summed over micro-steps (held slots, advanced or not)
    lane_slots_active: int = 0
    #: micro-steps per branch class; a sharded tick counts under the
    #: costliest class any shard advanced (the one that sets its length)
    ticks_by_class: dict[str, int] = dataclasses.field(
        default_factory=lambda: dict.fromkeys(CLASSES, 0)
    )
    #: micro-step dispatches, and the U-Net rows they computed (CFG rows,
    #: padding and masked rows included): ``1 - 2 * lane_steps_advanced /
    #: unet_rows`` is the share of computed rows thrown away
    micro_dispatches: int = 0
    unet_rows: int = 0
    #: running sums behind ``mean_occupancy`` / ``mean_advance_eff``
    occupancy_sum: float = 0.0
    advance_eff_sum: float = 0.0
    #: micro-steps with at least one active lane (``advance_eff`` samples)
    advance_eff_n: int = 0
    #: per-shard active-lane counts summed over micro-steps, and the
    #: number of such samples (sharded engine only)
    shard_active_sum: list[int] = dataclasses.field(default_factory=list)
    shard_ticks: int = 0
    #: FULL lane-steps actually executed (each one a full U-Net pass)
    full_steps: int = 0
    #: SKETCH / REFINE lane-steps actually executed (partial U-Net passes,
    #: demoted steps included — executed-class accounting for /stats)
    sketch_steps: int = 0
    refine_steps: int = 0
    #: planned-FULL lane-steps served from the feature cache as SKETCH
    demoted_steps: int = 0
    #: planned-SKETCH lane-steps served from the feature cache as REFINE
    demoted_refine_steps: int = 0
    # -- cache-tier attribution (which tier served / placed the work) --------
    #: executed demotions served straight from the device (HBM) slot ring
    hbm_hits: int = 0
    #: spill-resident captures lifted back onto the device ring at admission
    #: (the host-RAM tier paying off; incremented by the engine's prefetch)
    spill_promotions: int = 0
    #: admissions redirected to a cache-warm shard/replica by gossiped slot
    #: keys instead of the load-only default placement
    gossip_routed: int = 0
    #: submitted requests per resolved quality tier ("full"/"pas" = legacy)
    quality_mix: dict[str, int] = dataclasses.field(default_factory=dict)
    #: host spans by name: [count, total seconds] (see ``span``)
    spans: dict[str, list] = dataclasses.field(default_factory=dict)
    #: host seconds from a retirement sync to the next dispatch, and how
    #: many such gaps were counted
    drain_gap_s: float = 0.0
    drains: int = 0
    wall_s: float = 0.0
    _t_sync: float | None = dataclasses.field(default=None, repr=False)

    def record_step(
        self,
        n_lanes: int,
        n_active: int,
        n_advanced: int,
        n_full: int = 0,
        n_demoted: int = 0,
        n_sketch: int = 0,
        n_refine: int = 0,
        n_demoted_refine: int = 0,
        shard_active: Sequence[int] | None = None,
        dispatches: int = 0,
        unet_rows: int = 0,
    ) -> None:
        self.micro_steps += 1
        self.micro_dispatches += dispatches
        self.unet_rows += unet_rows
        self.lane_steps_advanced += n_advanced
        self.lane_slots_active += n_active
        cls = next((c for c, n in zip(CLASSES, (n_full, n_sketch, n_refine)) if n), None)
        if cls is not None:
            self.ticks_by_class[cls] += 1
        self.full_steps += n_full
        self.sketch_steps += n_sketch
        self.refine_steps += n_refine
        self.demoted_steps += n_demoted
        self.demoted_refine_steps += n_demoted_refine
        # every executed demotion was served by a device-resident slot
        self.hbm_hits += n_demoted + n_demoted_refine
        self.occupancy_sum += n_active / max(n_lanes, 1)
        if n_active:
            self.advance_eff_sum += n_advanced / n_active
            self.advance_eff_n += 1
        if shard_active is not None:
            base = self.shard_active_sum or [0] * len(shard_active)
            self.shard_active_sum = [s + a for s, a in zip(base, shard_active)]
            self.shard_ticks += 1

    def record_submission(self, tier: str) -> None:
        """Count one submitted request under its resolved quality tier."""
        self.quality_mix[tier] = self.quality_mix.get(tier, 0) + 1

    @contextlib.contextmanager
    def span(self, name: str, **ids) -> Iterator[None]:
        """Time the enclosed host work as ``name``: a profiler host span
        carrying ``ids`` as metadata, and one more interval in
        ``spans[name]``."""
        from jax.profiler import TraceAnnotation

        t0 = time.perf_counter()
        try:
            with TraceAnnotation(name, **ids):
                yield
        finally:
            acc = self.spans.setdefault(name, [0, 0.0])
            acc[0] += 1
            acc[1] += time.perf_counter() - t0

    def mark_sync(self) -> None:
        """A retirement read-back returned: the device queue is empty."""
        self._t_sync = time.perf_counter()

    def mark_dispatch(self) -> None:
        """A micro-step is being dispatched: close the drain gap, if a sync
        happened since the last dispatch."""
        if self._t_sync is not None:
            self.drain_gap_s += time.perf_counter() - self._t_sync
            self.drains += 1
            self._t_sync = None

    def mark_idle(self) -> None:
        """The engine holds no work: the wait for the next request is not a
        drain gap."""
        self._t_sync = None

    def record_completion(self, latency_s: float, queue_wait_s: float) -> None:
        self.latencies_s.append(latency_s)
        self.queue_waits_s.append(queue_wait_s)

    def summary(self) -> dict:
        lat = np.asarray(self.latencies_s) if self.latencies_s else np.zeros(1)
        n = len(self.latencies_s)
        return {
            "requests": n,
            "wall_s": round(self.wall_s, 3),
            "throughput_req_s": round(n / self.wall_s, 3) if self.wall_s else 0.0,
            "p50_latency_s": round(float(np.percentile(lat, 50)), 3),
            "p99_latency_s": round(float(np.percentile(lat, 99)), 3),
            "mean_queue_wait_s": round(float(np.mean(self.queue_waits_s)), 3)
            if self.queue_waits_s
            else 0.0,
            "micro_steps": self.micro_steps,
            "lane_steps_advanced": self.lane_steps_advanced,
            "lane_slots_active": self.lane_slots_active,
            "ticks_by_class": dict(self.ticks_by_class),
            "micro_dispatches": self.micro_dispatches,
            "unet_rows": self.unet_rows,
            "mean_occupancy": round(self.occupancy_sum / self.micro_steps, 3)
            if self.micro_steps
            else 0.0,
            "mean_advance_eff": round(self.advance_eff_sum / self.advance_eff_n, 3)
            if self.advance_eff_n
            else 0.0,
            "full_steps": self.full_steps,
            "sketch_steps": self.sketch_steps,
            "refine_steps": self.refine_steps,
            "demoted_full_steps": self.demoted_steps,
            "demoted_sketch_steps": self.demoted_refine_steps,
            # fraction of planned FULL lane-steps served from the cache
            "cache_hit_rate": round(
                self.demoted_steps / max(self.full_steps + self.demoted_steps, 1), 3
            ),
            # per-tier attribution: device-ring hits, spill-tier promotions,
            # gossip-directed admissions (all zero without the cache tiers)
            "hbm_hits": self.hbm_hits,
            "spill_promotions": self.spill_promotions,
            "gossip_routed": self.gossip_routed,
            "quality_mix": dict(sorted(self.quality_mix.items())),
            "spans": {
                k: {"n": c, "s": round(t, 6)} for k, (c, t) in sorted(self.spans.items())
            },
            "drain_gap_s": round(self.drain_gap_s, 6),
            "drains": self.drains,
            **self._shard_summary(),
        }

    def _shard_summary(self) -> dict:
        """Lane-occupancy balance across shards (sharded engine only).

        ``shard_occupancy_balance`` is min/max of the per-shard mean
        active-lane counts: 1.0 = perfectly balanced admission, 0.0 = at
        least one shard sat idle the whole run.
        """
        if not self.shard_ticks:
            return {}
        per_shard = [a / self.shard_ticks for a in self.shard_active_sum]
        peak = max(per_shard)
        return {
            "shard_mean_active": [round(v, 3) for v in per_shard],
            "shard_occupancy_balance": round(min(per_shard) / peak, 3) if peak > 0 else 0.0,
        }
