"""Property-based packing-policy invariants (host logic, plus one
device-level mixed-threshold isolation property at the end).

A miniature of the engine's event loop (`_Sim`) drives the real schedulers
over randomized arrival traces and branch plans, asserting the three
liveness/safety invariants the serving layer promises:

* **bounded starvation** — no active lane sits unadvanced longer than
  ``patience + n_lanes`` micro-steps (one aging override can only serve one
  class per step, so simultaneous stalls queue behind each other);
* **admission safety** — a request is admitted at most once, only ever into
  a free lane, and only after it was submitted;
* **eventual retirement** — every submitted request retires within the
  trivial work bound (total plan steps x (patience + 1) + admissions).

Random traces come in two flavours: seeded numpy cases that always run
(keeping the invariants in the tier-1 gate even without hypothesis), and
``@given`` fuzzing with the pinned hypothesis from requirements-dev.txt
(degrading to skips via the fallback shim on bare containers).
"""
import numpy as np
import pytest

from hypothesis import given, settings, strategies as st

from repro.serving.scheduler import CacheAwareScheduler, FIFOScheduler, PlanAwareScheduler


class _FakeReq:
    def __init__(self, rid, branches):
        self.rid = rid
        self.branches = np.asarray(branches, np.int32)

    def branch_vector(self):
        return self.branches


def _make_scheduler(kind: str, window: int):
    if kind == "fifo":
        return FIFOScheduler()
    if kind == "plan":
        return PlanAwareScheduler(window=window)
    return CacheAwareScheduler(window=window)  # no cache attached -> plan-aware


class _Sim:
    """Host-only mirror of ``DiffusionEngine.step``'s control flow."""

    def __init__(self, scheduler, n_lanes: int, plans: list[np.ndarray]):
        self.s = scheduler
        self.n_lanes = n_lanes
        self.reqs = [_FakeReq(i, p) for i, p in enumerate(plans)]
        self.lane_req = [None] * n_lanes
        self.lane_step = [0] * n_lanes
        self.stall = np.zeros(n_lanes, np.int64)
        self.retired: list[int] = []
        self.admitted: list[int] = []
        self.micro_steps = 0
        self.max_stall_seen = 0

    def _remaining(self):
        return [
            r.branches[self.lane_step[i]:]
            for i, r in enumerate(self.lane_req)
            if r is not None
        ]

    def _backfill(self):
        for lane in range(self.n_lanes):
            if self.lane_req[lane] is not None:
                continue
            req = self.s.next_request(self._remaining())
            if req is None:
                return
            # admission safety: never admit twice, never into a busy lane
            assert req.rid not in self.admitted, f"rid {req.rid} admitted twice"
            self.admitted.append(req.rid)
            self.lane_req[lane] = req
            self.lane_step[lane] = 0
            self.stall[lane] = 0

    def run(self):
        for r in self.reqs:
            self.s.add(r)
        total_steps = sum(len(r.branches) for r in self.reqs)
        bound = total_steps * (self.s.patience + 1) + len(self.reqs) + 1
        while len(self.retired) < len(self.reqs):
            self.micro_steps += 1
            assert self.micro_steps <= bound, (
                f"no progress: {len(self.retired)}/{len(self.reqs)} retired "
                f"after {self.micro_steps} micro-steps"
            )
            self._backfill()
            active = [i for i in range(self.n_lanes) if self.lane_req[i] is not None]
            assert active, "deadlock: pending requests but no active lanes"
            classes = np.array(
                [self.lane_req[i].branches[self.lane_step[i]] for i in active], np.int64
            )
            b = self.s.pick_branch(classes, self.stall[active])
            advanced = [i for k, i in enumerate(active) if classes[k] == b]
            assert advanced, "branch pick advanced no lane"
            self.stall[active] += 1
            for lane in advanced:
                self.stall[lane] = 0
                self.lane_step[lane] += 1
                req = self.lane_req[lane]
                if self.lane_step[lane] >= len(req.branches):
                    self.retired.append(req.rid)
                    self.lane_req[lane] = None
            self.max_stall_seen = max(self.max_stall_seen, int(self.stall.max()))
            # bounded starvation: aging can only clear one class per step,
            # so simultaneous stalls queue at most n_lanes deep
            assert self.max_stall_seen <= self.s.patience + self.n_lanes, (
                f"lane starved {self.max_stall_seen} micro-steps "
                f"(patience={self.s.patience}, lanes={self.n_lanes})"
            )
        return self


def _check_trace(kind, window, n_lanes, plans):
    plans = [np.asarray(p, np.int32) for p in plans if len(p)]
    if not plans:
        return
    sim = _Sim(_make_scheduler(kind, window), n_lanes, plans).run()
    assert sorted(sim.retired) == list(range(len(plans))), "a request never retired"
    assert sorted(sim.admitted) == list(range(len(plans)))


SCHEDULERS = ("fifo", "plan", "cache")


# ---------------------------------------------------------------------------
# Seeded numpy traces — always run (tier-1, no hypothesis needed)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("kind", SCHEDULERS)
@pytest.mark.parametrize("seed", range(8))
def test_random_trace_invariants(kind, seed):
    rng = np.random.default_rng(1000 * seed + 7)
    n_lanes = int(rng.integers(1, 5))
    n_reqs = int(rng.integers(1, 13))
    plans = [
        rng.integers(0, 3, size=int(rng.integers(1, 7))).astype(np.int32)
        for _ in range(n_reqs)
    ]
    _check_trace(kind, int(rng.integers(1, 6)), n_lanes, plans)


def test_fifo_preserves_arrival_order_single_lane():
    sim = _Sim(FIFOScheduler(), 1, [np.zeros(2, np.int32) for _ in range(6)]).run()
    assert sim.retired == list(range(6))


def test_adversarial_minority_class_never_starves():
    """One REFINE-only plan against a wall of FULL-only plans: aging must
    pull it through on every scheduler."""
    plans = [np.full(6, 2, np.int32)] + [np.zeros(6, np.int32) for _ in range(7)]
    for kind in SCHEDULERS:
        _check_trace(kind, 4, 2, plans)


# ---------------------------------------------------------------------------
# Hypothesis fuzzing — runs under the pinned CI environment
# ---------------------------------------------------------------------------


@given(
    kind=st.sampled_from(SCHEDULERS),
    window=st.integers(1, 6),
    n_lanes=st.integers(1, 4),
    plans=st.lists(
        st.lists(st.integers(0, 2), min_size=1, max_size=8), min_size=0, max_size=14
    ),
)
@settings(max_examples=120, deadline=None)
def test_fuzz_trace_invariants(kind, window, n_lanes, plans):
    _check_trace(kind, window, n_lanes, plans)


@given(
    classes=st.lists(st.integers(0, 2), min_size=1, max_size=8),
    stalls=st.lists(st.integers(0, 30), min_size=1, max_size=8),
)
@settings(max_examples=120, deadline=None)
def test_fuzz_pick_branch_always_serves_an_active_lane(classes, stalls):
    n = min(len(classes), len(stalls))
    classes = np.asarray(classes[:n], np.int64)
    stalls = np.asarray(stalls[:n], np.int64)
    s = FIFOScheduler()
    b = s.pick_branch(classes, stalls)
    assert b in classes, "picked a branch class no active lane is in"
    if stalls.max() >= s.patience:
        assert b == classes[int(np.argmax(stalls))], "aging override ignored"


@given(
    window=st.integers(2, 5),
    aligned=st.lists(st.integers(0, 2), min_size=2, max_size=6),
    n_competitors=st.integers(1, 10),
)
@settings(max_examples=60, deadline=None)
def test_fuzz_plan_aware_head_admission_is_bounded(window, aligned, n_competitors):
    """However many better-aligned competitors stream past, the queue head
    is admitted after at most max_head_skips bypasses."""
    s = PlanAwareScheduler(window=window)
    flight = [np.asarray(aligned, np.int32)]
    head_plan = (np.asarray(aligned, np.int32) + 1) % 3  # maximally misaligned
    s.add(_FakeReq(0, head_plan))
    admitted = []
    for i in range(1, n_competitors + s.max_head_skips + 2):
        s.add(_FakeReq(i, aligned))
        admitted.append(s.next_request(flight).rid)
        if 0 in admitted:
            break
    assert 0 in admitted
    assert admitted.index(0) <= s.max_head_skips


# ---------------------------------------------------------------------------
# Lifecycle traces: submits / cancels / drain interleaved with micro-steps
# (mirrors the HTTP frontend's driver: EngineDriver.submit/cancel/shutdown)
# ---------------------------------------------------------------------------


class _LifecycleSim:
    """Host-only mirror of the *driver's* control flow over the engine.

    Operations arrive as a trace of ``("submit", plan)``, ``("step",)``
    and ``("cancel", k)`` tuples (``k`` counts into the submission order);
    the run ends with a drain — step until every open request reaches a
    terminal state.  Cancellation uses the real ``scheduler.remove`` for
    queued requests and frees the lane for in-flight ones, exactly like
    ``DiffusionEngine.cancel``.
    """

    def __init__(self, scheduler, n_lanes: int):
        self.s = scheduler
        self.n_lanes = n_lanes
        self.lane_req = [None] * n_lanes
        self.lane_step = [0] * n_lanes
        self.stall = np.zeros(n_lanes, np.int64)
        self.reqs: list[_FakeReq] = []
        self.admitted: list[int] = []
        self.retired: list[int] = []
        self.cancelled: list[int] = []

    # -- driver operations ---------------------------------------------------

    def submit(self, plan) -> None:
        req = _FakeReq(len(self.reqs), plan)
        self.reqs.append(req)
        self.s.add(req)

    def cancel(self, rid: int) -> None:
        if rid in self.retired or rid in self.cancelled:
            return  # already terminal: driver ignores the control message
        if self.s.remove(rid):
            self.cancelled.append(rid)
            return
        for lane in range(self.n_lanes):
            if self.lane_req[lane] is not None and self.lane_req[lane].rid == rid:
                self.lane_req[lane] = None  # release: lane free for backfill
                self.stall[lane] = 0
                self.cancelled.append(rid)
                return

    def _backfill(self):
        for lane in range(self.n_lanes):
            if self.lane_req[lane] is not None:
                continue
            req = self.s.next_request([
                r.branches[self.lane_step[i]:]
                for i, r in enumerate(self.lane_req)
                if r is not None
            ])
            if req is None:
                return
            assert req.rid not in self.admitted, f"rid {req.rid} admitted twice"
            assert req.rid not in self.cancelled, "admitted a cancelled request"
            self.admitted.append(req.rid)
            self.lane_req[lane] = req
            self.lane_step[lane] = 0
            self.stall[lane] = 0

    def step(self):
        self._backfill()
        active = [i for i in range(self.n_lanes) if self.lane_req[i] is not None]
        if not active:
            return
        classes = np.array(
            [self.lane_req[i].branches[self.lane_step[i]] for i in active], np.int64
        )
        b = self.s.pick_branch(classes, self.stall[active])
        self.stall[active] += 1
        for k, lane in enumerate(active):
            if classes[k] != b:
                continue
            self.stall[lane] = 0
            self.lane_step[lane] += 1
            req = self.lane_req[lane]
            if self.lane_step[lane] >= len(req.branches):
                self.retired.append(req.rid)
                self.lane_req[lane] = None

    def open_rids(self) -> list[int]:
        terminal = set(self.retired) | set(self.cancelled)
        return [r.rid for r in self.reqs if r.rid not in terminal]

    def drain(self, bound: int) -> None:
        steps = 0
        while self.open_rids():
            steps += 1
            assert steps <= bound, "drain made no progress (lane leak?)"
            self.step()


def _run_lifecycle_trace(kind: str, window: int, n_lanes: int, ops: list[tuple]):
    """Execute a trace and assert the serving lifecycle invariants."""
    sim = _LifecycleSim(_make_scheduler(kind, window), n_lanes)
    for op in ops:
        if op[0] == "submit":
            sim.submit(op[1])
        elif op[0] == "step":
            sim.step()
        elif op[0] == "cancel" and sim.reqs:
            sim.cancel(op[1] % len(sim.reqs))
    total = sum(len(r.branches) for r in sim.reqs) + 1
    sim.drain(bound=total * (sim.s.patience + 1) + len(sim.reqs) + 1)

    # -- no lane leak: drain leaves nothing behind ---------------------------
    assert all(r is None for r in sim.lane_req), "drained with an occupied lane"
    assert len(sim.s) == 0, "drained with queued requests"

    # -- exactly-once terminal state per request -----------------------------
    terminal = sorted(sim.retired + sim.cancelled)
    assert terminal == list(range(len(sim.reqs))), "a request leaked or doubled"
    assert not (set(sim.retired) & set(sim.cancelled))

    # -- cancelled-before-admission requests never touched a lane ------------
    for rid in sim.cancelled:
        if rid not in sim.admitted:
            assert all(
                (r is None or r.rid != rid) for r in sim.lane_req
            )

    # -- FIFO within identical plans: among requests whose branch plans are
    # byte-equal, admission preserves submission order (windowed scoring can
    # reorder *different* plans only; removal by cancel keeps the rest stable)
    order = {rid: i for i, rid in enumerate(sim.admitted)}
    by_plan: dict[bytes, list[int]] = {}
    for r in sim.reqs:
        if r.rid in order:
            by_plan.setdefault(r.branches.tobytes(), []).append(r.rid)
    for rids in by_plan.values():
        pos = [order[rid] for rid in rids]  # rids ascend in submission order
        assert pos == sorted(pos), f"FIFO-within-plan violated: {rids} admitted at {pos}"


LIFECYCLE_OPS = st.lists(
    st.one_of(
        st.tuples(st.just("submit"), st.lists(st.integers(0, 2), min_size=1, max_size=6)),
        st.tuples(st.just("step")),
        st.tuples(st.just("cancel"), st.integers(0, 30)),
    ),
    min_size=0,
    max_size=40,
)


@pytest.mark.parametrize("kind", SCHEDULERS)
@pytest.mark.parametrize("seed", range(6))
def test_lifecycle_trace_invariants_seeded(kind, seed):
    rng = np.random.default_rng(5000 * seed + 13)
    ops = []
    for _ in range(int(rng.integers(4, 36))):
        roll = rng.random()
        if roll < 0.45:
            ops.append(("submit", rng.integers(0, 3, size=int(rng.integers(1, 7))).tolist()))
        elif roll < 0.8:
            ops.append(("step",))
        else:
            ops.append(("cancel", int(rng.integers(0, 30))))
    _run_lifecycle_trace(kind, int(rng.integers(1, 5)), int(rng.integers(1, 4)), ops)


def test_lifecycle_cancel_in_lane_frees_it_for_backfill():
    """1 lane, 2 requests: cancelling the in-flight one mid-denoise must
    hand the lane to the queued one (the driver/backfill contract)."""
    for kind in SCHEDULERS:
        sim = _LifecycleSim(_make_scheduler(kind, 2), 1)
        sim.submit([0, 0, 0, 0])
        sim.submit([0, 0])
        sim.step()  # admits rid 0, advances it
        assert sim.lane_req[0].rid == 0
        sim.cancel(0)
        assert sim.lane_req[0] is None
        sim.drain(bound=64)
        assert sim.retired == [1] and sim.cancelled == [0]


@given(kind=st.sampled_from(SCHEDULERS), window=st.integers(1, 5),
       n_lanes=st.integers(1, 4), ops=LIFECYCLE_OPS)
@settings(max_examples=120, deadline=None)
def test_fuzz_lifecycle_trace_invariants(kind, window, n_lanes, ops):
    _run_lifecycle_trace(kind, window, n_lanes, list(ops))


# ---------------------------------------------------------------------------
# Mixed-threshold batches: the per-lane threshold leaf isolates lanes.
# (The one device-touching test in this module — it is the property the
# whole per-request-policy refactor must preserve: a quality=exact lane is
# bit-exact with cache off even while co-resident lanes in the same
# micro-step consume warm cache slots under draft thresholds.)
# ---------------------------------------------------------------------------


def test_exact_lane_bit_exact_amid_warm_draft_lanes():
    import numpy as _np

    from repro.serving import golden as G
    from repro.serving.engine import DiffusionEngine, EngineConfig, GenRequest
    from repro.serving.policy import QualityPolicy

    params = G.golden_params()
    policy = QualityPolicy(
        G.N_UP, l_sketch=G.L_SKETCH, l_refine=G.L_REFINE, base_threshold=0.3,
        t_bucket=1000,
    )
    twin_ctx = _np.random.default_rng(31).normal(
        size=(G.UCFG.ctx_len, G.UCFG.ctx_dim)
    ).astype(_np.float32) * 0.2

    def stream():
        reqs = []
        for rid, (t, quality, ctx_seed) in enumerate(
            ((6, "draft", None), (8, "exact", 77), (6, "draft", None))
        ):
            pol = policy.resolve(t, quality=quality)
            ctx = twin_ctx if ctx_seed is None else _np.random.default_rng(
                ctx_seed
            ).normal(size=(G.UCFG.ctx_len, G.UCFG.ctx_dim)).astype(_np.float32) * 0.2
            noise = _np.random.default_rng(500 + rid).normal(
                size=(G.UCFG.latent_size**2, G.UCFG.in_channels)
            ).astype(_np.float32)
            reqs.append(GenRequest(
                rid=rid, ctx=ctx, noise=noise, timesteps=t,
                plan=pol.plan, policy=pol,
            ))
        return reqs

    def run(cache_mode: str):
        cfg = EngineConfig(
            n_lanes=2, max_steps=8, l_sketch=G.L_SKETCH, l_refine=G.L_REFINE,
            decode_images=False, cache_mode=cache_mode, cache_slots=8,
            cache_threshold=0.3, cache_t_bucket=1000,
        )
        eng = DiffusionEngine(G.UCFG, G.DCFG, params, None, cfg)
        done, summary = eng.run(stream())
        return {d.rid: d.latent for d in done}, summary

    base, _ = run("off")
    warm, summary = run("cross")
    # the draft twins must actually share features in the warm run —
    # otherwise this asserts nothing about mixed-threshold micro-steps
    assert (
        summary["demoted_full_steps"] + summary["demoted_sketch_steps"] > 0
    ), f"draft lanes never went warm: {summary}"
    assert summary["quality_mix"] == {"draft": 2, "exact": 1}
    # exact (threshold 0) lane: bit-equal despite co-resident warm lanes
    np.testing.assert_array_equal(
        warm[1], base[1],
        err_msg="quality=exact lane diverged from the cache-off engine "
        "while co-resident draft lanes consumed warm slots",
    )
    # and the draft lanes really did change (they consumed cached features)
    assert any(
        not _np.array_equal(warm[r], base[r]) for r in (0, 2)
    ), "warm draft lanes produced cache-off latents — no reuse happened?"


# ---------------------------------------------------------------------------
# SlotRing key-table invariants: LRU eviction order, offset-keyed isolation,
# generation-counter monotonicity — the correctness base of the gossip
# protocol (routers merge key deltas by (slot, gen) and trust the victim
# the eviction hook hands to the spill tier to be the true LRU).
# ---------------------------------------------------------------------------

from repro.serving.cache import SlotRing


def _ring(n_slots=4, mode="cross", threshold=0.25):
    return SlotRing(n_slots, 3, threshold=threshold, t_bucket=100, mode=mode)


def _apply_key_trace(ring: SlotRing, ops):
    """Drive reserve/touch ops, asserting the LRU + clock invariants at
    every step: each reserve ticks the clock exactly once and stamps the
    written slot with the new value; LRU touches never tick it; an
    eviction always claims the least-recently-used valid slot (checked
    inside the hook, while the victim's metadata is still intact)."""
    rng = np.random.default_rng(11)

    def on_evict(slot):
        assert ring.valid[slot], "evicted an empty slot"
        assert ring.last_use[slot] == ring.last_use[ring.valid].min(), (
            "evicted a slot that was not the LRU"
        )

    ring.on_evict = on_evict
    version = ring.version
    for kind, a, b in ops:
        if kind == "reserve":
            slot = ring.reserve(
                (a % 5) * ring.t_bucket, rng.normal(size=3).astype(np.float32),
                rid=b, offset=0,
            )
            assert slot is not None
            assert ring.version == version + 1, "reserve must tick the clock once"
            version = ring.version
            assert int(ring.gen[slot]) == version, "written slot not stamped newest"
        else:  # LRU touch of some warm slot (an executed hit)
            warm = np.nonzero(ring.valid)[0]
            if warm.size:
                ring.note_hit(int(warm[a % warm.size]))
                assert ring.version == version, "LRU touch must not tick the clock"
    gens = ring.gen[ring.valid]
    assert len(set(gens.tolist())) == gens.size, "duplicate generation stamps"
    assert (gens <= ring.version).all()


@pytest.mark.parametrize("seed", range(6))
def test_slot_ring_trace_invariants_seeded(seed):
    rng = np.random.default_rng(9000 + seed)
    ops = [
        (("reserve" if rng.random() < 0.7 else "touch"),
         int(rng.integers(0, 30)), int(rng.integers(0, 6)))
        for _ in range(int(rng.integers(5, 40)))
    ]
    _apply_key_trace(_ring(n_slots=int(rng.integers(1, 5))), ops)


@given(
    n_slots=st.integers(1, 5),
    ops=st.lists(
        st.tuples(st.sampled_from(("reserve", "touch")),
                  st.integers(0, 30), st.integers(0, 6)),
        min_size=0, max_size=50,
    ),
)
@settings(max_examples=120, deadline=None)
def test_fuzz_slot_ring_trace_invariants(n_slots, ops):
    _apply_key_trace(_ring(n_slots=n_slots), list(ops))


@given(
    offsets=st.lists(st.integers(0, 5), min_size=1, max_size=4, unique=True),
    probe_offset=st.integers(0, 6),
)
@settings(max_examples=60, deadline=None)
def test_fuzz_slot_ring_offset_isolation(offsets, probe_offset):
    """Slots are keyed by schedule offset: a probe only ever hits a slot
    captured under the same truncation, however close the signatures."""
    ring = _ring(n_slots=8)
    sig = np.ones(3, np.float32)
    for i, off in enumerate(offsets):
        ring.reserve(150, sig, rid=i, offset=off)
    hit = ring.probe(150, sig, rid=99, threshold=0.5, offset=probe_offset)
    if probe_offset in offsets:
        assert hit is not None and int(ring.offset[hit]) == probe_offset
    else:
        assert hit is None


@given(
    writes=st.lists(
        st.tuples(st.integers(0, 4), st.integers(0, 5), st.integers(0, 1)),
        min_size=0, max_size=40,
    ),
    sync_every=st.integers(1, 7),
)
@settings(max_examples=80, deadline=None)
def test_fuzz_key_delta_merge_reconstructs_summary(writes, sync_every):
    """A consumer that merges ``key_delta(since)`` rows by slot index from
    a monotone cursor ends up with exactly the full warm-slot summary —
    the property the router's gossip mirror depends on."""
    rng = np.random.default_rng(5)
    ring = _ring(n_slots=3)
    mirror: dict[int, dict] = {}
    cursor = 0
    for i, (b, rid, off) in enumerate(writes):
        ring.reserve(b * ring.t_bucket, rng.normal(size=3).astype(np.float32),
                     rid=rid, offset=off)
        if i % sync_every == 0:
            for row in ring.key_delta(cursor):
                mirror[row["slot"]] = row
            cursor = ring.version
    for row in ring.key_delta(cursor):
        mirror[row["slot"]] = row
    full = {row["slot"]: row for row in ring.slot_summary(max_slots=None)}
    assert mirror == full, "merged deltas diverged from the full key table"
    assert ring.key_delta(ring.version) == [], "cursor at head must be empty"
