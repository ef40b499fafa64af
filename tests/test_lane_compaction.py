"""Lane compaction in the single-device micro-step.

The micro-step gathers the K lanes of one dispatch (``idx``), runs the
U-Net on their 2K CFG rows and scatters the lanes marked ``valid`` back.
Its reference is the same step over the whole lane batch, ``idx =
arange(N)`` with ``valid`` the tick's advance mask: the masked computation
every tick ran before compaction.  The two run different batch sizes, which
XLA fuses differently, so advanced lanes are compared within the golden
harness's cross-program tolerance; every other lane must not move a bit.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.common.types import DiffusionConfig, PASPlan
from repro.configs import get_unet_config
from repro.core import sampler as SM
from repro.models import unet as U
from repro.serving import (
    DiffusionEngine,
    EngineConfig,
    GenRequest,
    make_plan_arrays,
)
from repro.serving import lanes as LN
from repro.serving.cache import CacheState
from repro.serving.metrics import ServingMetrics

TOY = get_unet_config("sd_toy")
N_UP = U.n_up_steps(TOY)
L = TOY.latent_size**2
L_SK, L_RF = min(3, N_UP), min(2, N_UP)
E_SK, E_RF = N_UP - L_SK, N_UP - L_RF
DCFG = DiffusionConfig(timesteps_sample=8)
N, MAX_STEPS, SLOTS = 4, 8, 3
K = LN.compact_capacity(N)
#: the golden harness's cross-program tolerance (tests/test_golden_latents.py)
ATOL = 2e-4
#: which lanes advance when ``n`` of them do (not in lane order on purpose)
ADVANCE_ORDER = (2, 0, 3, 1)
CLASSES = {"full": SM.FULL, "sketch": SM.SKETCH, "refine": SM.REFINE}


@pytest.fixture(scope="module")
def params():
    return U.init_unet(jax.random.key(0), TOY)


@pytest.fixture(scope="module")
def steps():
    return {
        cached: LN.make_micro_step(TOY, DCFG, E_SK, E_RF, cached=cached)
        for cached in (False, True)
    }


def lane_state(seed: int = 0) -> LN.LaneState:
    """Four lanes mid-flight: distinct steps, PNDM warm-up counts, features,
    and one inpaint lane (half its mask 0)."""
    rng = np.random.default_rng(seed)
    base = LN.init_lanes(TOY, N, MAX_STEPS, E_SK, E_RF)
    rand = lambda a, s=1.0: (rng.normal(size=a.shape) * s).astype(np.float32)
    plans = [make_plan_arrays(DCFG, t, None, MAX_STEPS, threshold=0.5) for t in (8, 6, 7, 5)]
    mask = np.ones((N, L, 1), np.float32)
    mask[2, : L // 2] = 0.0
    host = base._replace(
        x=rand(base.x), ets=rand(base.ets, 0.3), n_ets=np.array([0, 1, 3, 4], np.int32),
        f_sk=rand(base.f_sk, 0.5), f_rf=rand(base.f_rf, 0.5), ctx2=rand(base.ctx2, 0.2),
        branches=np.stack([p.branches for p in plans]),
        ts=np.stack([p.ts for p in plans]), t_prev=np.stack([p.t_prev for p in plans]),
        step=np.array([0, 2, 5, 4], np.int32),
        n_steps=np.array([p.n_steps for p in plans], np.int32),
        thr=np.stack([p.thr for p in plans]),
        mask=mask, x_init=rand(base.x_init), noise0=rand(base.noise0),
    )
    return jax.tree.map(jnp.asarray, host)


def cache_state(seed: int = 1) -> CacheState:
    rng = np.random.default_rng(seed)
    shape = lambda e: (SLOTS, 2) + SM.feat_shape(TOY, e, 1)[1:]
    return CacheState(
        f_sk=jnp.asarray(rng.normal(size=shape(E_SK)).astype(np.float32) * 0.5),
        f_rf=jnp.asarray(rng.normal(size=shape(E_RF)).astype(np.float32) * 0.5),
    )


def to_host(state: LN.LaneState) -> LN.LaneState:
    return jax.tree.map(np.asarray, state)


def run(step, params, state, b, batches, extra=()):
    """Dispatch ``batches`` in order on a copy of ``state`` (the step donates)."""
    state = jax.tree.map(jnp.copy, state)
    for idx, valid in batches:
        state = step(state, params, jnp.int32(b), jnp.asarray(idx), jnp.asarray(valid), *extra)
    return to_host(state)


def masked(advanced: np.ndarray) -> list[tuple[np.ndarray, np.ndarray]]:
    sel = np.zeros((N,), bool)
    sel[advanced] = True
    return [(np.arange(N, dtype=np.int32), sel)]


def lane_rows(lane: int) -> dict[str, tuple]:
    """Index of one lane in every leaf (its two CFG rows in the [2N] ones)."""
    return {
        name: (np.array([lane, N + lane]) if name in ("f_sk", "f_rf", "ctx2") else lane)
        for name in LN.LaneState._fields
    }


def compare(got: LN.LaneState, want: LN.LaneState, before: LN.LaneState, advanced) -> None:
    for lane in range(N):
        for name, at in lane_rows(lane).items():
            g, w, b = (getattr(s, name)[at] for s in (got, want, before))
            if lane in advanced:
                np.testing.assert_allclose(g, w, atol=ATOL, err_msg=f"lane {lane} {name}")
            else:
                np.testing.assert_array_equal(g, b, err_msg=f"idle lane {lane} {name}")
    np.testing.assert_array_equal(got.step, before.step + np.isin(np.arange(N), list(advanced)))


@pytest.mark.parametrize("n_adv", range(N + 1))
@pytest.mark.parametrize("cls", CLASSES)
def test_compact_step_matches_masked_step(params, steps, cls, n_adv):
    """Every branch class, 0..N advancing lanes: the compact dispatches
    give the masked whole-batch step's new state lane by lane."""
    advanced = np.array(sorted(ADVANCE_ORDER[:n_adv]), np.int64)
    state = lane_state()
    before = to_host(state)
    batches = LN.compact_batches(advanced, N, K)
    assert len(batches) == -(-n_adv // K)
    got = run(steps[False], params, state, CLASSES[cls], batches)
    want = run(steps[False], params, state, CLASSES[cls], masked(advanced))
    compare(got, want, before, set(advanced.tolist()))


@pytest.mark.parametrize("threshold", ["hits", "zero"])
@pytest.mark.parametrize("cls", CLASSES)
def test_cached_compact_step_matches_masked_step(params, steps, cls, threshold):
    """The cached variant gathers ``feat_src``/``feat_dist`` with the lanes:
    with slot hits it consumes the same slots as the masked step, and at
    threshold 0 it is bit-identical to the uncached compact step."""
    state = lane_state()
    if threshold == "zero":
        state = state._replace(thr=jnp.zeros_like(state.thr))
    before = to_host(state)
    advanced = np.array([0, 1, 3])  # two dispatches, the second padded
    feat_src = jnp.asarray(np.array([2, 0, -1, 1], np.int32))
    feat_dist = jnp.asarray(np.array([0.1, 0.2, 0.0, 0.3], np.float32))
    extra = (feat_src, feat_dist, cache_state())
    batches = LN.compact_batches(advanced, N, K)
    b = CLASSES[cls]
    got = run(steps[True], params, state, b, batches, extra)
    compare(got, run(steps[True], params, state, b, masked(advanced), extra), before, {0, 1, 3})
    own = run(steps[False], params, state, b, batches)
    if threshold == "zero":
        for name in LN.LaneState._fields:
            np.testing.assert_array_equal(getattr(got, name), getattr(own, name), err_msg=name)
    elif b != SM.FULL:  # a hit changes what the partial branches consume
        assert not np.array_equal(got.x, own.x)


@pytest.mark.parametrize(
    "n_lanes,n_adv", [(n, a) for n in range(1, 6) for a in range(n + 1)]
)
def test_compact_batches_pad_with_distinct_lanes(n_lanes, n_adv):
    """``ceil(n / K)`` dispatches of K distinct lanes; padding never repeats
    a lane the same dispatch advances, and is a lane the tick does not
    advance wherever one is left."""
    k = LN.compact_capacity(n_lanes)
    assert k == -(-n_lanes // 2) and 1 <= k <= n_lanes
    advanced = np.array(sorted(range(n_lanes)[::-1][:n_adv]), np.int64)
    batches = LN.compact_batches(advanced, n_lanes, k)
    assert len(batches) == -(-n_adv // k)
    seen = []
    for idx, valid in batches:
        assert idx.shape == valid.shape == (k,) and idx.dtype == np.int32
        assert len(set(idx.tolist())) == k and 0 <= idx.min() and idx.max() < n_lanes
        chunk, pad = idx[valid].tolist(), idx[~valid].tolist()
        assert not set(chunk) & set(pad)
        idle = set(range(n_lanes)) - set(advanced.tolist())
        assert set(pad) <= idle or len(idle) < len(pad)
        seen += chunk
    assert seen == advanced.tolist()


def test_compact_capacity_halves_the_lane_batch():
    assert [LN.compact_capacity(n) for n in range(1, 9)] == [1, 1, 2, 2, 3, 3, 4, 4]


def _request(rid, t, pas):
    rng = np.random.default_rng(300 + rid)
    plan = PASPlan(t_sketch=max(2, t // 2 + 1), t_complete=2, t_sparse=2,
                   l_sketch=L_SK, l_refine=L_RF) if pas else None
    return GenRequest(
        rid=rid,
        ctx=rng.normal(size=(TOY.ctx_len, TOY.ctx_dim)).astype(np.float32) * 0.2,
        noise=rng.normal(size=(L, TOY.in_channels)).astype(np.float32),
        timesteps=t, plan=plan,
    )


@pytest.mark.parametrize("cache_mode", ["off", "cross"])
def test_engine_dispatches_and_counts_compact_rows(params, cache_mode, monkeypatch):
    """Each tick dispatches ``ceil(advanced / K)`` compact calls inside its
    one ``engine.dispatch`` span, and ``unet_rows``/``micro_dispatches``
    count ``2K`` rows per call; the tick counters keep their meaning."""
    cfg = EngineConfig(n_lanes=N, max_steps=MAX_STEPS, l_sketch=L_SK, l_refine=L_RF,
                       decode_images=False, cache_mode=cache_mode, cache_threshold=0.0)
    eng = DiffusionEngine(TOY, DCFG, params, None, cfg)
    assert eng._k == K
    calls, ticks = [0], []
    real_micro = eng._micro

    def counted(*a):
        calls[0] += 1
        return real_micro(*a)

    eng._micro = counted
    real_record = eng.metrics.record_step

    def spy(n_lanes, n_active, n_advanced, **kw):
        ticks.append((n_advanced, calls[0], kw["dispatches"], kw["unet_rows"]))
        calls[0] = 0
        return real_record(n_lanes, n_active, n_advanced, **kw)

    monkeypatch.setattr(eng.metrics, "record_step", spy)
    for rid, (t, pas) in enumerate([(6, True), (5, True), (6, False), (4, False), (5, True)]):
        eng.submit(_request(rid, t, pas))
    done = []
    while eng.n_pending or eng.n_active:
        done += eng.step()
    assert sorted(d.rid for d in done) == [0, 1, 2, 3, 4]
    assert ticks and {n for n, *_ in ticks} >= {1, 2, 3}
    for n_adv, n_calls, dispatches, rows in ticks:
        assert n_calls == dispatches == -(-n_adv // K)
        assert rows == 2 * K * dispatches
    m = eng.metrics
    assert m.micro_steps == len(ticks) == m.spans["engine.dispatch"][0]
    assert m.micro_dispatches == sum(t[2] for t in ticks)
    assert m.unet_rows == 2 * K * m.micro_dispatches
    assert m.lane_steps_advanced == sum(t[0] for t in ticks) == 5 + 6 + 6 + 4 + 5
    s = m.summary()
    assert (s["micro_dispatches"], s["unet_rows"]) == (m.micro_dispatches, m.unet_rows)


def test_record_step_sums_dispatches_and_rows():
    m = ServingMetrics()
    m.record_step(4, 3, 1, n_full=1, dispatches=1, unet_rows=4)
    m.record_step(4, 4, 3, n_sketch=3, dispatches=2, unet_rows=8)
    m.record_step(4, 2, 2)  # a caller that dispatches no micro-step
    s = m.summary()
    assert (s["micro_steps"], s["micro_dispatches"], s["unet_rows"]) == (3, 3, 12)
    assert s["lane_steps_advanced"] == 6
