"""Spans and counters of the serving engines and driver (``ServingMetrics``).

Both engine families run the toy U-Net on the CPU: the single-device
``DiffusionEngine`` and the mesh-sharded ``ShardedDiffusionEngine`` on a
one-device mesh (so the plain tier-1 job runs it).  The counters must
agree with each other and with what the engine was seen to do; the
running-sum means must equal the per-tick means they replaced; and the
spans must reach the profiler's host timeline with their ids.
"""
import glob
import os
import subprocess
import sys
import threading

import jax
import numpy as np
import pytest

from repro.common.types import DiffusionConfig, PASPlan
from repro.configs import get_unet_config
from repro.models import unet as U
from repro.serving import (
    DiffusionEngine,
    EngineConfig,
    EngineDriver,
    GenRequest,
    ServingMetrics,
    ShardedDiffusionEngine,
)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TOY = get_unet_config("sd_toy")
N_UP = U.n_up_steps(TOY)
L = TOY.latent_size**2
L_SK, L_RF = min(3, N_UP), min(2, N_UP)
DCFG = DiffusionConfig(timesteps_sample=6)
LANES = 2


def _plan(t):
    return PASPlan(
        t_sketch=max(2, t // 2 + 1), t_complete=2, t_sparse=2,
        l_sketch=L_SK, l_refine=L_RF,
    )


def _requests(n=5, ctx=None):
    """Mixed step counts and plans (all-FULL and PAS), so lanes hold
    requests that the tick's branch class does not advance."""
    out = []
    for rid in range(n):
        rng = np.random.default_rng(700 + rid)
        t = (4, 6, 5)[rid % 3]
        out.append(GenRequest(
            rid=rid,
            ctx=ctx if ctx is not None
            else rng.normal(size=(TOY.ctx_len, TOY.ctx_dim)).astype(np.float32) * 0.2,
            noise=rng.normal(size=(L, TOY.in_channels)).astype(np.float32),
            timesteps=t,
            plan=None if rid % 3 == 0 else _plan(t),
        ))
    return out


@pytest.fixture(scope="module")
def params():
    return U.init_unet(jax.random.key(0), TOY)


def _engine(params, family: str, cache: str = "off"):
    cfg = EngineConfig(
        n_lanes=LANES, max_steps=6, l_sketch=L_SK, l_refine=L_RF,
        decode_images=False, cache_mode=cache, n_shards=1,
    )
    cls = ShardedDiffusionEngine if family == "sharded" else DiffusionEngine
    return cls(TOY, DCFG, params, None, cfg)


FAMILIES = [("single", "off"), ("sharded", "off"), ("single", "cross")]


@pytest.fixture(scope="module", params=FAMILIES, ids=["-".join(f) for f in FAMILIES])
def engine(request, params):
    return _engine(params, *request.param)


def _run_observed(eng, reqs):
    """``eng.run`` with every ``step`` call seen from outside: (ticks
    before, ticks after, lanes retired)."""
    calls = []
    step = eng.step

    def observed(*a, **k):
        before = eng.metrics.micro_steps
        done = step(*a, **k)
        calls.append((before, eng.metrics.micro_steps, len(done)))
        return done

    eng.step = observed
    try:
        done, summary = eng.run(reqs)
    finally:
        del eng.step
    return done, summary, calls


def test_counters_agree_with_the_ticks(engine):
    reqs = _requests()
    done, s, calls = _run_observed(engine, reqs)
    assert len(done) == len(reqs)
    ticks = s["micro_steps"]
    assert ticks == sum(after - before for before, after, _ in calls)
    assert sum(s["ticks_by_class"].values()) == ticks
    assert sum(n > 0 for n in s["ticks_by_class"].values()) >= 2  # PAS ran more than FULL
    spans = s["spans"]
    for name in ("engine.tick", "engine.plan", "engine.dispatch"):
        assert spans[name]["n"] == ticks, name
    assert spans["engine.admit"]["n"] == len(reqs)
    assert spans["engine.retire"]["n"] == len(done)
    assert all(v["s"] >= 0 for v in spans.values())
    # the tick span holds the spans inside it
    inner = sum(spans[k]["s"] for k in ("engine.admit", "engine.plan", "engine.dispatch",
                                        "engine.retire"))
    assert inner <= spans["engine.tick"]["s"]
    assert s["lane_steps_advanced"] < s["lane_slots_active"] <= ticks * LANES
    assert s["mean_occupancy"] == round(s["lane_slots_active"] / (ticks * LANES), 3)


def test_drains_count_retiring_ticks_followed_by_a_dispatch(engine):
    _, s, calls = _run_observed(engine, _requests())
    dispatched = [after > before for before, after, _ in calls]
    want = sum(
        1 for i, (_, _, retired) in enumerate(calls) if retired and any(dispatched[i + 1:])
    )
    assert want > 0
    assert s["drains"] == want
    assert 0 < s["drain_gap_s"] <= s["spans"]["engine.tick"]["s"]


def test_running_sums_equal_the_per_tick_means():
    rng = np.random.default_rng(11)
    m = ServingMetrics()
    occupancy, advance_eff, shard_active = [], [], []
    for _ in range(37):
        n_active = int(rng.integers(0, 5))
        n_adv = int(rng.integers(0, n_active + 1))
        per_shard = [int(rng.integers(0, 3)), int(rng.integers(0, 3))]
        m.record_step(4, n_active, n_adv, n_full=n_adv, shard_active=per_shard)
        occupancy.append(n_active / 4)
        if n_active:
            advance_eff.append(n_adv / n_active)
        shard_active.append(per_shard)
    s = m.summary()
    assert s["mean_occupancy"] == round(float(np.mean(occupancy)), 3)
    assert s["mean_advance_eff"] == round(float(np.mean(advance_eff)), 3)
    per_shard = np.asarray(shard_active, np.float64).mean(axis=0)
    assert s["shard_mean_active"] == [round(float(v), 3) for v in per_shard]
    assert s["shard_occupancy_balance"] == round(float(per_shard.min() / per_shard.max()), 3)


def test_span_counts_and_times_each_interval():
    m = ServingMetrics()
    for k in range(3):
        with m.span("engine.retire", rid=k):
            pass
    with pytest.raises(RuntimeError):
        with m.span("engine.retire", rid=9):
            raise RuntimeError("the interval still counts")
    assert m.spans["engine.retire"][0] == 4
    assert m.summary()["spans"]["engine.retire"]["n"] == 4


def test_drain_gap_skips_a_sync_after_which_the_engine_ran_dry():
    m = ServingMetrics()
    m.mark_dispatch()  # no sync yet
    m.mark_sync()
    m.mark_dispatch()
    m.mark_dispatch()  # no sync since the last dispatch
    m.mark_sync()
    m.mark_idle()
    m.mark_dispatch()
    assert m.drains == 1 and m.drain_gap_s >= 0


def test_metrics_module_imports_without_jax():
    code = ("import sys; import repro.serving.metrics as M; M.ServingMetrics().summary(); "
            "sys.exit(1 if 'jax' in sys.modules else 0)")
    env = dict(os.environ, PYTHONPATH=os.path.join(REPO, "src"))
    assert subprocess.run([sys.executable, "-c", code], env=env).returncode == 0


def test_dispatch_spans_reach_the_profiler_with_their_class(engine, tmp_path):
    with jax.profiler.trace(str(tmp_path)):
        _, s, _ = _run_observed(engine, _requests(3))
    (path,) = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    data = jax.profiler.ProfileData.from_file(path)
    found: dict[str, list[dict]] = {}
    for plane in data.planes:
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith("engine."):
                    found.setdefault(ev.name, []).append(dict(ev.stats))
    dispatch = found["engine.dispatch"]
    assert len(dispatch) == s["micro_steps"]
    classes = {c for ev in dispatch for c in ev["cls"].split(",")}
    assert classes <= {"full", "sketch", "refine"} and len(classes) >= 2
    assert all(ev["lanes"] >= 1 for ev in dispatch)
    assert sorted(ev["rid"] for ev in found["engine.admit"]) == [0, 1, 2]
    assert sorted(ev["rid"] for ev in found["engine.retire"]) == [0, 1, 2]
    assert len(found["engine.tick"]) == s["micro_steps"]


def test_driver_spans(params):
    eng = _engine(params, "single")
    driver = EngineDriver(eng, max_inflight=8, idle_wait_s=0.005).start()
    finished = threading.Event()
    done = []

    def on_event(ev):
        if ev["event"] == "done":
            done.append(ev["rid"])
            if len(done) == 3:
                finished.set()

    for req in _requests(3):
        driver.submit(req, on_event)
    assert finished.wait(120)
    s = driver.shutdown(timeout=60)
    spans = s["spans"]
    assert spans["driver.submit"]["n"] == 3
    assert spans["driver.wait"]["n"] >= 1
    # every step call is followed by its events
    assert spans["driver.events"]["n"] == spans["engine.tick"]["n"] == s["micro_steps"]
    assert s["drains"] <= spans["engine.retire"]["n"]
