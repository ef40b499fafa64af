"""Continuous-batching engine: lane admission/retirement correctness,
equivalence with the straight-line PAS sampler, backfill, and the serve CLI.
"""
import dataclasses
import os
import subprocess
import sys

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
    PlanAwareScheduler,
    make_plan_arrays,
)
from repro.serving import lanes as LN

TOY = get_unet_config("sd_toy")
N_UP = U.n_up_steps(TOY)
L = TOY.latent_size**2
L_SK, L_RF = min(3, N_UP), min(2, N_UP)
DCFG = DiffusionConfig(timesteps_sample=8)


def _plan(t):
    return PASPlan(
        t_sketch=max(2, t // 2 + 1),
        t_complete=2,
        t_sparse=2,
        l_sketch=L_SK,
        l_refine=L_RF,
    )


def _request(rid, t, plan, seed=None):
    rng = np.random.default_rng(100 + (seed if seed is not None else rid))
    return GenRequest(
        rid=rid,
        ctx=rng.normal(size=(TOY.ctx_len, TOY.ctx_dim)).astype(np.float32) * 0.2,
        noise=rng.normal(size=(L, TOY.in_channels)).astype(np.float32),
        timesteps=t,
        plan=plan,
    )


@pytest.fixture(scope="module")
def engine():
    params = U.init_unet(jax.random.key(0), TOY)
    cfg = EngineConfig(
        n_lanes=2, max_steps=8, l_sketch=L_SK, l_refine=L_RF, decode_images=False
    )
    eng = DiffusionEngine(
        TOY, DCFG, params, None, cfg, scheduler=PlanAwareScheduler(window=2)
    )
    return eng, params


# ---------------------------------------------------------------------------
# Plan arrays
# ---------------------------------------------------------------------------


def test_make_plan_arrays_matches_plan_schedule():
    plan = _plan(8)
    lp = make_plan_arrays(DCFG, 8, plan, max_steps=12)
    assert lp.n_steps == 8
    np.testing.assert_array_equal(
        lp.branches[:8], np.asarray(SM.plan_to_branches(plan, 8))
    )
    assert (lp.branches[8:] == 0).all()  # padded tail
    assert lp.ts[0] > lp.ts[7] >= 0  # descending timesteps
    assert lp.t_prev[7] == -1  # final step closes the trajectory
    np.testing.assert_array_equal(lp.t_prev[:7], lp.ts[1:8])


def test_make_plan_arrays_rejects_oversize():
    with pytest.raises(ValueError):
        make_plan_arrays(DCFG, 9, None, max_steps=8)


# ---------------------------------------------------------------------------
# Lane state admission/retirement (no U-Net execution)
# ---------------------------------------------------------------------------


def test_lane_admission_and_release_state():
    state = LN.init_lanes(TOY, 3, 8, N_UP - L_SK, N_UP - L_RF)
    assert not bool(state.active_mask().any())
    lp = make_plan_arrays(DCFG, 6, None, 8)
    noise = jnp.ones((L, TOY.in_channels))
    ctx = jnp.ones((TOY.ctx_len, TOY.ctx_dim))
    state = LN.admit(
        state, jnp.int32(1), noise, ctx,
        jnp.asarray(lp.branches), jnp.asarray(lp.ts), jnp.asarray(lp.t_prev),
        jnp.int32(lp.n_steps),
    )
    assert [bool(a) for a in state.active_mask()] == [False, True, False]
    np.testing.assert_array_equal(np.asarray(state.x[1]), np.ones((L, TOY.in_channels)))
    assert int(state.n_steps[1]) == 6
    state = LN.release(state, jnp.int32(1))
    assert not bool(state.active_mask().any())


def test_engine_rejects_mismatched_cache_geometry(engine):
    eng, _ = engine
    bad = PASPlan(t_sketch=4, t_complete=2, t_sparse=2, l_sketch=N_UP, l_refine=1)
    with pytest.raises(ValueError):
        eng.submit(_request(0, 6, bad))
    eng.scheduler._queue.clear()


# ---------------------------------------------------------------------------
# Engine vs straight-line pas_denoise (the acceptance criterion)
# ---------------------------------------------------------------------------


def test_engine_matches_straight_line_sampler(engine):
    """Heterogeneous step counts + mixed PAS/full plans on 2 lanes, with
    backfill, must reproduce each request's solo pas_denoise trajectory."""
    eng, params = engine
    specs = [(8, _plan(8)), (6, _plan(6)), (7, None)]
    reqs = [_request(i, t, p) for i, (t, p) in enumerate(specs)]
    done, summary = eng.run(reqs)

    assert sorted(d.rid for d in done) == [0, 1, 2]
    assert summary["lane_steps_advanced"] == sum(t for t, _ in specs)
    for d in done:
        req = reqs[d.rid]
        dcfg = dataclasses.replace(DCFG, timesteps_sample=req.timesteps)
        ref = SM.pas_denoise(
            TOY, dcfg, params, req.plan,
            jnp.asarray(req.noise)[None], jnp.asarray(req.ctx)[None],
            jnp.zeros((1, TOY.ctx_len, TOY.ctx_dim), jnp.float32),
        )
        np.testing.assert_allclose(
            d.latent, np.asarray(ref[0]), atol=5e-4,
            err_msg=f"lane trajectory diverged for rid={d.rid}",
        )


def test_engine_backfills_and_retires(engine, monkeypatch):
    """More requests than lanes: every lane retirement must immediately
    admit the next queued request, keeping occupancy at 1 until the queue
    drains."""
    from repro.serving.metrics import ServingMetrics

    eng, _ = engine
    occ = []  # per-micro-step occupancy, as the engine reports each tick
    record = ServingMetrics.record_step

    def spy(self, n_lanes, n_active, *a, **k):
        occ.append(n_active / n_lanes)
        return record(self, n_lanes, n_active, *a, **k)

    monkeypatch.setattr(ServingMetrics, "record_step", spy)
    reqs = [_request(i, 3, None, seed=50 + i) for i in range(5)]
    done, summary = eng.run(reqs)
    assert sorted(d.rid for d in done) == list(range(5))
    assert summary["lane_steps_advanced"] == 15
    # 5 requests x 3 steps over 2 lanes admit in waves of two: both lanes
    # busy for 6 micro-steps, then the odd request drains alone for 3.
    assert summary["micro_steps"] == 9
    assert summary["mean_advance_eff"] == 1.0
    assert len(occ) == 9
    assert all(o == 1.0 for o in occ[:6]) and all(o == 0.5 for o in occ[6:])
    assert summary["lane_slots_active"] == 15
    assert summary["mean_occupancy"] == round(7.5 / 9, 3)
    # FIFO admission: first two completions are the first two submissions
    assert {done[0].rid, done[1].rid} == {0, 1}


def test_engine_single_lane_heterogeneous_plans(engine):
    """One lane serializes everything — ordering and per-request schedules
    must still hold (pure FIFO, no cross-lane interference)."""
    _, params = engine
    cfg = EngineConfig(
        n_lanes=1, max_steps=8, l_sketch=L_SK, l_refine=L_RF, decode_images=False
    )
    eng = DiffusionEngine(TOY, DCFG, params, None, cfg)
    reqs = [_request(0, 5, _plan(5)), _request(1, 4, None)]
    done, summary = eng.run(reqs)
    assert [d.rid for d in done] == [0, 1]
    assert summary["micro_steps"] == 9
    assert summary["mean_occupancy"] == 1.0


# ---------------------------------------------------------------------------
# CLI smoke
# ---------------------------------------------------------------------------


def test_serve_cli_diffusion_smoke():
    env = dict(os.environ)
    env["PYTHONPATH"] = "src" + os.pathsep + env.get("PYTHONPATH", "")
    out = subprocess.run(
        [sys.executable, "-m", "repro.launch.serve", "--mode", "diffusion",
         "--requests", "2", "--batch", "2", "--timesteps", "4"],
        capture_output=True, text=True, timeout=600, env=env,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    )
    assert out.returncode == 0, out.stderr[-2000:]
    assert "'requests': 2" in out.stdout
    assert "'mode': 'diffusion'" in out.stdout
