"""Differential fuzz: StaticServer vs DiffusionEngine (cache off).

Random seeded request mixes served by the lockstep baseline and by the
continuous engine must land every request on the same latent.  Groups of
``batch`` consecutive requests share one step count (and one plan choice)
because lockstep overshoot is *semantic* for StaticServer: a short request
batched with a longer one runs the longer schedule, so heterogeneous
groups would legitimately differ.  Within homogeneous groups the two
serving paths compute the same per-request trajectory.

Equality is within a tight tolerance rather than bitwise: the two paths
run different XLA programs (one ``lax.scan`` over the whole schedule vs
per-step masked micro-steps), which fuse differently at the ~1e-5 level on
the toy config.  Bit-level stability of each path individually is pinned
by ``tests/test_golden_latents.py``.

This file also owns the XLA-vs-Pallas kernel-backend pins:

* per-primitive parity (Uni-conv, stream group norm with and without the
  fused SiLU epilogue, flash attention) at exactly the (L, C) shapes the
  served ``sd_toy`` U-Net runs them, through the same
  :class:`~repro.models.backend.KernelBackend` objects the engine uses;
* a full differential of a ``backend="pallas"`` engine against the
  straight-line XLA sampler.  Elementwise kernels match to ~1e-5; the
  flash-attention online softmax is mathematically but not bitwise equal
  to ``jax.nn.softmax``, so the end-to-end tolerance is the documented
  ``PALLAS_ATOL`` (measured headroom: ~7e-5 on the golden workload).
Off-TPU the Pallas kernels run in interpret mode, so all of this is
exercised on CPU CI.
"""
import dataclasses

import jax
import numpy as np
import pytest

from repro.common.types import DiffusionConfig, PASPlan
from repro.configs import get_unet_config
from repro.models import unet as U
from repro.models.backend import resolve_backend
from repro.serving import DiffusionEngine, EngineConfig, GenRequest, StaticServer

TOY = get_unet_config("sd_toy")
N_UP = U.n_up_steps(TOY)
L = TOY.latent_size**2
L_SK, L_RF = min(3, N_UP), min(2, N_UP)
ATOL = 5e-4
#: documented tolerance for pallas engines vs the XLA reference paths
PALLAS_ATOL = 5e-4


def _plan_for(t: int) -> PASPlan | None:
    """Deterministic plan choice shared by both serving paths: PAS on even
    step counts, all-FULL on odd."""
    if t % 2:
        return None
    return PASPlan(
        t_sketch=max(2, t // 2 + 1), t_complete=2, t_sparse=2,
        l_sketch=L_SK, l_refine=L_RF,
    )


def _mix(seed: int, n_groups: int, batch: int, t_lo: int, t_hi: int) -> list[GenRequest]:
    rng = np.random.default_rng(seed)
    reqs = []
    for g in range(n_groups):
        t = int(rng.integers(t_lo, t_hi + 1))
        for _ in range(batch):
            rid = len(reqs)
            reqs.append(
                GenRequest(
                    rid=rid,
                    ctx=rng.normal(size=(TOY.ctx_len, TOY.ctx_dim)).astype(np.float32) * 0.2,
                    noise=rng.normal(size=(L, TOY.in_channels)).astype(np.float32),
                    timesteps=t,
                    plan=_plan_for(t),
                )
            )
    return reqs


def _run_both(params, reqs, batch: int, max_steps: int):
    dcfg = DiffusionConfig(timesteps_sample=max_steps)
    static = StaticServer(
        TOY, dcfg, params, None, batch, plan_fn=_plan_for, decode_images=False
    )
    s_done, _ = static.run(reqs)
    cfg = EngineConfig(
        n_lanes=batch, max_steps=max_steps, l_sketch=L_SK, l_refine=L_RF,
        decode_images=False,
    )
    e_done, _ = DiffusionEngine(TOY, dcfg, params, None, cfg).run(reqs)
    return (
        {d.rid: d.latent for d in s_done},
        {d.rid: d.latent for d in e_done},
    )


def _assert_equal(static_lat, engine_lat, reqs):
    assert sorted(static_lat) == sorted(engine_lat) == [r.rid for r in reqs]
    for rid in static_lat:
        np.testing.assert_allclose(
            engine_lat[rid], static_lat[rid], atol=ATOL,
            err_msg=f"rid={rid} (t={reqs[rid].timesteps}, "
            f"pas={reqs[rid].plan is not None}) diverged between serving paths",
        )


@pytest.fixture(scope="module")
def params():
    return U.init_unet(jax.random.key(1), TOY)


@pytest.mark.parametrize("seed", [0, 1])
def test_differential_small_mix(params, seed):
    reqs = _mix(seed, n_groups=2, batch=2, t_lo=3, t_hi=5)
    _assert_equal(*_run_both(params, reqs, batch=2, max_steps=5), reqs)


@pytest.mark.slow
@pytest.mark.parametrize("seed", [2, 3, 4])
def test_differential_large_mix(params, seed):
    reqs = _mix(seed, n_groups=4, batch=3, t_lo=3, t_hi=8)
    _assert_equal(*_run_both(params, reqs, batch=3, max_steps=8), reqs)


# ---------------------------------------------------------------------------
# XLA-vs-Pallas kernel parity at the served sd_toy shapes
# ---------------------------------------------------------------------------

XLA = resolve_backend("xla")
PALLAS = resolve_backend("pallas")

#: (L, C) of every sd_toy U-Net level (16x16 latent, channel mults 1/2/4)
SERVED_LC = [(256, 32), (64, 64), (16, 128)]
#: levels that run attention (attn_levels = (0, 1)); heads = 2
ATTN_LC = [(256, 32), (64, 64)]


def _hw(length: int) -> tuple[int, int]:
    side = int(round(length**0.5))
    assert side * side == length
    return side, side


@pytest.mark.parametrize("l,c", SERVED_LC)
@pytest.mark.parametrize("ksize", [1, 3])
def test_conv_parity_served_shapes(l, c, ksize):
    rng = np.random.default_rng(10 * l + c + ksize)
    w = rng.normal(size=(ksize * ksize, c, c)).astype(np.float32) * 0.05
    b = rng.normal(size=(c,)).astype(np.float32)
    x = rng.normal(size=(2, l, c)).astype(np.float32)
    got = PALLAS.conv(w, b, x, _hw(l), ksize)
    ref = XLA.conv(w, b, x, _hw(l), ksize)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref), atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("l,c", SERVED_LC)
@pytest.mark.parametrize("silu", [False, True])
def test_group_norm_parity_served_shapes(l, c, silu):
    rng = np.random.default_rng(20 * l + c + silu)
    groups = TOY.groups
    p = {
        "scale": rng.normal(size=(c,)).astype(np.float32),
        "bias": rng.normal(size=(c,)).astype(np.float32),
    }
    x = rng.normal(size=(2, l, c)).astype(np.float32)
    got = PALLAS.group_norm(x, p, groups, silu=silu)
    ref = XLA.group_norm(x, p, groups, silu=silu)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref), atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("l,c", ATTN_LC)
@pytest.mark.parametrize("lkv", [None, 8])  # None = self-attention, 8 = ctx_len
def test_attention_parity_served_shapes(l, c, lkv):
    rng = np.random.default_rng(30 * l + c + (lkv or 0))
    lk = l if lkv is None else lkv
    q = rng.normal(size=(2, l, c)).astype(np.float32)
    k = rng.normal(size=(2, lk, c)).astype(np.float32)
    v = rng.normal(size=(2, lk, c)).astype(np.float32)
    o_proj = (rng.normal(size=(c, c)) * c**-0.5).astype(np.float32)
    got = PALLAS.attention(q, k, v, o_proj, TOY.n_heads)
    ref = XLA.attention(q, k, v, o_proj, TOY.n_heads)
    # online softmax vs jax.nn.softmax: equal math, different accumulation
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref), atol=1e-4, rtol=1e-4)


# ---------------------------------------------------------------------------
# Full differential: pallas engine vs the straight-line XLA sampler
# ---------------------------------------------------------------------------


def _run_pallas_engine(params, reqs, batch: int, max_steps: int):
    dcfg = DiffusionConfig(timesteps_sample=max_steps)
    cfg = EngineConfig(
        n_lanes=batch, max_steps=max_steps, l_sketch=L_SK, l_refine=L_RF,
        decode_images=False, backend="pallas",
    )
    done, summary = DiffusionEngine(TOY, dcfg, params, None, cfg).run(reqs)
    assert summary["kernels"] == "pallas"
    assert summary["spans"]["engine.tick"]["n"] > 0
    return {d.rid: d.latent for d in done}


def test_differential_pallas_engine(params):
    """A pallas engine must land every request within PALLAS_ATOL of the
    straight-line XLA sampler (the same oracle the xla engine is held to)."""
    reqs = _mix(0, n_groups=2, batch=2, t_lo=3, t_hi=5)
    dcfg = DiffusionConfig(timesteps_sample=5)
    static = StaticServer(
        TOY, dcfg, params, None, 2, plan_fn=_plan_for, decode_images=False
    )
    s_done, _ = static.run(reqs)
    static_lat = {d.rid: d.latent for d in s_done}
    pallas_lat = _run_pallas_engine(params, reqs, batch=2, max_steps=5)
    assert sorted(static_lat) == sorted(pallas_lat) == [r.rid for r in reqs]
    for rid in static_lat:
        np.testing.assert_allclose(
            pallas_lat[rid], static_lat[rid], atol=PALLAS_ATOL,
            err_msg=f"rid={rid} (t={reqs[rid].timesteps}) diverged between "
            "the pallas engine and the XLA straight-line sampler",
        )


@pytest.mark.slow
def test_differential_pallas_engine_large(params):
    reqs = _mix(5, n_groups=3, batch=2, t_lo=3, t_hi=8)
    static = StaticServer(
        TOY, DiffusionConfig(timesteps_sample=8), params, None, 2,
        plan_fn=_plan_for, decode_images=False,
    )
    static_lat = {d.rid: d.latent for d in static.run(reqs)[0]}
    pallas_lat = _run_pallas_engine(params, reqs, batch=2, max_steps=8)
    for rid in static_lat:
        np.testing.assert_allclose(pallas_lat[rid], static_lat[rid], atol=PALLAS_ATOL)
