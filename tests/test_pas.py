"""Phase-aware sampling: plan algebra (hypothesis), cost function, MAC
reduction (Eq. 3), and the PAS executor vs the full sampler."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.common.types import DiffusionConfig, PASPlan, UNetConfig
from repro.configs import get_unet_config
from repro.core import framework as FW
from repro.core import sampler as SM
from repro.core.metrics import latent_psnr
from repro.models import unet as U

TOY = get_unet_config("sd_toy")
N_UP = U.n_up_steps(TOY)


# ---------------------------------------------------------------------------
# PASPlan schedule properties
# ---------------------------------------------------------------------------

plans = st.builds(
    PASPlan,
    t_sketch=st.integers(2, 40),
    t_complete=st.integers(1, 10),
    t_sparse=st.integers(1, 8),
    l_sketch=st.integers(1, 12),
    l_refine=st.integers(1, 12),
)


@given(plan=plans, total=st.integers(5, 60))
@settings(max_examples=300, deadline=None)
def test_schedule_structure(plan, total):
    try:
        plan.validate(total, 12)
    except ValueError:
        return  # invalid plans are rejected; nothing to check
    sched = plan.schedule(total)
    assert len(sched) == total
    # 1) first T_complete steps run the full net
    assert all(l == -1 for l in sched[: plan.t_complete])
    # 2) refinement phase runs exactly L_refine blocks
    assert all(l == plan.l_refine for l in sched[plan.t_sketch :])
    # 3) sketching phase: only full runs or L_sketch partial runs
    assert all(l in (-1, plan.l_sketch) for l in sched[plan.t_complete : plan.t_sketch])
    # 4) sparse sampling: within the sketch window, every T_sparse-th is full
    window = sched[plan.t_complete : plan.t_sketch]
    for i, l in enumerate(window):
        assert (l == -1) == ((i + 1) % plan.t_sparse == 0)


@given(plan=plans)
@settings(max_examples=200, deadline=None)
def test_validate_enforces_paper_constraints(plan):
    total, n_blocks, d_star = 50, 12, 20
    ok = (
        0 < plan.t_complete <= plan.t_sketch <= total
        and plan.t_sparse >= 1
        and 0 < plan.l_refine <= plan.l_sketch <= n_blocks
        and plan.t_sketch >= d_star
    )
    try:
        plan.validate(total, n_blocks, d_star)
        assert ok
    except ValueError:
        assert not ok


# ---------------------------------------------------------------------------
# Cost function f(l) and Eq. 3
# ---------------------------------------------------------------------------


def test_cost_function_monotone_and_bounded():
    f = FW.cost_function(TOY)
    vals = [f(l) for l in range(1, N_UP + 1)]
    assert all(0 < v <= 1 for v in vals)
    assert all(b >= a for a, b in zip(vals, vals[1:])), "f(l) must be nondecreasing"
    assert f(-1) == 1.0  # full network


def test_mac_reduction_eq3():
    plan = PASPlan(t_sketch=25, t_complete=4, t_sparse=4, l_sketch=2, l_refine=2)
    red = FW.mac_reduction(TOY, plan, 50)
    assert red > 1.0, "PAS must reduce MACs"
    f = FW.cost_function(TOY)
    manual = 50 / sum(f(l) for l in plan.schedule(50))
    assert abs(red - manual) < 1e-9


def test_full_plan_has_no_reduction():
    plan = PASPlan(t_sketch=50, t_complete=50, t_sparse=1, l_sketch=1, l_refine=1)
    assert abs(FW.mac_reduction(TOY, plan, 50) - 1.0) < 1e-9


def test_mac_breakdown_total_positive_and_consistent():
    br = FW.unet_mac_breakdown(TOY)
    assert br.total == br.conv_in + sum(br.down) + br.mid + sum(br.up) + br.conv_out
    assert len(br.up) == N_UP
    assert all(m > 0 for m in br.up)


# ---------------------------------------------------------------------------
# PAS executor vs the full sampler
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def toy_setup():
    params = U.init_unet(jax.random.key(0), TOY)
    dcfg = DiffusionConfig(timesteps_sample=12)
    b, L = 1, TOY.latent_size**2
    x = jax.random.normal(jax.random.key(1), (b, L, TOY.in_channels))
    ctx = jax.random.normal(jax.random.key(2), (b, TOY.ctx_len, TOY.ctx_dim)) * 0.2
    return params, dcfg, x, ctx


def test_all_full_plan_equals_original(toy_setup):
    """A PAS plan whose schedule is all-full must bit-match the original
    sampler (the degenerate-reduction sanity check)."""
    params, dcfg, x, ctx = toy_setup
    t = dcfg.timesteps_sample
    plan = PASPlan(t_sketch=t, t_complete=t, t_sparse=1, l_sketch=2, l_refine=2)
    full = SM.pas_denoise(TOY, dcfg, params, None, x, ctx, ctx)
    pas = SM.pas_denoise(TOY, dcfg, params, plan, x, ctx, ctx)
    np.testing.assert_allclose(np.asarray(pas), np.asarray(full), atol=1e-5)


def test_pas_approximates_full(toy_setup):
    """A real PAS plan must stay close to the full trajectory (finite
    PSNR floor) while running far fewer MACs."""
    params, dcfg, x, ctx = toy_setup
    plan = PASPlan(t_sketch=6, t_complete=2, t_sparse=2, l_sketch=4, l_refine=3)
    plan.validate(dcfg.timesteps_sample, N_UP)
    full = SM.pas_denoise(TOY, dcfg, params, None, x, ctx, ctx)
    pas = SM.pas_denoise(TOY, dcfg, params, plan, x, ctx, ctx)
    assert not bool(jnp.isnan(pas).any())
    psnr = latent_psnr(np.asarray(full), np.asarray(pas))
    assert psnr > 10.0, f"PAS diverged from the full trajectory: psnr={psnr:.2f}"
    # the 12-step toy schedule keeps 4 full runs; reduction is modest but real
    assert FW.mac_reduction(TOY, plan, dcfg.timesteps_sample) > 1.2


def test_more_aggressive_plans_reduce_more(toy_setup):
    params, dcfg, *_ = toy_setup
    t = dcfg.timesteps_sample
    reds = []
    for t_sparse in (2, 3, 4):
        plan = PASPlan(t_sketch=6, t_complete=2, t_sparse=t_sparse, l_sketch=2, l_refine=2)
        reds.append(FW.mac_reduction(TOY, plan, t))
    assert reds == sorted(reds), "larger T_sparse must reduce MACs more"


def test_branch_labels(toy_setup):
    plan = PASPlan(t_sketch=6, t_complete=2, t_sparse=2, l_sketch=4, l_refine=3)
    br = np.asarray(SM.plan_to_branches(plan, 12))
    assert (br[:2] == SM.FULL).all()
    assert (br[6:] == SM.REFINE).all()
    assert set(br[2:6]) <= {SM.FULL, SM.SKETCH}
