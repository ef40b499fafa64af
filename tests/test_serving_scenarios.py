"""Conditioned-pipeline golden + differential harness (tier-1).

The v2-task counterpart of ``tests/test_golden_latents.py``: checked-in
tiny-config latents pin the img2img / inpaint / variation scenarios
bit-for-bit across both execution families (straight-line
``pas_denoise_scheduled`` and the continuous engine), the two families are
differentially cross-checked within the cross-program tolerance, and the
structural contract of the inpaint blend — a full-ones mask is *exactly*
txt2img — is asserted bit-level, both on the fixed scenario and under
randomized seeds/plans (hypothesis when installed, seeded cases always).

Bit-level comparisons against the checked-in file run in a subprocess
through ``tools/regen_golden_scenarios.py --check`` under the canonical
XLA environment; see the txt2img harness for why.
"""
import dataclasses
import os
import subprocess
import sys

import numpy as np
import pytest

from hypothesis import given, settings, strategies as st

from repro.serving import scenarios as S
from repro.serving.engine import DiffusionEngine, EngineConfig, GenRequest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
GOLDEN_PATH = os.path.join(REPO, "tests", "golden", S.GOLDEN_FILE)

SCENARIO_NAMES = [
    "img2img_s040", "img2img_s075",
    "inpaint_ones", "inpaint_half",
    "var_0", "var_1", "var_2",
]


@pytest.fixture(scope="module")
def golden():
    assert os.path.exists(GOLDEN_PATH), (
        f"missing {GOLDEN_PATH} — run tools/regen_golden_scenarios.py"
    )
    return S.load_golden(GOLDEN_PATH)


@pytest.fixture(scope="module")
def params():
    return S.golden_params()


# ---------------------------------------------------------------------------
# Golden families
# ---------------------------------------------------------------------------


def test_scenario_stream_shape():
    named = S.scenario_requests()
    assert [name for name, _ in named] == SCENARIO_NAMES
    reqs = dict(named)
    # strength truncation resolved into executed-vs-base step counts
    assert reqs["img2img_s040"].timesteps == 2
    assert reqs["img2img_s075"].timesteps == 4
    for n in ("img2img_s040", "img2img_s075"):
        assert reqs[n].base_timesteps == S.BASE_T
        assert reqs[n].init_latent is not None
    # inpaint masks: identity and genuinely mixed
    assert np.all(reqs["inpaint_ones"].mask == 1.0)
    half = reqs["inpaint_half"].mask
    assert 0 < float(half.sum()) < half.size
    # variations: one ctx, distinct noises
    v0, v1, v2 = (reqs[f"var_{i}"] for i in range(3))
    assert np.array_equal(v0.ctx, v1.ctx) and np.array_equal(v0.ctx, v2.ctx)
    assert not np.array_equal(v0.noise, v1.noise)
    assert not np.array_equal(v1.noise, v2.noise)


def test_golden_file_families_cross_check(golden):
    line, engine = golden
    assert sorted(line) == sorted(engine) == sorted(SCENARIO_NAMES)
    for name in line:
        np.testing.assert_allclose(line[name], engine[name], atol=2e-4)


def test_all_scenarios_bit_exact_vs_golden_file():
    """Subprocess under the canonical XLA env: the scheduled straight-line
    sampler, the engine with cache off, and the engine at threshold 0 must
    reproduce the checked-in conditioned latents without moving a bit."""
    env = dict(os.environ)
    env["PYTHONPATH"] = "src" + os.pathsep + env.get("PYTHONPATH", "")
    out = subprocess.run(
        [sys.executable, "tools/regen_golden_scenarios.py", "--check"],
        capture_output=True, text=True, timeout=900, env=env, cwd=REPO,
    )
    assert out.returncode == 0, (
        f"scenario golden drift:\n{out.stdout[-3000:]}\n{out.stderr[-2000:]}"
    )
    if not os.environ.get("GOLDEN_ATOL"):  # hardware-drift escape hatch off
        assert out.stdout.count("bit-exact") == 21  # 3 paths x 7 scenarios


def test_engine_tracks_scenarios_within_tolerance_in_any_regime(golden, params):
    """In-process differential: whatever the process's XLA flag regime, the
    engine must stay within float-fusion distance of the straight-line
    reference on every conditioned task."""
    got = S.run_engine(params, cache_mode="off")
    line, _ = golden
    for name in SCENARIO_NAMES:
        np.testing.assert_allclose(
            got[name], line[name], atol=2e-4,
            err_msg=f"scenario {name}: engine diverged from pas_denoise_scheduled",
        )


# ---------------------------------------------------------------------------
# Structural identity: full-ones inpaint == txt2img, bit for bit
# ---------------------------------------------------------------------------


def _identity_pair(params, seed: int, timesteps: int, pas: bool):
    """One txt2img request and its full-ones-mask inpaint twin -> latents."""
    rng = np.random.default_rng(seed)
    ctx = rng.normal(size=(S.UCFG.ctx_len, S.UCFG.ctx_dim)).astype(np.float32) * 0.2
    noise = rng.normal(
        size=(S.UCFG.latent_size**2, S.UCFG.in_channels)
    ).astype(np.float32)
    init = rng.normal(
        size=(S.UCFG.latent_size**2, S.UCFG.in_channels)
    ).astype(np.float32)
    plan = S._plan(timesteps) if pas else None
    base = dict(ctx=ctx, noise=noise, timesteps=timesteps, plan=plan)
    txt = GenRequest(rid=0, **base)
    inp = GenRequest(
        rid=0, **base,
        init_latent=init,
        mask=np.ones((S.UCFG.latent_size**2, 1), np.float32),
    )
    cfg = EngineConfig(
        n_lanes=S.N_LANES, max_steps=S.MAX_STEPS,
        l_sketch=S.L_SKETCH, l_refine=S.L_REFINE,
        decode_images=False, cache_mode="off",
    )
    out = []
    for req in (txt, inp):
        engine = DiffusionEngine(S.UCFG, S.DCFG, params, None, cfg)
        done, _ = engine.run([dataclasses.replace(req)])
        out.append(done[0].latent)
    return out


def test_full_ones_mask_is_txt2img_identity_fixed_case(params):
    """The exact-tier structural contract on the pinned scenario: running
    the same request as txt2img and as inpaint-with-ones-mask must agree
    bit for bit — the blend's ``where`` never touches generated cells."""
    txt, inp = _identity_pair(params, seed=7, timesteps=S.BASE_T, pas=True)
    np.testing.assert_array_equal(
        inp, txt, err_msg="full-ones inpaint mask moved a bit vs txt2img"
    )


@settings(max_examples=3, deadline=None)
@given(
    seed=st.integers(min_value=0, max_value=2**16),
    timesteps=st.integers(min_value=4, max_value=6),
    pas=st.booleans(),
)
def test_full_ones_mask_is_txt2img_identity_property(seed, timesteps, pas):
    txt, inp = _identity_pair(S.golden_params(), seed, timesteps, pas)
    np.testing.assert_array_equal(
        inp, txt,
        err_msg=f"identity broke at seed={seed} t={timesteps} pas={pas}",
    )
