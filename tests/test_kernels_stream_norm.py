"""Stream-norm Pallas kernels (one-pass layernorm/rmsnorm/groupnorm, Eq. 4)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.stream_norm.ops import stream_group_norm, stream_norm
from repro.kernels.stream_norm.ref import stream_group_norm_ref, stream_norm_ref

CASES = [
    (64, 128), (256, 384), (1024, 64), (8, 8), (100, 33),  # odd shapes too
]


@pytest.mark.parametrize("m,d", CASES)
@pytest.mark.parametrize("mode", ["layernorm", "rmsnorm"])
def test_stream_norm_matches_ref(m, d, mode):
    x = jax.random.normal(jax.random.key(m + d), (m, d), jnp.float32) * 3 + 1
    scale = jax.random.normal(jax.random.key(1), (d,)) * 0.1 + 1
    bias = jax.random.normal(jax.random.key(2), (d,)) * 0.1
    got = stream_norm(x, scale, bias, mode=mode)
    want = stream_norm_ref(x, scale, bias, mode=mode)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5, rtol=2e-5)


def test_stream_norm_leading_batch_dims():
    x = jax.random.normal(jax.random.key(3), (2, 8, 16, 32), jnp.float32)
    scale = jnp.ones((32,))
    got = stream_norm(x, scale, None, mode="rmsnorm")
    want = stream_norm_ref(x, scale, None, mode="rmsnorm")
    assert got.shape == x.shape
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)


def test_stream_norm_single_pass_identity():
    """Layernorm output must have ~zero mean / unit variance per row
    (validates the one-pass E[x^2]-E[x]^2 formulation against catastrophic
    cancellation at moderate offsets)."""
    x = jax.random.normal(jax.random.key(4), (128, 512)) + 100.0  # big offset
    y = stream_norm(x, jnp.ones((512,)), jnp.zeros((512,)), mode="layernorm")
    y = np.asarray(y)
    np.testing.assert_allclose(y.mean(-1), 0.0, atol=1e-3)
    np.testing.assert_allclose(y.var(-1), 1.0, atol=1e-2)


def test_stream_norm_block_m_invariance():
    x = jax.random.normal(jax.random.key(5), (512, 128))
    s = jnp.ones((128,))
    a = stream_norm(x, s, None, mode="rmsnorm", block_m=64)
    b = stream_norm(x, s, None, mode="rmsnorm", block_m=512)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-6)


# -- group norm (+ fused SiLU epilogue) --------------------------------------

GN_CASES = [
    # (b, l, c, groups) — includes the served sd_toy shapes (groups=8)
    (2, 256, 32, 8), (2, 64, 64, 8), (1, 16, 128, 8), (3, 100, 24, 4),
    # sd_v14 level 1: statistics accumulate over 8 row tiles
    (1, 4096, 640, 32),
]


@pytest.mark.parametrize("b,l,c,groups", GN_CASES)
@pytest.mark.parametrize("silu", [False, True])
def test_stream_group_norm_matches_ref(b, l, c, groups, silu):
    x = jax.random.normal(jax.random.key(b * l + c), (b, l, c), jnp.float32) * 2 + 0.5
    scale = jax.random.normal(jax.random.key(6), (c,)) * 0.1 + 1
    bias = jax.random.normal(jax.random.key(7), (c,)) * 0.1
    got = stream_group_norm(x, scale, bias, groups=groups, silu=silu)
    want = stream_group_norm_ref(x, scale, bias, groups=groups, silu=silu)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5, rtol=2e-5)


def test_stream_group_norm_matches_model_group_norm():
    """The kernel normalizes over the same (L, per-group-C) statistics as
    the model's reference ``group_norm`` — per (batch, group), not per row."""
    from repro.models.unet import group_norm, init_gn

    x = jax.random.normal(jax.random.key(8), (2, 64, 32), jnp.float32)
    p = init_gn(32)
    got = stream_group_norm(x, p["scale"], p["bias"], groups=8)
    want = group_norm(x, p, 8)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5, rtol=2e-5)


def test_stream_group_norm_fused_silu_equals_unfused():
    """f32 in, f32 out: the fused epilogue equals silu-after (the fusion
    only removes the HBM round-trip, not a rounding step)."""
    x = jax.random.normal(jax.random.key(9), (2, 64, 32), jnp.float32)
    s, b = jnp.ones((32,)), jnp.zeros((32,))
    fused = stream_group_norm(x, s, b, groups=8, silu=True)
    after = jax.nn.silu(stream_group_norm(x, s, b, groups=8, silu=False))
    np.testing.assert_allclose(np.asarray(fused), np.asarray(after), atol=1e-7)
