"""Data pipeline determinism/sharding + optimizer behaviour + gradient
compression error-feedback property."""
import jax
import jax.numpy as jnp
import numpy as np
from hypothesis import given, settings, strategies as st

from repro.data.pipeline import DataConfig, Prefetcher, latent_batch, token_batch
from repro.optim import (
    AdamWConfig,
    adamw_update,
    compress_decompress,
    compressed_grads,
    init_adamw,
    init_compression,
    lr_schedule,
)


# ---------------------------------------------------------------------------
# Data pipeline
# ---------------------------------------------------------------------------


def test_restart_determinism():
    cfg = DataConfig(global_batch=8, seq_len=32, vocab_size=100, seed=3)
    a = token_batch(cfg, step=17)
    b = token_batch(cfg, step=17)
    np.testing.assert_array_equal(a["tokens"], b["tokens"])


def test_steps_differ():
    cfg = DataConfig(global_batch=8, seq_len=32, vocab_size=100)
    a = token_batch(cfg, 0)
    b = token_batch(cfg, 1)
    assert not np.array_equal(a["tokens"], b["tokens"])


def test_host_sharding_disjoint_and_sized():
    full = DataConfig(global_batch=8, seq_len=16, vocab_size=50)
    h0 = DataConfig(global_batch=8, seq_len=16, vocab_size=50, process_index=0, process_count=2)
    h1 = DataConfig(global_batch=8, seq_len=16, vocab_size=50, process_index=1, process_count=2)
    b0, b1 = token_batch(h0, 5), token_batch(h1, 5)
    assert b0["tokens"].shape[0] == 4 and b1["tokens"].shape[0] == 4
    assert not np.array_equal(b0["tokens"], b1["tokens"])


def test_labels_shifted():
    cfg = DataConfig(global_batch=2, seq_len=16, vocab_size=50)
    b = token_batch(cfg, 0)
    assert b["tokens"].shape == b["labels"].shape == (2, 15)


def test_latent_batch_shapes():
    cfg = DataConfig(global_batch=4, seq_len=0, vocab_size=8)
    b = latent_batch(cfg, 0, size=16)
    assert b["latents"].shape == (4, 256, 4)
    assert np.isfinite(b["latents"]).all()


def test_prefetcher_orders_steps():
    cfg = DataConfig(global_batch=2, seq_len=8, vocab_size=10)
    pre = Prefetcher(lambda s: token_batch(cfg, s), start_step=3)
    try:
        steps = [next(pre)[0] for _ in range(4)]
        assert steps == [3, 4, 5, 6]
    finally:
        pre.close()


# ---------------------------------------------------------------------------
# AdamW
# ---------------------------------------------------------------------------


def test_adamw_minimizes_quadratic():
    cfg = AdamWConfig(lr=0.1, weight_decay=0.0, warmup_steps=1, total_steps=200)
    params = {"x": jnp.array([5.0, -3.0])}
    state = init_adamw(params)
    for _ in range(150):
        grads = {"x": 2 * params["x"]}  # d/dx x^2
        params, state = adamw_update(cfg, params, grads, state)
    assert float(jnp.abs(params["x"]).max()) < 0.2


def test_lr_schedule_shape():
    cfg = AdamWConfig(lr=1.0, warmup_steps=10, total_steps=100, min_lr_frac=0.1)
    lrs = [float(lr_schedule(cfg, jnp.asarray(s))) for s in range(101)]
    assert lrs[0] < 0.2  # warmup start
    assert abs(lrs[10] - 1.0) < 1e-6  # peak at end of warmup
    assert lrs[-1] <= 0.11  # cosine floor
    assert max(lrs) <= 1.0 + 1e-6


def test_grad_clip_bounds_update():
    cfg = AdamWConfig(lr=1.0, grad_clip=1.0, weight_decay=0.0, warmup_steps=0, total_steps=10)
    params = {"x": jnp.zeros(4)}
    state = init_adamw(params)
    p1, _ = adamw_update(cfg, params, {"x": jnp.full(4, 1e6)}, state)
    assert float(jnp.abs(p1["x"]).max()) < 2.0  # clip kept the step sane


# ---------------------------------------------------------------------------
# Gradient compression (error feedback)
# ---------------------------------------------------------------------------


@given(st.lists(st.floats(-10, 10), min_size=4, max_size=64), st.integers(2, 30))
@settings(max_examples=50, deadline=None)
def test_error_feedback_unbiased_long_run(xs, steps):
    """Sum of dequantized grads + final residual == sum of true grads
    (error feedback makes compression lossless in the long run)."""
    g = jnp.asarray(xs, jnp.float32)
    err = jnp.zeros_like(g)
    total_deq = jnp.zeros_like(g)
    for _ in range(steps):
        deq, err = compress_decompress(g, err)
        total_deq += deq
    np.testing.assert_allclose(
        np.asarray(total_deq + err), np.asarray(g * steps), rtol=1e-4, atol=1e-3
    )


def test_compression_wire_format_int8_range():
    g = jax.random.normal(jax.random.key(0), (128,)) * 5
    deq, err = compress_decompress(g, jnp.zeros_like(g))
    # dequantized values live on a 255-level grid scaled by max/127
    scale = float(jnp.max(jnp.abs(g))) / 127.0
    levels = np.asarray(deq) / scale
    np.testing.assert_allclose(levels, np.round(levels), atol=1e-4)
    assert np.abs(levels).max() <= 127


def test_compressed_grads_tree():
    grads = {"a": jnp.ones((4,)), "b": {"c": jnp.full((2, 2), -3.0)}}
    comp = init_compression(grads)
    new_g, comp2 = compressed_grads(grads, comp)
    assert jax.tree.structure(new_g) == jax.tree.structure(grads)
    np.testing.assert_allclose(np.asarray(new_g["a"]), 1.0, atol=0.02)
