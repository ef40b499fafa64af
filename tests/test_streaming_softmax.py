"""Property tests of the tile-decoupled online-softmax recurrence
(paper Eqs. 5-6), the math underlying both 2-stage streaming computing
and the flash-attention kernel.

    ES <- ES * exp(prev_max - new_max) + ES_n ;  N1 <- N1 + N0
"""
import math

import numpy as np
from hypothesis import given, settings, strategies as st


def online_softmax_denominator(x: np.ndarray, tile: int) -> tuple[float, float]:
    """Stream x in tiles; return (global_max, exp-sum) via Eqs. 5-6."""
    run_max = -np.inf
    es = 0.0
    for i in range(0, len(x), tile):
        t = x[i : i + tile]
        new_max = max(run_max, float(t.max()))
        es_n = float(np.exp(t - new_max).sum())  # Eq. 5 right
        es = es * math.exp(run_max - new_max) + es_n  # Eq. 6
        run_max = new_max
    return run_max, es


@given(
    x=st.lists(st.floats(-50, 50), min_size=1, max_size=300),
    tile=st.integers(1, 64),
)
@settings(max_examples=200, deadline=None)
def test_online_equals_offline(x, tile):
    x = np.asarray(x, np.float64)
    m, es = online_softmax_denominator(x, tile)
    assert m == x.max()
    want = np.exp(x - x.max()).sum()
    np.testing.assert_allclose(es, want, rtol=1e-10)


@given(
    x=st.lists(st.floats(-30, 30), min_size=2, max_size=200),
    tile_a=st.integers(1, 50),
    tile_b=st.integers(1, 50),
)
@settings(max_examples=100, deadline=None)
def test_tile_size_invariance(x, tile_a, tile_b):
    """Tile decoupling: the result must not depend on the tile size (the
    paper's claim that NCA can start from the FIRST tile generated)."""
    x = np.asarray(x, np.float64)
    _, ea = online_softmax_denominator(x, tile_a)
    _, eb = online_softmax_denominator(x, tile_b)
    np.testing.assert_allclose(ea, eb, rtol=1e-10)


@given(x=st.lists(st.floats(-20, 20), min_size=1, max_size=100))
@settings(max_examples=100, deadline=None)
def test_softmax_from_streamed_characteristics(x):
    """Norm stage: softmax reconstructed from the two streamed
    characteristics (xmax, exp_sum) equals full softmax."""
    x = np.asarray(x, np.float64)
    m, es = online_softmax_denominator(x, 7)
    got = np.exp(x - m) / es
    e = np.exp(x - x.max())
    want = e / e.sum()
    np.testing.assert_allclose(got, want, rtol=1e-9, atol=1e-12)


@given(
    xs=st.lists(st.floats(-10, 10), min_size=2, max_size=100),
    split=st.integers(1, 99),
)
@settings(max_examples=100, deadline=None)
def test_streaming_layernorm_characteristics_merge(xs, split):
    """Eq. 4: (sum, sqsum) accumulated over tiles give exact mean/var."""
    x = np.asarray(xs, np.float64)
    k = min(split, len(x) - 1)
    a, b = x[:k], x[k:]
    s = a.sum() + b.sum()
    sq = (a * a).sum() + (b * b).sum()
    n = len(x)
    mean = s / n
    var = sq / n - mean**2
    np.testing.assert_allclose(mean, x.mean(), rtol=1e-12, atol=1e-12)
    np.testing.assert_allclose(var, x.var(), rtol=1e-9, atol=1e-9)
