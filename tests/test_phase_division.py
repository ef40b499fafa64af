"""Phase division (Eq. 2) and shift-score machinery (Eq. 1)."""
import numpy as np
from hypothesis import given, settings, strategies as st

from repro.core import phase_division as PD
from repro.core import shift_score as SS


def synthetic_profile(t=49, n_blocks=12, d_true=24, noise=0.02, outliers=(1, 2), seed=0):
    """Two-phase curves shaped like the paper's Fig. 4: an active plateau
    (wave-like, high mean) through the sketching phase, a sharp drop to a
    quiet plateau in refinement; outlier blocks stay active late (Key
    Observation 2).  The 2-means split (Eq. 2) should recover d_true."""
    rng = np.random.default_rng(seed)
    scores = np.zeros((t, n_blocks))
    tt = np.arange(t)
    for b in range(n_blocks):
        early = 0.7 + 0.2 * np.sin(tt / 3 + b)  # active, wave-like
        late = 0.07 + 0.02 * np.sin(tt / 5)
        curve = np.where(tt <= d_true, early, late)
        if (b + 1) in outliers:
            curve = np.where(tt > d_true, 0.6 + 0.1 * np.sin(tt / 2), curve)
        scores[:, b] = curve + rng.normal(0, noise, t)
    return SS.minmax_normalize(np.clip(scores, 0, None))


def test_find_transition_recovers_true_split():
    scores = synthetic_profile(d_true=24)
    prof = SS.ShiftProfile(scores=scores, outlier_blocks=(1, 2))
    d = PD.find_transition(prof)
    assert 18 <= d <= 30, f"D*={d} far from true 24"


def test_outlier_detection():
    scores = synthetic_profile(outliers=(1, 2))
    out = SS.detect_outliers(scores)
    assert set(out) == {1, 2}


def test_no_outliers_on_uniform_curves():
    scores = synthetic_profile(outliers=())
    out = SS.detect_outliers(scores)
    assert len(out) <= 2  # tolerance for noise, but nothing systematic


@given(d_true=st.integers(8, 40), seed=st.integers(0, 20))
@settings(max_examples=30, deadline=None)
def test_transition_tracks_d_true(d_true, seed):
    scores = synthetic_profile(t=49, d_true=d_true, seed=seed)
    prof = SS.ShiftProfile(scores=scores, outlier_blocks=(1, 2))
    d = PD.find_transition(prof)
    assert abs(d - d_true) <= 8


def test_shift_scores_shape_and_order():
    """Eq. 1 on a synthetic trajectory; paper block order (top first)."""
    t, steps = 5, [0, 2, 4]
    rng = np.random.default_rng(0)
    traj = [{s: rng.normal(size=(2, 16, 8)) for s in steps} for _ in range(t)]
    sc = SS.shift_scores(traj)
    assert sc.shape == (t - 1, len(steps))
    # constant activations -> zero shift
    traj_const = [{s: np.ones((2, 4, 4)) for s in steps} for _ in range(t)]
    assert np.allclose(SS.shift_scores(traj_const), 0)


def test_shift_score_eq1_manual():
    a0 = np.ones((4, 4))
    a1 = np.ones((4, 4)) * 2
    traj = [{0: a0}, {0: a1}]
    s = SS.shift_scores(traj)
    want = np.linalg.norm(a1 - a0) / np.linalg.norm(a0)
    np.testing.assert_allclose(s[0, 0], want, rtol=1e-6)


def test_minmax_normalize_range():
    x = np.random.default_rng(1).normal(size=(20, 5)) * 7 + 3
    y = SS.minmax_normalize(x)
    np.testing.assert_allclose(y.min(0), 0, atol=1e-12)
    np.testing.assert_allclose(y.max(0), 1, atol=1e-12)


def test_phase_stats_report():
    scores = synthetic_profile()
    prof = SS.ShiftProfile(scores=scores, outlier_blocks=(1, 2))
    d = PD.find_transition(prof)
    stats = PD.phase_stats(prof, d)
    assert stats["mu_sketch"] > stats["mu_refine"], "sketching phase varies more"
