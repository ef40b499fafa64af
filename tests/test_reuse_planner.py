"""Adaptive reuse & fusion planner (Sec. V): invariants + paper ablation."""
import numpy as np
from hypothesis import given, settings, strategies as st

from repro.configs import get_unet_config
from repro.core import reuse_planner as RP

MB = 2**20


def test_unet_layer_list_nonempty_and_positive():
    layers = RP.unet_conv_layers(get_unet_config("sd_v14"))
    assert len(layers) > 40  # paper Fig. 13 indexes 0-51
    for l in layers:
        assert l.weight > 0 and l.act_in > 0 and l.act_out > 0


def test_optimized_never_exceeds_baseline():
    layers = RP.unet_conv_layers(get_unet_config("sd_v14"))
    plans = RP.plan_layers(layers, 2 * MB)
    for p in plans:
        assert p.traffic_optimized <= p.traffic_baseline


def test_reuse_picks_smaller_operand():
    layers = [
        RP.LayerSizes("big_act", weight=1 * MB, act_in=8 * MB, act_out=8 * MB),
        RP.LayerSizes("big_wgt", weight=8 * MB, act_in=1 * MB, act_out=1 * MB),
    ]
    plans = RP.plan_layers(layers, 2 * MB)
    assert plans[0].reuse == "weight"
    assert plans[1].reuse == "input"


def test_tiled_when_both_exceed_buffer():
    layers = [RP.LayerSizes("huge", weight=8 * MB, act_in=8 * MB, act_out=8 * MB)]
    plans = RP.plan_layers(layers, 2 * MB)
    assert plans[0].reuse == "tiled"


def test_cross_fusion_only_with_weight_reuse():
    layers = RP.unet_conv_layers(get_unet_config("sd_v14"))
    for p in RP.plan_layers(layers, 2 * MB):
        if p.fusion == "cross":
            assert p.reuse == "weight", "cross-layer fusion requires weight reuse (Sec. V-B)"


def test_paper_shallow_deep_pattern():
    """Paper Fig. 13: shallow/deep layers are activation-heavy (weight
    reuse), middle layers weight-heavy (input reuse)."""
    layers = RP.unet_conv_layers(get_unet_config("sd_v14"))
    plans = RP.plan_layers(layers, 2 * MB)
    n = len(plans)
    shallow = plans[:4]
    middle = plans[n // 2 - 4 : n // 2 + 4]
    assert sum(p.reuse == "weight" for p in shallow) >= 3
    assert sum(p.reuse == "input" for p in middle) >= 6


def test_buffer_sweep_monotone():
    """Fig. 16 (right): larger buffers never increase off-chip traffic."""
    layers = RP.unet_conv_layers(get_unet_config("sd_v14"))
    sizes = [256 * 1024, 512 * 1024, MB, 2 * MB, 4 * MB, 8 * MB]
    sweep = RP.buffer_sweep(layers, sizes)
    vals = [sweep[s] for s in sizes]
    assert all(b <= a for a, b in zip(vals, vals[1:]))


def test_summary_reduction_band():
    """Paper reports ~24.3% (reuse) + ~30.5% (fusion) off-chip savings; the
    combined model should show a large (>30%) reduction vs im2col."""
    layers = RP.unet_conv_layers(get_unet_config("sd_v14"))
    summary = RP.traffic_summary(RP.plan_layers(layers, 2 * MB))
    assert summary["reduction"] > 0.3
    assert summary["n_input_reuse"] + summary["n_weight_reuse"] + summary["n_tiled"] == len(layers)


# ---------------------------------------------------------------------------
# Edge cases: degenerate budgets, degenerate networks, dtype widths
# ---------------------------------------------------------------------------


def test_zero_buffer_budget_everything_tiled():
    """A zero-byte buffer can keep nothing resident: every layer must fall
    back to tiled streaming and still beat the im2col baseline."""
    layers = RP.unet_conv_layers(get_unet_config("sd_v14"))
    plans = RP.plan_layers(layers, 0)
    for p in plans:
        assert p.reuse == "tiled"
        assert p.fusion == "none"
        assert p.traffic_optimized <= p.traffic_baseline


def test_tiny_buffer_budget_invariant_holds():
    for budget in (1, 64, 4096):
        plans = RP.plan_layers(RP.unet_conv_layers(get_unet_config("sd_v14")), budget)
        for p in plans:
            assert p.traffic_optimized <= p.traffic_baseline


def test_single_layer_network_never_fuses():
    lay = RP.LayerSizes("only", weight=MB, act_in=2 * MB, act_out=2 * MB)
    for budget in (0, MB // 2, 2 * MB, 64 * MB):
        plans = RP.plan_layers([lay], budget)
        assert len(plans) == 1
        assert plans[0].fusion == "none"  # no successor to fuse into
        assert plans[0].traffic_optimized <= plans[0].traffic_baseline


def test_dtype_bytes_variants():
    """Layer byte sizes must scale linearly with dtype width (MACs must
    not), and the optimized<=baseline invariant must hold at every width."""
    cfg = get_unet_config("sd_v14")
    ref = RP.unet_conv_layers(cfg, dtype_bytes=1)
    for db in (1, 2, 4, 8):
        layers = RP.unet_conv_layers(cfg, dtype_bytes=db)
        for lay, base in zip(layers, ref):
            assert lay.weight == db * base.weight
            assert lay.act_in == db * base.act_in
            assert lay.act_out == db * base.act_out
            assert lay.macs == base.macs
        for p in RP.plan_layers(layers, 2 * MB):
            assert p.traffic_optimized <= p.traffic_baseline


def test_buffer_sweep_handles_degenerate_sizes():
    layers = RP.unet_conv_layers(get_unet_config("sd_toy"))
    sweep = RP.buffer_sweep(layers, [0, 1, 2 * MB])
    assert sweep[0] >= sweep[1] >= sweep[2 * MB] > 0


@given(
    w=st.integers(1, 64), ai=st.integers(1, 64), ao=st.integers(1, 64),
    buf=st.integers(1, 64),
)
@settings(max_examples=200, deadline=None)
def test_single_layer_traffic_bounds(w, ai, ao, buf):
    """Property: optimized traffic for one layer is at least the compulsory
    traffic (each tensor touched once) and at most the tiled bound."""
    lay = RP.LayerSizes("x", weight=w * MB, act_in=ai * MB, act_out=ao * MB)
    p = RP.plan_layers([lay], buf * MB)[0]
    compulsory = lay.weight + lay.act_in + lay.act_out
    tiled_bound = lay.weight + 2 * lay.act_in + lay.act_out
    assert compulsory <= p.traffic_optimized + 1e-9 or p.fusion != "none"
    assert p.traffic_optimized <= tiled_bound
