"""The SDXL base architecture module (``arch/sdxl_unet.py``): what its
``dims`` refuses, its work model at the published widths, its reference
against the served path at a toy size (``testdata/toy_xl.json``) through a
whole run on the CPU, the float8 control it must tell apart, and the
``tf_block_rows_per_image`` reader.  The globbed tests of
``test_work.py`` and ``test_arch_contract.py`` take both of its
configuration files too."""
import copy
import types

import numpy as np
import pytest

from benchmarks.chip import check, run, spec, system
from benchmarks.chip.test_arch_contract import with_key

CELL = "sdxl_base.tiers30-steady"
PUBLISHED = "configs/sdxl_base.json"
TOY = "testdata/toy_xl.json"

#: (key, a value ``arch/sdxl_unet.py`` cannot honour)
REFUSED = [
    ("addition_embed_type", None),
    ("addition_embed_type", "text_image"),
    ("attention_head_dim", 64),
    ("attention_head_dim", [5, 10]),
    ("attention_head_dim", [5, 10, 21]),
    ("transformer_layers_per_block", [1, 2]),
    ("projection_class_embeddings_input_dim", 1536),
    ("addition_time_embed_dim", 128),
    ("num_attention_heads", [5, 10, 20]),
    ("reverse_transformer_layers_per_block", [[10, 10, 10], [2, 2, 2], [1, 1, 1]]),
    ("class_embed_type", "timestep"),
    ("encoder_hid_dim", 1024),
    ("time_cond_proj_dim", 256),
    ("mid_block_type", "UNetMidBlock2DSimpleCrossAttn"),
    ("resnet_time_scale_shift", "scale_shift"),
    ("only_cross_attention", True),
    ("conv_in_kernel", 7),
    ("flip_sin_to_cos", False),
    ("up_block_types", ["UpBlock2D", "CrossAttnUpBlock2D", "CrossAttnUpBlock2D"]),
    ("down_block_types", ["CrossAttnDownBlock2D", "CrossAttnDownBlock2D", "DownBlock2D"]),
    ("scheduler._class_name", "EulerDiscreteScheduler"),
    ("prediction_type", "v_prediction"),
]


def loaded(relpath: str):
    config = spec.load_json(relpath)
    arch = spec.arch(config)
    return config, arch, arch.dims(config)


@pytest.mark.parametrize("key,value", REFUSED, ids=[f"{k}={v}" for k, v in REFUSED])
def test_a_key_sdxl_unet_would_misread_is_refused(key, value):
    config = with_key(spec.load_json(PUBLISHED), key, value)
    with pytest.raises(ValueError, match=key.replace(".", r"\.")):
        spec.arch(config).dims(config)


def test_an_sd1_file_is_refused():
    config = dict(spec.load_json("configs/sd_v14.json"), arch="sdxl_unet")
    with pytest.raises(ValueError, match="addition_embed_type"):
        spec.arch(config).dims(config)


def test_published_dims():
    _, _, d = loaded(PUBLISHED)
    assert d.heads == (5, 10, 20) and d.depths == (1, 2, 10) and d.mid_depth == 10
    assert d.attn_levels == (1, 2) and d.pooled_dim == 1280 and d.time_dim == 1280
    assert [c // h for c, h in zip(d.block_out_channels, d.heads)] == [64, 64, 64]


def test_full_pass_and_partial_fractions():
    _, arch, d = loaded(PUBLISHED)
    f = arch.class_flops(d, 3, 2)
    assert f["full"] == 2 * 3_380_593_295_360  # MACs per row per FULL pass
    assert round(f["sketch"] / f["full"], 4) == 0.0661  # SKETCH, l_sketch = 3
    assert round(f["refine"] / f["full"], 4) == 0.0378  # REFINE, l_refine = 2
    sites = sum(arch.site_macs(p, c, n, d) for kind, p, c, *rest in arch.blocks(d)
                if kind == "tf" for _, n in [rest])
    assert round(sites / arch.macs(d), 3) == 0.760  # transformer sites' share


def test_kernel_calls_carry_each_sites_heads_and_depth():
    _, arch, d = loaded(PUBLISHED)
    calls = arch.kernel_calls(d)
    # 70 blocks, a self- and a cross-attention each; level 1: 10 heads over
    # 4096 positions, level 2 and mid: 20 heads over 1024
    assert len(calls["attention"]) == 2 * 70
    assert calls["attention"][0] == (4096, 4096, 10, 64)
    assert calls["attention"][1] == (4096, 77, 10, 64)
    assert (1024, 1024, 20, 64) in calls["attention"]
    assert not arch.kernel_calls(d, 3)["attention"]  # SKETCH and REFINE run no attention


def test_program_config_matches_the_program_preset():
    from repro.configs import get_unet_config

    config, arch, _ = loaded(PUBLISHED)
    ours = arch.program_config(config)
    preset = get_unet_config("sd_xl")
    fields = ("base_channels", "channel_mult", "n_res_blocks", "attn_levels", "ctx_dim",
              "ctx_len", "time_dim", "groups", "latent_size", "dtype", "pooled_dim",
              "time_ids")
    assert all(getattr(ours, f) == getattr(preset, f) for f in fields)
    assert [ours.heads_at(i) for i in range(3)] == [preset.heads_at(i) for i in range(3)]
    assert [ours.depth_at(i) for i in range(3)] == [preset.depth_at(i) for i in range(3)]
    assert ours.mid_tf_depth == preset.mid_tf_depth == 10


def test_sampler_holds_the_tree_it_is_given_in_bfloat16():
    """The float32 tree handed over is held in place: its matrices become
    bfloat16, its sites stacked; a second sampler on the same tree (as
    ``readings.py`` makes) reads it as it is and gives the same latents."""
    import jax.numpy as jnp

    config, arch, d = loaded(TOY)
    params = system.make_weights(arch.program_config(config), 2**31 + 9)
    tree = check.params_f32(params)
    first = arch.Sampler(config, d, tree)
    assert first.params is tree
    assert tree["conv_in"]["w"].dtype == jnp.bfloat16
    assert tree["conv_in"]["b"].dtype == jnp.bfloat16
    assert tree["gn_out"]["scale"].dtype == jnp.float32
    site = tree["mid"]["tf"]
    assert set(site) == {"gn", "proj_in", "proj_out", "blocks"}
    assert site["blocks"]["self_q"].shape[0] == d.mid_depth
    again = arch.Sampler(config, d, tree, batch=2, rows=1)
    req = [("held", 7, "high", 6), ("held 2", 8, "draft", 5)]
    a, b = first.run_many(req), again.run_many(req)
    for x, y in zip(a, b):
        np.testing.assert_allclose(x, y, rtol=0, atol=1e-5)


def test_sampler_refuses_weights_that_are_not_bfloat16_values():
    config, arch, d = loaded(TOY)
    tree = check.params_f32(system.make_weights(arch.program_config(config), 3))
    tree["conv_in"]["w"] = tree["conv_in"]["w"] * (1 + 2.0**-12)
    with pytest.raises(ValueError, match="bfloat16"):
        arch.Sampler(config, d, tree)


def toy_cell() -> spec.Cell:
    """The toy configuration under the cell's mix, at 8 steps and 2/s."""
    config = spec.load_json(TOY)
    traffic = dict(spec.load_json("traffic/tiers30-steady.json"), rate_per_s=2.0,
                   warmup_steps=8, check_sample=4,
                   steps=[{"p": 0.5, "value": 8}, {"p": 0.5, "uniform": [4, 6]}])
    return spec.Cell(name=CELL, chips=1, config=config, traffic=traffic,
                     end_to_end=[], per_layer=[])


def test_toy_run_is_correct_and_the_control_is_not(tmp_path, monkeypatch):
    monkeypatch.setattr(system, "CACHE_DIR", tmp_path / "jax_cache")
    monkeypatch.setattr(system, "RUNS_DIR", tmp_path / "runs")
    kept = {}
    compare = check.compare

    def keep(cell, rec, latents, params, seed, limits, log):
        kept.update(cell=cell, rec=rec, params=params, seed=seed)
        return compare(cell, rec, latents, params, seed, limits, log)

    monkeypatch.setattr(check, "compare", keep)
    limits = check.load_limits(CELL)
    result = run.run(toy_cell(), 2**31 + 77, 4.0, False, require_tpu=False, limits=limits)
    assert result["correct"], result["checks"]
    assert result["checks"]["compared"]["value"] == 4
    rec = kept["rec"]
    assert spec.load_module("metrics", "tf_block_rows_per_image").read(rec) > 0

    cell = kept["cell"]
    control = spec.arch(cell.config).Sampler(cell.config, rec.dims,
                                             check.params_f32(kept["params"]), quant="fp8")
    latents = {}
    for r in check.sample(rec.window_reqs(), cell.traffic["check_sample"], kept["seed"]):
        latents[r.rid] = control.run(r.prompt, r.seed, r.tier, r.steps)
        r.digest = check.digest(latents[r.rid])
    out = compare(cell, rec, latents, kept["params"], kept["seed"], limits, lambda m: None)
    assert not out["correct"]
    assert out["numbers"]["latent_rel_gap"]["value"] > limits["latent_rel_gap"]


def _record(stats0: dict, stats_end: dict, statuses: list[str]):
    reqs = [types.SimpleNamespace(status=s) for s in statuses]
    return types.SimpleNamespace(stats0=stats0, stats_end=stats_end,
                                 window_reqs=lambda: reqs)


def test_tf_block_rows_per_image_reader():
    read = spec.load_module("metrics", "tf_block_rows_per_image").read
    rec = _record({"tf_block_rows": 1_000}, {"tf_block_rows": 8_000},
                  ["done", "done", "failed", "done"])
    assert read(rec) == 7_000 / 3
    assert read(_record({}, {}, ["done"])) is None  # a program without the counter
    assert read(_record({"tf_block_rows": 0}, {"tf_block_rows": 5}, ["failed"])) is None


def test_toy_file_is_the_published_file_cut_in_width_and_depth_only():
    published, toy = spec.load_json(PUBLISHED), spec.load_json(TOY)
    cut = {"name", "source", "attention_head_dim", "block_out_channels", "cross_attention_dim", "layers_per_block", "norm_num_groups",
           "projection_class_embeddings_input_dim", "sample_size",
           "transformer_layers_per_block", "serving"}
    assert {k: v for k, v in published.items() if k not in cut} == \
        {k: v for k, v in toy.items() if k not in cut}
    assert copy.deepcopy(published["serving"]).keys() == toy["serving"].keys()
