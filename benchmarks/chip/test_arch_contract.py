"""The architecture contract: every configuration file names a module
``arch/<arch>.py`` that exports ``ARCH_NAMES``; a configuration that
names none, or that its module would misread, is refused before any run;
and ``arch/sd_unet.py``'s reference gives, bit for bit, the latents the
benchmark's reference gave before it moved there
(``testdata/golden/reference_toy.npz``; regenerate it with ``python
benchmarks/chip/test_arch_contract.py`` only after a JAX upgrade)."""
import copy
import json
import sys
from pathlib import Path

import numpy as np
import pytest

if __name__ == "__main__":
    ROOT = Path(__file__).resolve().parents[2]
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from benchmarks.chip import spec  # noqa: E402
from benchmarks.chip.test_work import CONFIGS, loaded  # noqa: E402

#: what every ``arch/<name>.py`` exports (``spec.py`` says what each is)
ARCH_NAMES = ("dims", "program_config", "class_flops", "kernel_calls", "Sampler")
GOLDEN = spec.HERE / "testdata" / "golden" / "reference_toy.npz"
#: one request per tier kind: all FULL, FULL/SKETCH/REFINE interleaved,
#: REFINE-heavy; the first seed past 32 bits
GOLDEN_REQUESTS = [("golden exact", 2**31 + 11, "exact", 8), ("golden high", 12, "high", 8),
                   ("golden draft", 13, "draft", 8)]
GOLDEN_WEIGHT_SEED = 2**31 + 5

#: SDXL base's published U-Net keys (stabilityai/stable-diffusion-xl-base-1.0,
#: unet/config.json and scheduler/scheduler_config.json) that SD 1.x lacks
SDXL = {
    "attention_head_dim": [5, 10, 20],
    "transformer_layers_per_block": [1, 2, 10],
    "addition_embed_type": "text_time",
    "addition_time_embed_dim": 256,
    "projection_class_embeddings_input_dim": 2816,
    "scheduler._class_name": "EulerDiscreteScheduler",
}
#: (key, a value ``arch/sd_unet.py`` cannot honour)
REFUSED = [
    *SDXL.items(),
    ("num_attention_heads", 8),
    ("class_embed_type", "timestep"),
    ("encoder_hid_dim", 1024),
    ("prediction_type", "v_prediction"),
    ("center_input_sample", True),
    ("flip_sin_to_cos", False),
    ("freq_shift", 1),
    ("act_fn", "gelu"),
    ("downsample_padding", 0),
    ("mid_block_scale_factor", 2),
    ("up_block_types", ["CrossAttnUpBlock2D"] * 3 + ["UpBlock2D"]),
]


def with_key(config: dict, key: str, value) -> dict:
    """``config`` with ``key`` set (``scheduler.<k>`` in the scheduler;
    ``prediction_type`` there too, where diffusers keeps it)."""
    out = copy.deepcopy(config)
    if key.startswith("scheduler.") or key == "prediction_type":
        out["scheduler"][key.removeprefix("scheduler.")] = value
    else:
        out[key] = value
    return out


def load_cell_of(config: dict, tmp_path, monkeypatch) -> spec.Cell:
    """``spec.load_cell`` of a one-cell benchmark over ``config``."""
    path = tmp_path / "config.json"
    path.write_text(json.dumps(config))
    bench = {"configs": [{"name": "c", "file": str(path)}],
             "workloads": [{"name": "w", "config": "c", "traffic": "tiers-steady", "chips": 1}],
             "end_to_end": [], "per_layer": []}
    monkeypatch.setattr(spec, "load_benchmark", lambda: bench)
    return spec.load_cell("w")


@pytest.mark.parametrize("relpath", CONFIGS)
def test_every_configuration_names_a_module_with_the_contract(relpath):
    config = spec.load_json(relpath)
    arch = spec.arch(config)
    assert all(callable(getattr(arch, name)) for name in ARCH_NAMES)
    assert spec.arch(config) is arch  # one module object per architecture


@pytest.mark.parametrize("relpath", CONFIGS)
def test_full_costs_more_than_sketch_and_sketch_more_than_refine(relpath):
    config, arch, dims = loaded(relpath)
    s = config["serving"]
    f = arch.class_flops(dims, s["l_sketch"], s["l_refine"])
    assert f["full"] > f["sketch"] > f["refine"] > 0


def test_benchmark_cells_load():
    for w in spec.load_benchmark()["workloads"]:
        cell = spec.load_cell(w["name"])
        assert spec.arch(cell.config).dims(cell.config)


@pytest.mark.parametrize("arch", [None, "sdxl_unet", "../run", 3])
def test_a_missing_or_unknown_arch_is_refused(arch, tmp_path, monkeypatch):
    config = spec.load_json("configs/sd_v14.json")
    if arch is None:
        del config["arch"]
    else:
        config["arch"] = arch
    with pytest.raises(ValueError, match='key "arch"'):
        load_cell_of(config, tmp_path, monkeypatch)


@pytest.mark.parametrize("key,value", REFUSED, ids=[k for k, _ in REFUSED])
def test_a_key_sd_unet_would_misread_is_refused(key, value, tmp_path, monkeypatch):
    config = with_key(spec.load_json("configs/sd_v14.json"), key, value)
    arch = spec.arch(config)
    with pytest.raises(ValueError, match=key.replace(".", r"\.")):
        arch.dims(config)
    with pytest.raises(ValueError, match=key.replace(".", r"\.")):
        load_cell_of(config, tmp_path, monkeypatch)


def test_published_nulls_are_read_as_absent():
    """SDXL and SD 2.x publish these keys as null: no added embedding."""
    config = spec.load_json("configs/sd_v14.json")
    nulls = dict(config, num_attention_heads=None, class_embed_type=None, encoder_hid_dim=None,
                 addition_embed_type=None, transformer_layers_per_block=1)
    arch = spec.arch(config)
    assert arch.dims(nulls) == arch.dims(config)


def reference_latents() -> dict[str, np.ndarray]:
    """The toy configuration's reference latents of ``GOLDEN_REQUESTS``, in
    one lockstep batch, on weights made from ``GOLDEN_WEIGHT_SEED``."""
    from benchmarks.chip import check, system

    config, arch, dims = loaded("testdata/toy.json")
    params = system.make_weights(arch.program_config(config), GOLDEN_WEIGHT_SEED)
    sampler = arch.Sampler(config, dims, check.params_f32(params), batch=len(GOLDEN_REQUESTS))
    out = sampler.run_many(GOLDEN_REQUESTS)
    return {tier: x for (_, _, tier, _), x in zip(GOLDEN_REQUESTS, out)}


def test_reference_reproduces_its_golden_bit_for_bit():
    want = np.load(GOLDEN)
    for tier, got in reference_latents().items():
        np.testing.assert_array_equal(got, want[tier], err_msg=tier)


if __name__ == "__main__":
    import jax

    np.savez(GOLDEN, **reference_latents(), jax_version=np.array(jax.__version__))
