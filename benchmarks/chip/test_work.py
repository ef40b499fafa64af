"""The work models of the architectures: sd_v14's against the numbers the
program's own MAC model gives and against what the benchmark counted
before its U-Net code moved into ``arch/sd_unet.py``
(``testdata/golden/work.json``); every configuration's kernel calls
against its own MAC count."""
import importlib

import pytest

from benchmarks.chip import spec

#: every configuration file: the benchmark's and the CPU tests'
CONFIGS = sorted(str(p.relative_to(spec.HERE))
                 for p in (*spec.HERE.glob("configs/*.json"), *spec.HERE.glob("testdata/*.json")))
CLASSES = ("full", "sketch", "refine")


def budget(config: dict, cls: str) -> int:
    """The ``kernel_calls`` budget of a branch class (``-1``: FULL)."""
    return {"full": -1, "sketch": config["serving"]["l_sketch"],
            "refine": config["serving"]["l_refine"]}[cls]


def loaded(relpath: str):
    config = spec.load_json(relpath)
    arch = spec.arch(config)
    return config, arch, arch.dims(config)


def test_sd_v14_full_pass_and_partial_fractions():
    _, arch, dims = loaded("configs/sd_v14.json")
    br = arch.breakdown(dims)
    assert br.total == 401_608_867_840  # MACs per row per FULL pass
    assert round(br.total / 1e9, 1) == 401.6
    assert round(br.partial(3) / br.total, 3) == 0.380  # SKETCH, l_sketch = 3
    assert round(br.partial(2) / br.total, 3) == 0.224  # REFINE, l_refine = 2
    assert arch.class_flops(dims, 3, 2)["full"] == 2 * br.total


@pytest.mark.parametrize("cls", CLASSES)
@pytest.mark.parametrize("relpath", CONFIGS)
def test_blocks_account_for_every_mac(relpath, cls):
    """Convolutions + attention + plain matmuls, the calls ``kernel_calls``
    lists, add up to the MAC model's pass, FULL and partial."""
    config, arch, dims = loaded(relpath)
    s = config["serving"]
    flops = 0
    for kind, calls in arch.kernel_calls(dims, budget(config, cls)).items():
        work = importlib.import_module(f"benchmarks.chip.work.{kind}")
        flops += sum(work.flops(c, 1) for c in calls)
    assert flops == arch.class_flops(dims, s["l_sketch"], s["l_refine"])[cls]


@pytest.mark.parametrize("cls", CLASSES)
@pytest.mark.parametrize("relpath", ["configs/sd_v14.json", "testdata/toy.json"])
def test_sd_unet_work_matches_its_golden(relpath, cls):
    """``class_flops`` and the convolution and attention calls, case by case,
    as the benchmark counted them before the move."""
    config, arch, dims = loaded(relpath)
    s = config["serving"]
    want = spec.load_json("testdata/golden/work.json")[config["name"]]
    assert arch.class_flops(dims, s["l_sketch"], s["l_refine"]) == want["class_flops"]
    got = arch.kernel_calls(dims, budget(config, cls))
    for kind in ("conv", "attention"):
        assert [list(c) for c in got[kind]] == want["calls"][str(budget(config, cls))][kind]


def test_bytes_are_at_least_operands():
    from benchmarks.chip.work import attention, conv, matmul

    _, arch, dims = loaded("configs/sd_v14.json")
    calls = arch.kernel_calls(dims)
    call = calls["conv"][0]  # conv_in: 64x64x4 -> 320
    assert call == (4096, 4096, 4, 320, 3)
    assert conv.nbytes(call, 8, 4, 2) == 8 * 4096 * (4 + 320) * 4 + 9 * 4 * 320 * 2
    att = calls["attention"][0]  # first self-attention: 4096 positions, 8 heads of 40
    assert att == (4096, 4096, 8, 40)
    assert attention.flops(att, 1) == 4 * 8 * 4096 * 4096 * 40
    mm = calls["matmul"][0]  # first self-attention's q projection
    assert mm == (4096, 320, 320)
    assert matmul.nbytes(mm, 8, 4, 2) == 8 * 4096 * (320 + 320) * 4 + 320 * 320 * 2
