"""The analytic work functions against the numbers the program's own MAC
model gives for sd_v14, and against each other."""
import pytest

from benchmarks.chip import spec
from benchmarks.chip.work import attention, conv, unet


@pytest.fixture(scope="module")
def dims():
    return spec.unet_dims(spec.load_json("configs/sd_v14.json"))


def test_sd_v14_full_pass_and_partial_fractions(dims):
    br = unet.breakdown(dims)
    assert round(br.total / 1e9, 1) == 401.6  # GMAC per row per FULL pass
    assert round(br.partial(3) / br.total, 3) == 0.380  # SKETCH, l_sketch = 3
    assert round(br.partial(2) / br.total, 3) == 0.224  # REFINE, l_refine = 2
    assert unet.class_flops(dims, 3, 2)["full"] == 2 * br.total


@pytest.mark.parametrize("l", [-1, 3, 2])
def test_blocks_account_for_every_mac(dims, l):
    """Convolutions + attention + the transformer blocks' plain matmuls
    add up to the MAC model's pass, FULL and partial."""
    br = unet.breakdown(dims)
    macs = sum(conv.flops(c, 1) for c in conv.calls(dims, l)) // 2
    macs += sum(attention.flops(c, 1) for c in attention.calls(dims, l)) // 2
    for b in unet.blocks(dims, l):
        if b[0] == "tf":
            _, n, c = b
            ctx, cd = dims.ctx_len, dims.cross_attention_dim
            macs += 4 * n * c * c + 2 * n * c * c + 2 * ctx * cd * c + 12 * n * c * c
    assert macs == br.partial(l)


def test_bytes_are_at_least_operands(dims):
    call = conv.calls(dims)[0]  # conv_in: 64x64x4 -> 320
    assert call == (4096, 4096, 4, 320, 3)
    assert conv.nbytes(call, 8, 4, 2) == 8 * 4096 * (4 + 320) * 4 + 9 * 4 * 320 * 2
    att = attention.calls(dims)[0]  # first self-attention: 4096 positions, 8 heads of 40
    assert att == (4096, 4096, 8, 40)
    assert attention.flops(att, 1) == 4 * 8 * 4096 * 4096 * 40
