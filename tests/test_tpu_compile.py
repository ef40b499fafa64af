"""Compile rehearsals of the served Pallas kernels for a TPU v5e.

The TPU compiler is installed without a chip, so each kernel is lowered
and compiled for a *described* ``v5e:2x2`` topology at the published
sd_v14 widths.  Nothing runs: these tests catch what interpret mode
cannot — tiles that are not (8, 128)-aligned, ops Mosaic does not
implement, and kernels that overflow VMEM.  The kernels are called with
``interpret=False`` directly, since the ops wrappers pick interpret mode
from the (CPU) default backend.
"""
import os

import jax
import jax.numpy as jnp
import pytest

from repro.kernels.flash_attention.kernel import flash_attention
from repro.kernels.stream_norm.kernel import stream_group_norm
from repro.kernels.uniconv.kernel import uniconv

#: sd_v14 U-Net levels: (latent side, channels)
LEVELS = [(64, 320), (32, 640), (16, 1280), (8, 1280)]
DTYPES = [jnp.float32, jnp.bfloat16]
B = 2  # one lane, CFG-doubled


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import compilation_cache, topologies
    from jax.sharding import SingleDeviceSharding

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this environment
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip cannot be read back without one
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.compilation_cache.reset_cache()
    return SingleDeviceSharding(topo.devices[0])


def _compile(fn, sharding, *shapes):
    args = [jax.ShapeDtypeStruct(s, dt, sharding=sharding) for s, dt in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


@pytest.mark.parametrize("ksize", [3, 1])
@pytest.mark.parametrize("dtype", DTYPES, ids=["f32", "bf16"])
@pytest.mark.parametrize("side,c", LEVELS)
def test_uniconv_compiles(one_chip, side, c, dtype, ksize):
    fn = lambda x, w: uniconv(x, w, (side, side), ksize, interpret=False)
    _compile(fn, one_chip, ((B, side * side, c), dtype), ((ksize * ksize, c, c), dtype))


@pytest.mark.parametrize("dtype", DTYPES, ids=["f32", "bf16"])
@pytest.mark.parametrize("side,c", LEVELS + [(64, 960)])  # + the up-path concat
def test_group_norm_silu_compiles(one_chip, side, c, dtype):
    fn = lambda x, s, b: stream_group_norm(x, s, b, groups=32, silu=True, interpret=False)
    _compile(
        fn, one_chip, ((B, side * side, c), dtype), ((c,), jnp.float32), ((c,), jnp.float32)
    )


@pytest.mark.parametrize("lk", [4096, 77], ids=["self", "cross"])
def test_flash_attention_compiles(one_chip, lk):
    # sd_v14 level 0: 8 heads of width 40 over the 64x64 latent
    fn = lambda q, k, v: flash_attention(
        q, k, v, causal=False, block_q=128, block_k=128 if lk % 128 == 0 else lk,
        interpret=False,
    )
    q = ((B, 8, 4096, 40), jnp.float32)
    kv = ((B, 8, lk, 40), jnp.float32)
    _compile(fn, one_chip, q, kv, kv)
