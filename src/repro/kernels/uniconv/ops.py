"""Jitted public wrapper for uniconv (incl. bias and stride-2 subsampling)."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.common import interpret_default
from repro.kernels.uniconv.kernel import uniconv as _kernel


@functools.partial(jax.jit, static_argnames=("hw", "ksize", "stride", "block_l", "block_n"))
def uniconv(
    x: jax.Array,
    w: jax.Array,
    b: jax.Array | None,
    hw: tuple[int, int],
    ksize: int,
    stride: int = 1,
    *,
    block_l: int = 512,
    block_n: int = 256,
) -> jax.Array:
    out = _kernel(
        x, w, hw, ksize,
        block_l=block_l, block_n=block_n, interpret=interpret_default(),
    )
    if stride > 1:
        h, wd = hw
        out = out.reshape(out.shape[0], h, wd, -1)[:, ::stride, ::stride, :]
        out = out.reshape(out.shape[0], -1, out.shape[-1])
    if b is not None:
        out = out + b
    return out
