"""One-pass streaming norm kernel (paper Sec. IV-C, Eq. 4).

Layernorm is computed with a *single* traversal: sum and square-sum are
accumulated while the row streams through VMEM (the NCA stage), then
``var = E[x^2] - mean^2`` and the normalization are applied immediately —
no second pass over HBM, which is precisely inefficiency-(i) the paper
eliminates.  RMSNorm shares the datapath with the mean-branch muxed off
(the reconfigurable-VPU story of Sec. IV-D).

Grid: row tiles; the feature dimension stays VMEM-resident.

``stream_group_norm`` lifts the datapath to the U-Net's ``[B, L, C]``
group norm, whose statistics span L *and* the channels of each group.  A
whole 64x64 image at 640-960 channels does not fit VMEM, so it tiles L:
one statistics pass over the tiles, then one normalize pass with an
optional fused SiLU epilogue, so the pervasive ``silu(group_norm(x))``
pattern never round-trips the activation through HBM between norm and
nonlinearity (the MII-style fusion).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _norm_kernel(x_ref, scale_ref, bias_ref, o_ref, *, mode: str, eps: float):
    x = x_ref[...].astype(jnp.float32)  # [bm, d]
    d = x.shape[-1]
    # NCA: one pass produces both characteristics
    s = jnp.sum(x, axis=-1, keepdims=True) / d
    sq = jnp.sum(x * x, axis=-1, keepdims=True) / d
    if mode == "layernorm":
        var = jnp.maximum(sq - s * s, 0.0)
        y = (x - s) * jax.lax.rsqrt(var + eps)
    else:  # rmsnorm
        y = x * jax.lax.rsqrt(sq + eps)
    y = y * scale_ref[...].astype(jnp.float32)
    if mode == "layernorm":
        y = y + bias_ref[...].astype(jnp.float32)
    o_ref[...] = y.astype(o_ref.dtype)


def stream_norm(
    x: jax.Array,  # [M, D]
    scale: jax.Array,  # [D]
    bias: jax.Array | None,  # [D] (layernorm only)
    *,
    mode: str = "layernorm",
    eps: float = 1e-6,
    block_m: int = 256,
    interpret: bool = True,
) -> jax.Array:
    assert mode in ("layernorm", "rmsnorm")
    m, d = x.shape
    bm = min(block_m, m)
    while m % bm:
        bm -= 1
    if bias is None:
        bias = jnp.zeros((d,), x.dtype)
    kernel = functools.partial(_norm_kernel, mode=mode, eps=eps)
    return pl.pallas_call(
        kernel,
        grid=(m // bm,),
        in_specs=[
            pl.BlockSpec((bm, d), lambda i: (i, 0)),
            pl.BlockSpec((d,), lambda i: (0,)),
            pl.BlockSpec((d,), lambda i: (0,)),
        ],
        out_specs=pl.BlockSpec((bm, d), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((m, d), x.dtype),
        interpret=interpret,
    )(x, scale, bias)


#: VMEM bytes one group-norm row tile may take (it is double-buffered)
_GN_TILE_BYTES = 2 * 1024 * 1024


def _gn_stats_kernel(x_ref, s_ref, sq_ref):
    """Pass 1: per-channel sum and square-sum, accumulated over L tiles."""

    @pl.when(pl.program_id(1) == 0)
    def _init():
        s_ref[...] = jnp.zeros(s_ref.shape, jnp.float32)
        sq_ref[...] = jnp.zeros(sq_ref.shape, jnp.float32)

    x = x_ref[...].astype(jnp.float32)  # [bl, c]
    s_ref[...] += jnp.sum(x, axis=0, keepdims=True)
    sq_ref[...] += jnp.sum(x * x, axis=0, keepdims=True)


def _gn_apply_kernel(x_ref, a_ref, b_ref, o_ref, *, silu: bool):
    """Pass 2: the folded per-channel affine, plus the fused SiLU."""
    y = x_ref[...].astype(jnp.float32) * a_ref[...] + b_ref[...]
    if silu:
        y = y * jax.nn.sigmoid(y)  # fused epilogue: no HBM round-trip
    o_ref[...] = y.astype(o_ref.dtype)


def _gn_rows(l: int, c: int) -> int:
    """Largest power-of-two multiple of 8 dividing L within the tile
    budget (all of L when L has no such divisor)."""
    bl = 8
    while l % (bl * 2) == 0 and bl * 2 * c * 4 <= _GN_TILE_BYTES:
        bl *= 2
    return bl if l % bl == 0 else l


def stream_group_norm(
    x: jax.Array,  # [B, L, C]
    scale: jax.Array,  # [C]
    bias: jax.Array,  # [C]
    *,
    groups: int,
    eps: float = 1e-5,
    silu: bool = False,
    interpret: bool = True,
) -> jax.Array:
    """Group norm over L tiles in two passes, so no tile holds a whole image.

    Pass 1 streams the rows once and accumulates per-channel sum and
    square-sum (the NCA characteristics); the group statistics
    ``var = E[x^2] - mean^2`` are folded into one per-channel affine on
    ``[B, C]`` vectors; pass 2 applies it with the optional SiLU epilogue.
    """
    b, l, c = x.shape
    assert c % groups == 0, (c, groups)
    bl = _gn_rows(l, c)
    nl = l // bl
    row_spec = pl.BlockSpec((pl.Squeezed(), bl, c), lambda bi, li: (bi, li, 0))
    vec_spec = pl.BlockSpec((pl.Squeezed(), 1, c), lambda bi, li: (bi, 0, 0))
    vec = jax.ShapeDtypeStruct((b, 1, c), jnp.float32)
    s, sq = pl.pallas_call(
        _gn_stats_kernel,
        grid=(b, nl),
        in_specs=[row_spec],
        out_specs=[vec_spec, vec_spec],
        out_shape=[vec, vec],
        compiler_params=pltpu.CompilerParams(dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(x)
    n = l * (c // groups)
    mean = s.reshape(b, groups, c // groups).sum(-1) / n  # [B, G]
    var = jnp.maximum(sq.reshape(b, groups, c // groups).sum(-1) / n - mean * mean, 0.0)
    inv = jnp.repeat(jax.lax.rsqrt(var + eps), c // groups, axis=-1)  # [B, C]
    mean_c = jnp.repeat(mean, c // groups, axis=-1)
    a = inv * scale.astype(jnp.float32)
    shift = bias.astype(jnp.float32) - mean_c * a
    return pl.pallas_call(
        functools.partial(_gn_apply_kernel, silu=silu),
        grid=(b, nl),
        in_specs=[row_spec, vec_spec, vec_spec],
        out_specs=row_spec,
        out_shape=jax.ShapeDtypeStruct((b, l, c), x.dtype),
        compiler_params=pltpu.CompilerParams(dimension_semantics=("parallel", "parallel")),
        interpret=interpret,
    )(x, a[:, None, :], shift[:, None, :])
