"""Uni-conv Pallas kernel — the paper's address-centric dataflow on TPU.

A K x K convolution over the ``(L = H*W, C)`` storage format is executed as
F = K*K plain matmuls (each 1x1 kernel is an MXU-friendly
``(L, Cin) @ (Cin, Cout)``) whose partial sums are accumulated at remapped
output addresses ``l -> l - (oy*W + ox)``.  The activation tile is loaded
once with a halo of ``hr`` rows each side (``pl.Element`` row offsets,
``hr`` rounded up to the sublane tiling so every DMA stays aligned); each
tap multiplies the whole haloed tile and reads its partial sums back at
the static remapped offset ``hr + oy*W + ox`` (the paper's address
generator).  The edge-detector flags become row/col masks computed from
iota.  No im2col materialization, fully regular HBM reads of both
operands — the paper's Sec. IV-A/B benefits carry over verbatim.

Grid: (batch, L tiles, Cout tiles, kernel rows).  The kernel-row axis is
innermost-sequential and carries an fp32 VMEM accumulator; the K taps of
one kernel row run as static code inside the step, so every slice offset
is a compile-time constant.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

#: VMEM bytes one activation / weight tile may take (each is double-buffered)
_X_TILE_BYTES = 4 * 1024 * 1024
_W_TILE_BYTES = 4 * 1024 * 1024
_VMEM_LIMIT = 48 * 1024 * 1024


def _uniconv_kernel(
    x_ref,  # [bl + 2*hr, cin]  haloed activation rows
    w_ref,  # [ksize, cin, bn]  the taps of one kernel row
    o_ref,  # [bl, bn]
    acc_scr,  # [bl, bn] f32
    *,
    bl: int,
    hr: int,
    h: int,
    w: int,
    ksize: int,
):
    li = pl.program_id(1)
    ky_i = pl.program_id(3)

    @pl.when(ky_i == 0)
    def _init():
        acc_scr[...] = jnp.zeros(acc_scr.shape, jnp.float32)

    pad = (ksize - 1) // 2
    # edge detector: output (y, x) pulls input (y+oy, x+ox); contributions
    # crossing the H/W borders are masked (the paper's address flags).
    # The row split of the flat address uses an exact float division
    # (the VPU has no vector integer divide; l < 2**22 keeps it exact).
    out_idx = li * bl + jax.lax.broadcasted_iota(jnp.int32, (bl, 1), 0)
    row = jnp.floor((out_idx.astype(jnp.float32) + 0.5) * (1.0 / w)).astype(jnp.int32)
    col = out_idx - row * w
    dt = jnp.promote_types(x_ref.dtype, w_ref.dtype)
    x = x_ref[...].astype(dt)

    def tap_row(ky):
        oy = ky - pad
        row_ok = (row + oy >= 0) & (row + oy < h)
        for kx in range(ksize):
            ox = kx - pad
            part = jax.lax.dot_general(
                x, w_ref[kx].astype(dt), (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
            start = hr + oy * w + ox  # the paper's address mapping
            valid = row_ok & (col + ox >= 0) & (col + ox < w)
            acc_scr[...] += jnp.where(valid, part[start:start + bl], 0.0)

    for ky in range(ksize):
        pl.when(ky_i == ky)(functools.partial(tap_row, ky))

    @pl.when(ky_i == ksize - 1)
    def _finalize():
        o_ref[...] = acc_scr[...].astype(o_ref.dtype)


def _pick_rows(l: int, rows: int, row_bytes: int, tile: int, block_l: int) -> int:
    """Largest power-of-two row tile <= block_l that divides L, is a
    multiple of the sublane ``tile`` and keeps the haloed activation tile
    (``rows`` extra rows) within budget; all of L when none exists."""
    bl = 0
    cand = tile
    while cand <= min(block_l, l):
        if l % cand == 0 and (cand + rows) * row_bytes <= _X_TILE_BYTES:
            bl = cand
        cand *= 2
    return bl or l


def _pick_lanes(cout: int, block_n: int, col_bytes: int) -> int:
    """Largest multiple of 128 <= block_n dividing Cout whose weight tile
    fits the budget; else all of Cout (the only lane-legal tile widths on
    TPU)."""
    best = 0
    for bn in range(128, block_n + 1, 128):
        if cout % bn == 0 and (best == 0 or bn * col_bytes <= _W_TILE_BYTES):
            best = bn
    return best or cout


def uniconv(
    x: jax.Array,  # [B, L, Cin]
    w: jax.Array,  # [F, Cin, Cout]
    hw: tuple[int, int],
    ksize: int,
    *,
    block_l: int = 512,
    block_n: int = 256,
    interpret: bool = True,
) -> jax.Array:
    """Stride-1 'same' conv in the (L, C) layout via address-centric matmuls.

    Stride-2 downsampling (3 layers in SD's U-Net) is handled by the ops
    wrapper via output subsampling; the dominant stride-1 layers all run
    through this kernel.
    """
    b, l, cin = x.shape
    nf, _, cout = w.shape
    h, wdim = hw
    assert nf == ksize * ksize and l == h * wdim, (nf, ksize, l, h, wdim)

    pad = (ksize - 1) // 2
    tile = 8 * 4 // min(x.dtype.itemsize, 4)  # sublanes per (8, 128) tile
    halo = pad * wdim + pad  # max |flat shift|
    hr = -(-halo // tile) * tile
    bl = _pick_rows(l, 2 * hr, cin * x.dtype.itemsize, tile, block_l)
    bn = _pick_lanes(cout, block_n, ksize * cin * w.dtype.itemsize)
    nl, nn = l // bl, cout // bn

    kernel = functools.partial(_uniconv_kernel, bl=bl, hr=hr, h=h, w=wdim, ksize=ksize)
    xp = jnp.pad(x, ((0, 0), (hr, hr), (0, 0)))
    wk = w.reshape(ksize, ksize, cin, cout)
    return pl.pallas_call(
        kernel,
        grid=(b, nl, nn, ksize),
        in_specs=[
            pl.BlockSpec(
                (pl.Squeezed(), pl.Element(bl + 2 * hr), pl.Element(cin)),
                lambda bi, li, ni, ky: (bi, li * bl, 0),
            ),
            pl.BlockSpec(
                (pl.Squeezed(), ksize, cin, bn), lambda bi, li, ni, ky: (ky, 0, 0, ni)
            ),
        ],
        out_specs=pl.BlockSpec(
            (pl.Squeezed(), bl, bn), lambda bi, li, ni, ky: (bi, li, ni)
        ),
        out_shape=jax.ShapeDtypeStruct((b, l, cout), x.dtype),
        scratch_shapes=[pltpu.VMEM((bl, bn), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT,
        ),
        interpret=interpret,
    )(xp, wk)
