"""Work of the softmax attention in one U-Net pass, from the configuration's
shapes: per transformer block one self-attention over the ``L`` latent
positions and one cross-attention over the ``ctx_len`` text positions, with
``heads`` heads of width ``c / heads``.  The q/k/v/o projections are plain
matmuls outside the attention kernel and are not counted here.  Bytes are
q, k, v read once and the output written once, at the activation width.
"""
from __future__ import annotations

from benchmarks.chip.work import unet


def calls(d, l: int = -1) -> list[tuple[int, int, int, int]]:
    """``(Lq, Lk, heads, head_dim)`` per attention of a pass with budget
    ``l`` (``l < 0``: FULL)."""
    out = []
    for b in unet.blocks(d, l):
        if b[0] == "tf":
            _, l_q, c = b
            out += [(l_q, l_q, d.heads, c // d.heads), (l_q, d.ctx_len, d.heads, c // d.heads)]
    return out


def flops(call: tuple, rows: int) -> int:
    l_q, l_k, heads, dh = call
    return 2 * 2 * rows * heads * l_q * l_k * dh  # scores and weighted values


def nbytes(call: tuple, rows: int, act_bytes: int) -> int:
    l_q, l_k, heads, dh = call
    return rows * heads * dh * (2 * l_q + 2 * l_k) * act_bytes
