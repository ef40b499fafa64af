"""Work of one softmax-attention call ``(Lq, Lk, heads, head_dim)``, as the
architecture's ``kernel_calls`` lists them: scores and weighted values.
The q/k/v/o projections are plain matmuls outside the attention kernel
(``matmul.py``).  Bytes are q, k, v read once and the output written
once, at the activation width.
"""
from __future__ import annotations


def flops(call: tuple, rows: int) -> int:
    l_q, l_k, heads, dh = call
    return 2 * 2 * rows * heads * l_q * l_k * dh  # scores and weighted values


def nbytes(call: tuple, rows: int, act_bytes: int) -> int:
    l_q, l_k, heads, dh = call
    return rows * heads * dh * (2 * l_q + 2 * l_k) * act_bytes
