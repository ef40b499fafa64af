"""Work of one plain matmul call ``(L, cin, cout)``, as the architecture's
``kernel_calls`` lists them: ``L`` positions per row times a ``cin x
cout`` weight.  Bytes are the input and output activations once, at the
activation width, and the weight once, at its own.
"""
from __future__ import annotations


def flops(call: tuple, rows: int) -> int:
    l, cin, cout = call
    return 2 * rows * l * cin * cout


def nbytes(call: tuple, rows: int, act_bytes: int, weight_bytes: int) -> int:
    l, cin, cout = call
    return rows * l * (cin + cout) * act_bytes + cin * cout * weight_bytes
