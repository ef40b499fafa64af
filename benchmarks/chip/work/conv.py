"""Work of one convolution call ``(L_in, L_out, cin, cout, k)``, as the
architecture's ``kernel_calls`` lists them (from the configuration's
shapes, not from any kernel's tiling).  A strided convolution counts the
positions it produces.  Bytes are the least any implementation moves: the
input and output activations once, at the activation width the program
serves, and the weights once, at their own.
"""
from __future__ import annotations


def flops(call: tuple, rows: int) -> int:
    _, l_out, cin, cout, k = call
    return 2 * rows * l_out * cin * cout * k * k


def nbytes(call: tuple, rows: int, act_bytes: int, weight_bytes: int) -> int:
    l_in, l_out, cin, cout, k = call
    return rows * (l_in * cin + l_out * cout) * act_bytes + k * k * cin * cout * weight_bytes
