"""Work of the convolutions in one U-Net pass, from the configuration's
shapes (not from any kernel's tiling).

Every convolution of the model is one call: 3x3 and 1x1 convolutions of
the residual blocks, the 1x1 ``proj_in``/``proj_out`` of each transformer
block, and the strided and upsampling 3x3 convolutions.  A strided
convolution counts the positions it produces.  Bytes are the least any
implementation moves: the input and output activations once, at the
activation width the program serves, and the weights once, at their own.
"""
from __future__ import annotations

from benchmarks.chip.work import unet


def calls(d, l: int = -1) -> list[tuple[int, int, int, int, int]]:
    """``(L_in, L_out, cin, cout, k)`` per convolution of a pass with
    budget ``l`` (``l < 0``: FULL)."""
    out = []
    for b in unet.blocks(d, l):
        if b[0] == "conv":
            out.append(b[1:])
        elif b[0] == "res":
            _, l, cin, cout = b
            out += [(l, l, cin, cout, 3), (l, l, cout, cout, 3)]
            if cin != cout:
                out.append((l, l, cin, cout, 1))
        elif b[0] == "tf":
            _, l, c = b
            out += [(l, l, c, c, 1), (l, l, c, c, 1)]
    return out


def flops(call: tuple, rows: int) -> int:
    _, l_out, cin, cout, k = call
    return 2 * rows * l_out * cin * cout * k * k


def nbytes(call: tuple, rows: int, act_bytes: int, weight_bytes: int) -> int:
    l_in, l_out, cin, cout, k = call
    return rows * (l_in * cin + l_out * cout) * act_bytes + k * k * cin * cout * weight_bytes
