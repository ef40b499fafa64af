"""Shared kernel plumbing: interpret-mode selection and tiling helpers."""
from __future__ import annotations

import jax


def interpret_default() -> bool:
    """Pallas kernels compile for the TPU and run in the interpreter only
    on the CPU (tests); any other platform is an error, never a silent
    interpreter."""
    platform = jax.default_backend()
    if platform == "tpu":
        return False
    if platform == "cpu":
        return True
    raise RuntimeError(
        f"Pallas kernels support tpu (compiled) or cpu (interpreted), not {platform!r}"
    )


def pick_block(dim: int, preferred: int, align: int = 8) -> int:
    """Largest block <= preferred that divides dim, honoring TPU alignment
    when the dimension itself is aligned."""
    if dim <= preferred:
        return dim
    b = preferred
    while b >= align and dim % b:
        b -= align
    if b < align or dim % b:
        # fall back to any divisor
        for cand in range(min(preferred, dim), 0, -1):
            if dim % cand == 0:
                return cand
    return b
