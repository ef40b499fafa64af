"""What the process runs on, and where its compiled programs are kept.

Both are for entry points (``main()`` of the launchers and the chip smoke
test): nothing here runs at import.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

#: the checkout root (``src/repro/runtime/device.py`` is three levels below)
CHECKOUT = Path(__file__).resolve().parents[3]


def device_info() -> dict:
    """Platform, device kind and count of the devices JAX sees, plus each
    device's physical coordinates where the backend reports them (TPU)."""
    devs = jax.devices()
    info = {"platform": devs[0].platform, "kind": devs[0].device_kind, "count": len(devs)}
    if hasattr(devs[0], "coords"):
        info["coords"] = [list(d.coords) for d in devs]
    return info


def configure_compile_cache() -> str:
    """Keep JAX's persistent compilation cache in ``JAX_COMPILATION_CACHE_DIR``
    when it is set (JAX reads it itself), else in ``.jax_cache/`` of the
    checkout: a fixed path, since the path is part of the cache key."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(CHECKOUT / ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    return path
