"""The architecture-free part of the plain float32 reference of a served
Stable Diffusion request, which every ``arch/<name>.py`` module imports.

It imports nothing of the program: how a request's conditioning and
initial noise derive from its prompt and seed (``request_inputs``), a
quality tier's phase-aware-sampling (PAS) plan (``tier_branches``), the
sampler's timesteps and its PNDM update, and the rounding to float8 that
the control applies (``quantizer``).  The U-Net and the sampler that
drives it live in the architecture's module.
"""
from __future__ import annotations

import hashlib
import math

import numpy as np

FULL, SKETCH, REFINE = 0, 1, 2

# ---------------------------------------------------------------------------
# requests and plans (host side, numpy)
# ---------------------------------------------------------------------------


def request_inputs(prompt: str, seed: int, ctx_len: int, ctx_dim: int, latent: int,
                   channels: int) -> tuple[np.ndarray, np.ndarray]:
    """(conditioning [ctx_len, ctx_dim], noise [latent*latent, channels]) of a
    request: one numpy stream keyed by (seed, the first 8 bytes of the
    prompt's sha256, little-endian), conditioning first, scaled by 0.2."""
    mix = int.from_bytes(hashlib.sha256(prompt.encode()).digest()[:8], "little")
    rng = np.random.default_rng((seed, mix))
    ctx = rng.normal(size=(ctx_len, ctx_dim)).astype(np.float32) * 0.2
    noise = rng.normal(size=(latent * latent, channels)).astype(np.float32)
    return ctx, noise


def tier_branches(tier_spec: dict | None, steps: int) -> list[int]:
    """Branch class per step of a tier's PAS plan (``None``: all FULL).

    A plan {T_sketch, T_complete, T_sparse}: steps before T_complete run
    FULL; until T_sketch every T_sparse-th step (counted from T_complete)
    runs FULL and the rest SKETCH; from T_sketch on, REFINE.
    """
    if tier_spec is None:
        return [FULL] * steps
    num, den = tier_spec["sketch"]
    t_sketch = max(1, (num * steps) // den)
    t_complete = min(t_sketch, max(tier_spec["complete_min"], steps // tier_spec["complete_div"]))
    sparse = tier_spec["sparse"]
    out = []
    for t in range(steps):
        if t < t_complete:
            out.append(FULL)
        elif t < t_sketch:
            out.append(FULL if (t - t_complete + 1) % sparse == 0 else SKETCH)
        else:
            out.append(REFINE)
    return out


def timesteps(steps: int, train_steps: int) -> np.ndarray:
    stride = train_steps // steps
    return (np.arange(steps, dtype=np.int64) * stride)[::-1]


def alphas_cumprod(sched: dict) -> np.ndarray:
    n = sched["num_train_timesteps"]
    if sched["beta_schedule"] == "scaled_linear":
        betas = np.linspace(sched["beta_start"] ** 0.5, sched["beta_end"] ** 0.5, n) ** 2
    else:
        betas = np.linspace(sched["beta_start"], sched["beta_end"], n)
    return np.cumprod(1.0 - betas)


def pndm_update(acp: np.ndarray, ets: list, x: np.ndarray, eps: np.ndarray, t: int,
                t_prev: int) -> np.ndarray:
    """One PLMS step (float64): Adams-Bashforth over the last four eps of
    order min(steps so far, 4), then the deterministic DDIM transfer."""
    ets.insert(0, eps)
    del ets[4:]
    if len(ets) == 1:
        e = ets[0]
    elif len(ets) == 2:
        e = (3 * ets[0] - ets[1]) / 2
    elif len(ets) == 3:
        e = (23 * ets[0] - 16 * ets[1] + 5 * ets[2]) / 12
    else:
        e = (55 * ets[0] - 59 * ets[1] + 37 * ets[2] - 9 * ets[3]) / 24
    ab_t = acp[t]
    ab_p = acp[t_prev] if t_prev >= 0 else 1.0
    x0 = (x - math.sqrt(1 - ab_t) * e) / math.sqrt(ab_t)
    return math.sqrt(ab_p) * x0 + math.sqrt(1 - ab_p) * e


# ---------------------------------------------------------------------------
# the control's precision
# ---------------------------------------------------------------------------


def quantizer(quant: str | None):
    """Identity for ``None``; for ``"fp8"`` a rounding to float8 (e4m3, one
    absmax scale per tensor) and back to float32."""
    import jax.numpy as jnp

    if quant is None:
        return lambda a: a
    if quant != "fp8":
        raise ValueError(f"unknown reference precision {quant!r}")

    def q(a):
        s = jnp.maximum(jnp.max(jnp.abs(a)), 1e-30) / 448.0
        return (a / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s

    return q
