"""What decides ``correct``: served latents against the plain reference.

Once the window has closed and the program's state is freed, a sample of
the requests the window finished, drawn from the seed, goes through the
float32 reference (the ``Sampler`` of the configuration's
``arch/<name>.py``): the one with the most steps, one of each quality tier
the window served, then more drawn at random up to the traffic file's
``check_sample``.  Each served latent is first matched to the digest its
``done`` event streamed, so the latent compared is the one the client was
told about.  The number compared is the largest relative L2 gap,
``|served - reference| / |reference|``, over the sample; its limit is
``limits/<cell>.json``, set from sound runs and from the reference run at
float8 (the control), as ``PERF.md`` records.
"""
from __future__ import annotations

import hashlib
import json
import time

import numpy as np

from benchmarks.chip import spec


def digest(latent: np.ndarray) -> str:
    return hashlib.sha256(np.ascontiguousarray(latent).tobytes()).hexdigest()[:16]


def load_limits(cell: str) -> dict:
    with open(spec.HERE / "limits" / f"{cell}.json") as f:
        return json.load(f)


def sample(reqs: list, n: int, seed: int) -> list:
    """The requests to compare: the longest, one per tier, then random."""
    rng = np.random.default_rng((seed, 2))
    done = [r for r in reqs if r.status == "done"]
    if not done:
        return []
    order = [done[i] for i in rng.permutation(len(done))]
    picked = [max(order, key=lambda r: r.steps)]
    for tier in sorted({r.tier for r in order}):
        if len(picked) < n and tier not in {r.tier for r in picked}:
            picked.append(next(r for r in order if r.tier == tier))
    for r in order:
        if len(picked) >= n:
            break
        if r not in picked:
            picked.append(r)
    return picked


def params_f32(params):
    import jax
    import jax.numpy as jnp

    return jax.jit(lambda p: jax.tree.map(lambda a: a.astype(jnp.float32), p))(params)


def compare(cell: spec.Cell, rec, latents: dict, params, seed: int, limits: dict, log) -> dict:
    """Compare a sample of ``rec``'s finished requests with the reference;
    returns ``{"correct", "numbers": {name: {"value", "limit"}}, "gaps"}``."""
    picked = sample(rec.window_reqs(), cell.traffic["check_sample"], seed)
    known = [r for r in picked if r.rid in latents]
    t = time.perf_counter()
    sampler = spec.arch(cell.config).Sampler(cell.config, rec.dims, params_f32(params),
                                             batch=cell.traffic["check_sample"])
    wants = sampler.run_many([(r.prompt, r.seed, r.tier, r.steps) for r in known])
    log(f"reference ran {len(known)} requests in {time.perf_counter() - t:.3f} s")
    return judge(cell, picked, latents, wants, limits, log)


def judge(cell: spec.Cell, picked: list, latents: dict, wants: list, limits: dict,
          log) -> dict:
    """The verdict on ``picked`` requests: each latent the program finished
    must carry the digest its ``done`` event streamed, and its relative
    gap to the reference's (``wants``, in the order of the picked requests
    that have a latent) must stay within the limit."""
    mismatched = sum(
        r.rid not in latents or digest(latents[r.rid]) != r.digest for r in picked
    )
    known = [r for r in picked if r.rid in latents]
    gaps = []
    for r, want in zip(known, wants):
        got = np.asarray(latents[r.rid], np.float64)
        gap = float(np.linalg.norm(got - want) / np.linalg.norm(want))
        gap = gap if np.isfinite(gap) else float("inf")
        gaps.append(gap)
        log(f"request {r.k} ({r.tier}, {r.steps} steps): relative gap {gap!r}")
    worst = max(gaps) if gaps else float("inf")
    numbers = {
        "compared": {"value": len(gaps), "limit": cell.traffic["check_sample"]},
        "digest_mismatches": {"value": mismatched, "limit": 0},
        "latent_rel_gap": {"value": worst, "limit": limits["latent_rel_gap"]},
    }
    correct = (
        len(gaps) >= min(cell.traffic["check_sample"], len(picked)) and len(gaps) > 0
        and mismatched == 0 and worst <= limits["latent_rel_gap"]
    )
    return {"correct": bool(correct), "numbers": numbers, "gaps": gaps}
