"""Record sd_v14's served parameter tree and the digests of its lowered
serving programs (``tests/golden/sd_v14_programs.json``), which
``tests/test_sd14_unchanged.py`` holds the program to.

Run only after a JAX upgrade changes the lowered text::

    PYTHONPATH=src JAX_PLATFORMS=cpu python tools/regen_sd14_program_golden.py

Nothing is compiled or allocated at sd_v14's size: the tree comes from
``jax.eval_shape`` and the programs are lowered on abstract arguments.
"""
from __future__ import annotations

import hashlib
import json
import os
import sys

import jax
import jax.numpy as jnp

from repro.common.types import DiffusionConfig
from repro.configs import get_unet_config
from repro.models import unet as U
from repro.serving import lanes as LN

HERE = os.path.dirname(os.path.abspath(__file__))
GOLDEN = os.path.join(HERE, "..", "tests", "golden", "sd_v14_programs.json")
#: the served sd_v14 geometry: 4 lanes, 50 steps, SKETCH 3 and REFINE 2
LANES, MAX_STEPS, L_SKETCH, L_REFINE = 4, 50, 3, 2


def param_tree(cfg) -> list[list]:
    """``[path, shape, dtype]`` of every leaf, in flatten order."""
    shapes = jax.eval_shape(lambda k: U.init_unet(k, cfg), jax.random.key(0))
    return [[jax.tree_util.keystr(path), list(leaf.shape), str(leaf.dtype)]
            for path, leaf in jax.tree_util.tree_flatten_with_path(shapes)[0]]


def _abstract(tree):
    return jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), tree)


def program_digests(cfg) -> dict[str, str]:
    """sha256 of the lowered text of each serving program sd_v14 runs: the
    compact micro-step, lane admission, and the sharded family's micro-step
    and admission on a one-device mesh."""
    from repro.common.sharding import lane_mesh

    dcfg = DiffusionConfig(timesteps_sample=MAX_STEPS, scheduler="pndm", guidance_scale=7.5)
    n_up = U.n_up_steps(cfg)
    e_sk, e_rf = n_up - L_SKETCH, n_up - L_REFINE
    params = _abstract(jax.eval_shape(lambda k: U.init_unet(k, cfg), jax.random.key(0)))
    state = _abstract(jax.eval_shape(
        lambda: LN.init_lanes(cfg, LANES, MAX_STEPS, e_sk, e_rf)))
    k = LN.compact_capacity(LANES)
    i32, f32 = jnp.int32, jnp.float32
    s = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt)  # noqa: E731
    L, c = cfg.latent_size**2, cfg.in_channels
    steps = s((MAX_STEPS,), i32)
    out = {}
    micro = LN.make_micro_step(cfg, dcfg, e_sk, e_rf)
    out["micro_step"] = micro.lower(state, params, s((), i32), s((k,), i32),
                                    s((k,), jnp.bool_))
    admit_args = (s((), i32), s((L, c), f32), s((cfg.ctx_len, cfg.ctx_dim), f32), steps,
                  steps, steps, s((), i32), s((MAX_STEPS,), f32), s((L, 1), f32),
                  s((L, c), f32), s((L, c), f32))
    out["admit"] = jax.jit(LN.admit, donate_argnums=(0,)).lower(state, *admit_args)
    mesh = lane_mesh(1)
    sstate = _abstract(jax.eval_shape(
        lambda: LN.init_sharded_lanes(cfg, LANES, MAX_STEPS, e_sk, e_rf, mesh)))
    smicro = LN.make_sharded_micro_step(cfg, dcfg, e_sk, e_rf, mesh)
    out["sharded_micro_step"] = smicro.lower(sstate, params, s((1,), i32),
                                             s((LANES,), jnp.bool_))
    out["sharded_admit"] = LN.make_sharded_admit(mesh).lower(sstate, *admit_args)
    return {name: hashlib.sha256(low.as_text().encode()).hexdigest()
            for name, low in out.items()}


def record() -> dict:
    cfg = get_unet_config("sd_v14")
    return {"jax_version": jax.__version__, "param_tree": param_tree(cfg),
            "programs": program_digests(cfg)}


if __name__ == "__main__":
    rec = record()
    with open(GOLDEN, "w") as f:  # one leaf per line
        leaves = ",\n  ".join(json.dumps(leaf) for leaf in rec["param_tree"])
        f.write(f'{{"jax_version": {json.dumps(rec["jax_version"])},\n'
                f' "programs": {json.dumps(rec["programs"], indent=2)},\n'
                f' "param_tree": [\n  {leaves}\n ]}}\n')
    print(json.dumps(rec["programs"], indent=1), file=sys.stderr)
