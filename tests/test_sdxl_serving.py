"""The SDXL-shaped U-Net on the served path, against the plain reference.

A toy configuration with SDXL's structure (``benchmarks/chip/testdata/
toy_xl.json``: three levels, attention at levels 1 and 2 with 2 and 4
heads, Transformer2D sites of depth 2 and 3, a mid site of depth 3, and
the ``text_time`` added conditioning) is served through the request
factory, the engine and its compact lanes, and through the sharded lane
family on two virtual CPU devices, and each finished latent is compared
with the float32 reference of ``benchmarks/chip/arch/sdxl_unet.py``,
which imports nothing of the program.  Two faults planted in the program,
the added embedding dropped and a depth-N site built as N Transformer2Ds,
each fail the comparison.
"""
import json
import os
import subprocess
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from benchmarks.chip import check, spec, system  # noqa: E402
from repro.common.types import DiffusionConfig  # noqa: E402
from repro.models import unet as U  # noqa: E402
from repro.serving import (  # noqa: E402
    DiffusionEngine,
    EngineConfig,
    RequestFactory,
)
from repro.serving.metrics import ServingMetrics  # noqa: E402

CONFIG = spec.load_json("testdata/toy_xl.json")
ARCH = spec.arch(CONFIG)
DIMS = ARCH.dims(CONFIG)
UCFG = ARCH.program_config(CONFIG)
WEIGHT_SEED = 2**31 + 17
#: the relative L2 gap a sound path stays within: the CPU computes the
#: served path in float32 on the bfloat16 weights, the reference to float32
#: accuracy, so what is left is float32 rounding over 8 steps (2.5e-6 seen)
TOLERANCE = 1e-4
#: one request per tier kind: all FULL, FULL/SKETCH/REFINE interleaved,
#: REFINE-heavy, and a short balanced one
REQUESTS = [("xl exact", 2**31 + 3, "exact", 8), ("xl high", 4, "high", 8),
            ("xl draft", 5, "draft", 7), ("xl balanced", 6, "balanced", 6)]


def _dcfg() -> DiffusionConfig:
    sched, s = CONFIG["scheduler"], CONFIG["serving"]
    return DiffusionConfig(
        timesteps_train=sched["num_train_timesteps"], timesteps_sample=s["max_steps"],
        scheduler="pndm", beta_start=sched["beta_start"], beta_end=sched["beta_end"],
        beta_schedule=sched["beta_schedule"], guidance_scale=s["guidance_scale"],
    )


def _engine_config(n_shards: int = 1) -> EngineConfig:
    s = CONFIG["serving"]
    return EngineConfig(n_lanes=s["lanes"], max_steps=s["max_steps"], l_sketch=s["l_sketch"],
                        l_refine=s["l_refine"], decode_images=False, n_shards=n_shards)


def serve(params, n_shards: int = 1) -> dict[str, np.ndarray]:
    """Final latents of ``REQUESTS`` through the request factory and the
    engine (the sharded one for ``n_shards > 1``), by prompt."""
    from repro.serving.engine import make_serving_engine

    cfg = _engine_config(n_shards)
    engine = make_serving_engine(UCFG, _dcfg(), params, None, cfg)
    factory = RequestFactory(UCFG, _dcfg(), cfg)
    reqs = [factory.make({"prompt": p, "seed": s, "timesteps": n, "quality": tier})
            for p, s, tier, n in REQUESTS]
    done, summary = engine.run(reqs)
    by_rid = {d.rid: d.latent for d in done}
    assert summary["tf_block_rows"] > 0
    return {r[0]: by_rid[q.rid] for r, q in zip(REQUESTS, reqs)}


@pytest.fixture(scope="module")
def params():
    return system.make_weights(UCFG, WEIGHT_SEED)


@pytest.fixture(scope="module")
def reference(params):
    sampler = ARCH.Sampler(CONFIG, DIMS, check.params_f32(params), batch=len(REQUESTS))
    return {r[0]: x for r, x in zip(REQUESTS, sampler.run_many(REQUESTS))}


def gaps(got: dict, want: dict) -> dict[str, float]:
    return {k: float(np.linalg.norm(got[k] - want[k]) / np.linalg.norm(want[k])) for k in want}


def test_toy_config_has_the_sdxl_structure():
    assert UCFG.level_heads == (1, 2, 4) and UCFG.level_depths == (1, 2, 3)
    assert UCFG.attn_levels == (1, 2) and UCFG.mid_tf_depth == 3
    assert UCFG.added_dim == DIMS.pooled_dim + 6
    tree = jax.eval_shape(lambda k: U.init_unet(k, UCFG), jax.random.key(0))
    site = tree["mid"]["tf"]
    assert len(site) == 3
    assert {"gn", "proj_in"} <= set(site[0]) and "proj_out" not in site[0]
    assert not {"gn", "proj_in", "proj_out"} & set(site[1])
    assert "proj_out" in site[2] and "gn" not in site[2]
    assert tree["add_embedding"]["w1"].shape == (DIMS.pooled_dim + 6 * DIMS.time_id_dim,
                                                 UCFG.time_dim)


def test_engine_matches_the_reference(params, reference):
    got = gaps(serve(params), reference)
    assert max(got.values()) < TOLERANCE, got


def test_engine_counts_transformer_block_rows(params, monkeypatch):
    """``tf_block_rows``: every dispatch's computed rows times the blocks its
    class's pass runs (18 FULL, 2 SKETCH, 0 REFINE in the toy)."""
    assert (U.tf_blocks(UCFG), U.tf_blocks(UCFG, 3), U.tf_blocks(UCFG, 4)) == (18, 2, 0)
    cfg = _engine_config()
    engine = DiffusionEngine(UCFG, _dcfg(), params, None, cfg)
    factory = RequestFactory(UCFG, _dcfg(), cfg)
    reqs = [factory.make({"prompt": p, "seed": s, "timesteps": n, "quality": tier})
            for p, s, tier, n in REQUESTS]
    seen = []
    real = ServingMetrics.record_step

    def spy(self, *a, **k):
        seen.append((k["n_full"], k["n_sketch"], k["unet_rows"], k["tf_block_rows"]))
        return real(self, *a, **k)

    monkeypatch.setattr(ServingMetrics, "record_step", spy)
    _, summary = engine.run(reqs)
    assert seen
    for n_full, n_sketch, rows, blocks in seen:
        per_row = 18 if n_full else 2 if n_sketch else 0
        assert blocks == rows * per_row
    assert summary["tf_block_rows"] == sum(b for *_, b in seen)


def test_request_without_added_conditioning_is_refused(params):
    cfg = _engine_config()
    engine = DiffusionEngine(UCFG, _dcfg(), params, None, cfg)
    req = RequestFactory(UCFG, _dcfg(), cfg).make({"prompt": "p", "seed": 1, "timesteps": 4})
    req.added = None
    with pytest.raises(ValueError, match="added conditioning"):
        engine.submit(req)


def _n_transformer2ds(blocks, x, ctx, hw, n_heads, groups, backend=None):
    """A depth-N site built as N Transformer2Ds: each block behind its own
    norm, projections and residual."""
    first, last = blocks[0], blocks[-1]
    for p in blocks:
        x = U.apply_site([dict(p, gn=first["gn"], proj_in=first["proj_in"],
                               proj_out=last["proj_out"])], x, ctx, hw, n_heads, groups,
                         backend=backend)
    return x


FAULTS = {
    "added_embedding_dropped": (
        "added_embedding",
        lambda cfg, p, added: jnp.zeros((added.shape[0], cfg.time_dim), added.dtype)),
    "site_as_n_transformer2ds": ("apply_site", _n_transformer2ds),
}


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_planted_fault_fails_the_comparison(params, reference, monkeypatch, fault):
    name, fake = FAULTS[fault]
    real = getattr(U, name)
    if name == "apply_site":
        monkeypatch.setattr(U, name, lambda blocks, *a, **k: fake(blocks, *a, **k)
                            if len(blocks) > 1 else real(blocks, *a, **k))
    else:
        monkeypatch.setattr(U, name, fake)
    got = gaps(serve(params), reference)
    assert max(got.values()) > 100 * TOLERANCE, got


_SHARDED = r"""
import sys
import numpy as np
sys.path[:0] = [{root!r}]
import tests.test_sdxl_serving as T
from benchmarks.chip import system
import jax
assert len(jax.devices()) == 2, jax.devices()
np.savez({out!r}, **T.serve(system.make_weights(T.UCFG, T.WEIGHT_SEED), n_shards=2))
"""


def test_sharded_engine_on_two_devices_matches_the_reference(reference, tmp_path):
    """The sharded lane family, two shards on two virtual CPU devices, in a
    process of its own (the device count is fixed when JAX starts)."""
    out = tmp_path / "latents.npz"
    flags = [f for f in os.environ.get("XLA_FLAGS", "").split()
             if not f.startswith("--xla_force_host_platform_device_count")]
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS=" ".join(flags + ["--xla_force_host_platform_device_count=2"]),
               PYTHONPATH=os.pathsep.join([str(ROOT / "src"), str(ROOT),
                                           os.environ.get("PYTHONPATH", "")]))
    proc = subprocess.run([sys.executable, "-c", _SHARDED.format(root=str(ROOT), out=str(out))],
                          env=env, capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    got = gaps(dict(np.load(out)), reference)
    assert max(got.values()) < TOLERANCE, json.dumps(got)
