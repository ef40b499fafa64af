"""Integration tests: training drivers, serving, examples-level flows."""
import argparse
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SRC = os.path.join(REPO, "src")


def _ns(**kw):
    return argparse.Namespace(**kw)


@pytest.mark.slow
def test_unet_training_reduces_loss(tmp_path):
    from repro.launch.train import train_unet

    args = _ns(unet="sd_toy", steps=30, batch=4, lr=3e-4, seed=0,
               ckpt_dir=str(tmp_path), save_every=10, log_every=50,
               compress_grads=False)
    res = train_unet(args)
    assert res["final_loss"] < res["first_loss"]
    # checkpoints were written
    assert any(d.startswith("step_") for d in os.listdir(tmp_path))


@pytest.mark.slow
def test_unet_training_resumes(tmp_path):
    from repro.checkpoint.manager import CheckpointManager
    from repro.launch.train import train_unet

    args = _ns(unet="sd_toy", steps=10, batch=2, lr=3e-4, seed=0,
               ckpt_dir=str(tmp_path), save_every=5, log_every=50,
               compress_grads=False)
    train_unet(args)
    cm = CheckpointManager(str(tmp_path))
    assert cm.list_steps()[-1] == 10
    # "restart": running again resumes from step 10 and is a no-op
    args2 = _ns(**{**vars(args), "steps": 12})
    res = train_unet(args2)
    assert np.isfinite(res["final_loss"])


@pytest.mark.slow
def test_unet_training_with_grad_compression(tmp_path):
    from repro.launch.train import train_unet

    args = _ns(unet="sd_toy", steps=12, batch=2, lr=3e-4, seed=0,
               ckpt_dir=None, save_every=100, log_every=50,
               compress_grads=True)
    res = train_unet(args)
    assert res["final_loss"] < res["first_loss"] * 1.1  # still trains


@pytest.mark.slow
def test_lm_training_smoke():
    from repro.launch.train import train_lm

    args = _ns(arch="gemma3-1b", variant="smoke", steps=8, batch=2, seq=32,
               lr=1e-3, seed=0, ckpt_dir=None, save_every=100, log_every=100,
               no_sigterm=True)
    res = train_lm(args)
    assert np.isfinite(res["final_loss"])
    assert res["final_loss"] < res["first_loss"]


def test_serve_pack_batches():
    from repro.launch.serve import Request, pack_batches

    reqs = [Request(rid=i, payload=i) for i in range(7)]
    groups = pack_batches(reqs, 3)
    assert [len(g) for g in groups] == [3, 3, 1]
    assert [r.rid for g in groups for r in g] == list(range(7))


@pytest.mark.slow
def test_serve_diffusion_end_to_end():
    from repro.launch.serve import serve_diffusion

    args = _ns(unet="sd_toy", requests=2, batch=2, timesteps=6, pas=True, seed=0)
    stats = serve_diffusion(args)
    assert stats["requests"] == 2
    assert stats["engine"] == "continuous"
    assert stats["throughput_req_s"] > 0
    assert len(stats["image_shape"]) == 2  # [H*W, C] pixels

    args = _ns(
        unet="sd_toy", requests=2, batch=2, timesteps=6, pas=True, seed=0,
        engine="static",
    )
    stats = serve_diffusion(args)
    assert stats["requests"] == 2
    assert stats["engine"] == "static"
    assert stats["throughput_req_s"] > 0


@pytest.mark.slow
def test_distributed_train_step_8dev_subprocess():
    """The production pjit train step actually executes on an emulated
    4x2 mesh (separate process so the forced device count cannot leak)."""
    code = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, jax.numpy as jnp
from jax.sharding import AxisType, NamedSharding, PartitionSpec as P
from repro.common.sharding import set_activation_mesh
from repro.configs import get_lm_config
from repro.launch.steps import get_adapter, make_train_step, opt_pspecs
from repro.optim import AdamWConfig, init_adamw

cfg = get_lm_config("yi-6b", "smoke")
mesh = jax.make_mesh((4, 2), ("data", "model"), axis_types=(AxisType.Auto,) * 2)
set_activation_mesh(mesh)
ad = get_adapter(cfg)
pspecs = ad.pspecs(2)
sh = lambda t: jax.tree.map(lambda s: NamedSharding(mesh, s), t, is_leaf=lambda x: isinstance(x, P))
with mesh:
    params = jax.jit(ad.init, out_shardings=sh(pspecs))(jax.random.key(0))
    opt = jax.jit(init_adamw, out_shardings=sh(opt_pspecs(pspecs)))(params)
    step = jax.jit(make_train_step(ad, AdamWConfig(total_steps=4, warmup_steps=1), remat=True),
                   donate_argnums=(0, 1))
    batch = {"inputs": jnp.zeros((8, 32), jnp.int32),
             "labels": jnp.zeros((8, 32), jnp.int32)}
    for _ in range(2):
        params, opt, loss = step(params, opt, batch)
    assert jnp.isfinite(loss), loss
print("DIST_OK", float(loss))
"""
    env = dict(os.environ, PYTHONPATH=SRC)
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=900)
    assert "DIST_OK" in out.stdout, out.stderr[-2000:]


@pytest.mark.slow
def test_dryrun_smoke_cell_subprocess():
    """One dry-run cell with the smoke config end-to-end (fast compile)."""
    env = dict(os.environ, PYTHONPATH=SRC)
    out = subprocess.run(
        [sys.executable, "-m", "repro.launch.dryrun", "--arch", "gemma3-1b",
         "--cell", "train_4k", "--variant", "smoke", "--skip-unrolled"],
        env=env, capture_output=True, text=True, timeout=900, cwd=REPO,
    )
    assert "1/1 cells passed" in out.stdout, out.stdout + out.stderr[-2000:]
