"""Distributed-runtime demo on 8 emulated devices: the SAME pjit train
step the 256/512-chip dry-run lowers, actually executed on a (4 data x 2
model) host mesh, with FSDP+TP sharded params/optimizer, checkpoint save,
simulated chip failure, elastic re-mesh, and resume.

Run:  PYTHONPATH=src python examples/distributed_smoke.py
"""
import os

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8 " + os.environ.get(
    "XLA_FLAGS", ""
)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
from jax.sharding import AxisType, NamedSharding, PartitionSpec as P  # noqa: E402

from repro.checkpoint.manager import CheckpointManager  # noqa: E402
from repro.common.sharding import set_activation_mesh  # noqa: E402
from repro.configs import get_lm_config  # noqa: E402
from repro.data.pipeline import DataConfig, token_batch  # noqa: E402
from repro.launch.steps import get_adapter, make_train_step, opt_pspecs  # noqa: E402
from repro.optim import AdamWConfig, init_adamw  # noqa: E402
from repro.runtime.fault_tolerance import ElasticPlan  # noqa: E402


def build(mesh, cfg, opt_cfg):
    adapter = get_adapter(cfg)
    pspecs = adapter.pspecs(mesh.shape["model"])
    p_shard = jax.tree.map(lambda s: NamedSharding(mesh, s), pspecs,
                           is_leaf=lambda x: isinstance(x, P))
    o_shard = jax.tree.map(lambda s: NamedSharding(mesh, s), opt_pspecs(pspecs),
                           is_leaf=lambda x: isinstance(x, P))
    with mesh:
        params = jax.jit(adapter.init, out_shardings=p_shard)(jax.random.key(0))
        opt = jax.jit(init_adamw, out_shardings=o_shard)(params)
        step = jax.jit(
            make_train_step(adapter, opt_cfg, remat=False),
            in_shardings=(p_shard, o_shard, NamedSharding(mesh, P(("data",), None))),
            out_shardings=(p_shard, o_shard, NamedSharding(mesh, P())),
            donate_argnums=(0, 1),
        )
    return adapter, params, opt, step


def main():
    cfg = get_lm_config("gemma3-1b", "smoke")
    opt_cfg = AdamWConfig(lr=1e-3, total_steps=20, warmup_steps=2)
    mesh = jax.make_mesh((4, 2), ("data", "model"), axis_types=(AxisType.Auto,) * 2)
    set_activation_mesh(mesh)
    print(f"mesh: {dict(mesh.shape)} over {len(jax.devices())} devices")

    adapter, params, opt, step = build(mesh, cfg, opt_cfg)
    n = sum(x.size for x in jax.tree.leaves(params))
    shard0 = jax.tree.leaves(params)[0]
    print(f"params: {n/1e6:.1f}M; leaf0 sharding: {shard0.sharding.spec}")

    dc = DataConfig(global_batch=8, seq_len=65, vocab_size=cfg.vocab_size)
    cm = CheckpointManager("/tmp/repro_dist_ckpt", keep=2)

    with mesh:
        for s in range(6):
            nb = token_batch(dc, s)
            batch = {"inputs": jnp.asarray(nb["tokens"]), "labels": jnp.asarray(nb["labels"])}
            params, opt, loss = step(params, opt, batch)
            print(f"  step {s}: loss={float(loss):.4f}")
    cm.save(6, {"params": jax.device_get(params), "opt": jax.device_get(opt)})
    print("checkpointed at step 6")

    # --- simulated failure: lose 1 chip -> elastic re-mesh to 3x2 ---------
    plan = ElasticPlan.plan(data=4, model=2, failed=1, global_batch=8)
    print(f"elastic plan after 1 failed chip: data {plan.old_data}->{plan.new_data}, "
          f"batch/shard {plan.batch_per_data_shard}")
    devices = np.array(jax.devices()[: plan.new_data * plan.new_model]).reshape(
        plan.new_data, plan.new_model
    )
    mesh2 = jax.sharding.Mesh(devices, ("data", "model"))
    set_activation_mesh(mesh2)
    adapter, params2, opt2, step2 = build(mesh2, cfg, opt_cfg)
    restored = cm.restore_latest({"params": jax.device_get(params2), "opt": jax.device_get(opt2)})
    start, state = restored
    # re-place the restored host arrays onto the new, smaller mesh
    pspecs = adapter.pspecs(mesh2.shape["model"])
    p_shard = jax.tree.map(lambda s: NamedSharding(mesh2, s), pspecs,
                           is_leaf=lambda x: isinstance(x, P))
    params2 = jax.device_put(state["params"], p_shard)
    opt2 = jax.device_put(state["opt"], jax.tree.map(
        lambda s: NamedSharding(mesh2, s), opt_pspecs(pspecs),
        is_leaf=lambda x: isinstance(x, P)))
    with mesh2:
        for s in range(start, start + 3):
            nb = token_batch(dc, s)
            batch = {"inputs": jnp.asarray(nb["tokens"]), "labels": jnp.asarray(nb["labels"])}
            params2, opt2, loss = step2(params2, opt2, batch)
            print(f"  [re-meshed 3x2] step {s}: loss={float(loss):.4f}")
    print("resumed training on the degraded mesh — elastic restart OK")


if __name__ == "__main__":
    main()
