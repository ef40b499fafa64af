"""Norms, MLPs and MoE layers shared by every architecture.

All parameters live in plain nested dicts; ``init_*`` builds them,
``apply_*`` consumes them.  Dtype policy: params are created in
``cfg.dtype`` (bf16 for LM archs); norm statistics and router math are
computed in fp32 (matching production practice and the paper's fp16-with-
fp32-characteristics VPU).
"""
from __future__ import annotations

import math
from typing import Any

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.common import sharding as _sh
from repro.common.sharding import constrain_act
from repro.common.types import LMConfig, MoESpec

Params = dict[str, Any]


def _dense_init(key, shape, dtype, scale: float | None = None):
    fan_in = shape[-2] if len(shape) >= 2 else shape[-1]
    std = scale if scale is not None else 1.0 / math.sqrt(fan_in)
    return (jax.random.normal(key, shape, jnp.float32) * std).astype(dtype)


# ---------------------------------------------------------------------------
# Norms — layernorm uses the paper's Eq. (4) one-pass sum/square-sum form.
# ---------------------------------------------------------------------------


def init_norm(cfg: LMConfig, dim: int) -> Params:
    p = {"scale": jnp.ones((dim,), jnp.float32)}
    if cfg.norm == "layernorm":
        p["bias"] = jnp.zeros((dim,), jnp.float32)
    return p


def apply_norm(cfg: LMConfig, p: Params, x: jax.Array, eps: float = 1e-6) -> jax.Array:
    xf = x.astype(jnp.float32)
    if cfg.norm == "layernorm":
        # One-pass statistics (paper Eq. 4): mean and E[x^2] in a single
        # traversal; var = E[x^2] - mean^2.
        s = jnp.mean(xf, axis=-1, keepdims=True)
        sq = jnp.mean(xf * xf, axis=-1, keepdims=True)
        var = jnp.maximum(sq - s * s, 0.0)
        y = (xf - s) * jax.lax.rsqrt(var + eps)
        y = y * p["scale"] + p["bias"]
    else:  # rmsnorm
        ms = jnp.mean(xf * xf, axis=-1, keepdims=True)
        y = xf * jax.lax.rsqrt(ms + eps) * p["scale"]
    return y.astype(x.dtype)


def act_fn(name: str):
    if name == "silu":
        return jax.nn.silu
    if name == "gelu":
        # paper Sec. IV-D: the official sigmoid form of GELU
        return lambda x: x * jax.nn.sigmoid(1.702 * x)
    raise ValueError(name)


# ---------------------------------------------------------------------------
# Dense MLP (optionally gated)
# ---------------------------------------------------------------------------


def init_mlp(key, cfg: LMConfig) -> Params:
    dtype = jnp.dtype(cfg.dtype)
    d, f = cfg.d_model, cfg.d_ff
    ks = jax.random.split(key, 3)
    p = {
        "w_in": _dense_init(ks[0], (d, f), dtype),
        "w_out": _dense_init(ks[1], (f, d), dtype),
    }
    if cfg.glu:
        p["w_gate"] = _dense_init(ks[2], (d, f), dtype)
    return p


def apply_mlp(cfg: LMConfig, p: Params, x: jax.Array) -> jax.Array:
    act = act_fn(cfg.act)
    h = x @ p["w_in"]
    if cfg.glu:
        h = act(x @ p["w_gate"]) * h
    else:
        h = act(h)
    return h @ p["w_out"]


# ---------------------------------------------------------------------------
# MoE with scatter-based (sort-free ragged) capacity dispatch.
#
# We deliberately avoid the dense [tokens, E, C] one-hot dispatch einsum of
# Mesh-TF/Switch: its FLOP count is quadratic in tokens-per-group.  Instead
# each (token, k) routing pair computes a destination slot
# ``expert * C + position_in_expert`` and tokens are scattered/gathered.
# FLOPs are then only the expert matmuls (capacity_factor padding aside).
# ---------------------------------------------------------------------------


def moe_capacity(spec: MoESpec, n_tokens: int) -> int:
    cap = int(math.ceil(n_tokens * spec.top_k * spec.capacity_factor / spec.num_experts))
    return max(8, -(-cap // 8) * 8)  # round up to 8 for TPU lane alignment


def init_moe(key, cfg: LMConfig) -> Params:
    spec = cfg.moe
    assert spec is not None
    dtype = jnp.dtype(cfg.dtype)
    d, f, e = cfg.d_model, spec.d_expert, spec.num_experts
    ks = jax.random.split(key, 4)
    return {
        "router": _dense_init(ks[0], (d, e), jnp.float32),
        "w_in": _dense_init(ks[1], (e, d, f), dtype),
        "w_gate": _dense_init(ks[2], (e, d, f), dtype),
        "w_out": _dense_init(ks[3], (e, f, d), dtype),
    }


def _moe_one_group(cfg: LMConfig, p: Params, xt: jax.Array, cap: int) -> tuple[jax.Array, jax.Array]:
    """Dispatch/compute/combine for one token group. xt: [T_g, d]."""
    spec = cfg.moe
    assert spec is not None
    t, d = xt.shape
    e, k = spec.num_experts, spec.top_k

    logits = xt.astype(jnp.float32) @ p["router"]  # [T, E]
    probs = jax.nn.softmax(logits, axis=-1)
    top_p, top_i = jax.lax.top_k(probs, k)  # [T, k]
    top_p = top_p / jnp.sum(top_p, axis=-1, keepdims=True)  # renormalize

    # load-balancing auxiliary loss (Switch-style)
    me = jnp.mean(probs, axis=0)  # [E]
    fe = jnp.mean(jax.nn.one_hot(top_i, e, dtype=jnp.float32), axis=(0, 1))
    aux = e * jnp.sum(me * fe)

    # position of each routing pair within its expert (token-major priority)
    flat_e = top_i.reshape(-1)  # [T*k]
    onehot = jax.nn.one_hot(flat_e, e, dtype=jnp.int32)  # [T*k, E]
    pos = jnp.cumsum(onehot, axis=0) - onehot
    pair_pos = jnp.take_along_axis(pos, flat_e[:, None], axis=1)[:, 0]  # [T*k]
    keep = pair_pos < cap
    dest = jnp.where(keep, flat_e * cap + pair_pos, e * cap)  # overflow slot

    # scatter tokens into the padded [E*C, d] expert buffer
    src = jnp.repeat(jnp.arange(t), k)
    buf = jnp.zeros((e * cap + 1, d), xt.dtype).at[dest].set(xt[src])
    buf = buf[: e * cap].reshape(e, cap, d)

    # expert computation (gated MLP per expert)
    act = act_fn(cfg.act)
    h = jnp.einsum("ecd,edf->ecf", buf, p["w_in"])
    g = jnp.einsum("ecd,edf->ecf", buf, p["w_gate"])
    out_buf = jnp.einsum("ecf,efd->ecd", act(g) * h, p["w_out"])  # [E, C, d]

    # gather back and combine with gate probabilities
    flat_out = out_buf.reshape(e * cap, d)
    gathered = jnp.where(keep[:, None], flat_out[jnp.minimum(dest, e * cap - 1)], 0.0)
    weighted = gathered * top_p.reshape(-1, 1).astype(xt.dtype)
    out = jnp.zeros((t, d), xt.dtype).at[src].add(weighted)
    return out, aux


def apply_moe(cfg: LMConfig, p: Params, x: jax.Array) -> tuple[jax.Array, jax.Array]:
    """Returns (output, aux_loss). x: [B, S, d_model].

    Tokens are dispatched in *groups* (one per batch row, GSPMD-style):
    every dispatch tensor keeps the leading batch axis, so data-parallel
    sharding propagates through the scatter/gather and no device ever
    materializes the global token set.  Capacity is per-group.
    """
    spec = cfg.moe
    assert spec is not None
    b, s, d = x.shape
    cap = min(moe_capacity(spec, s), s)
    grouped = jax.vmap(lambda xg: _moe_one_group(cfg, p, xg, cap))

    # GSPMD's scatter partitioner cannot shard the dispatch (it replicates
    # the expert buffers — observed as full-batch fp32 [E, B, C, f] temps,
    # ~10 GiB each).  When a mesh is registered, sidestep propagation
    # entirely with shard_map: each data shard dispatches its own rows to
    # f-sharded expert weights; the f-contraction is combined with a psum
    # over the model axis.  Falls back to plain vmap off-mesh (CPU tests).
    mesh = _sh.get_activation_mesh()
    ms = mesh.shape.get("model", 1) if mesh is not None else 1
    ba = tuple(a for a in ("pod", "data") if a in mesh.axis_names) if mesh else ()
    dp = 1
    for a in ba:
        dp *= mesh.shape[a]
    f_ok = spec.d_expert % ms == 0
    if mesh is None or b % dp or b < dp or not f_ok:
        x = constrain_act(x)
        out, aux = grouped(x)
        return constrain_act(out), jnp.mean(aux)


    m_ax = "model" if ms > 1 else None

    def local_fn(xl, router, w_in, w_gate, w_out):
        pl = {"router": router, "w_in": w_in, "w_gate": w_gate, "w_out": w_out}
        out, aux = jax.vmap(lambda xg: _moe_one_group(cfg, pl, xg, cap))(xl)
        if m_ax:
            out = jax.lax.psum(out, m_ax)  # combine f-shard partial sums
        aux = jax.lax.pmean(jnp.mean(aux), ba)
        return out, aux

    out, aux = jax.shard_map(
        local_fn,
        mesh=mesh,
        in_specs=(
            P(ba, None, None),
            P(None, None),  # router replicated
            P(None, None, m_ax),  # w_in: f sharded over model
            P(None, None, m_ax),  # w_gate
            P(None, m_ax, None),  # w_out: contraction dim sharded
        ),
        out_specs=(P(ba, None, None), P()),
        check_vma=False,
    )(x, p["router"], p["w_in"], p["w_gate"], p["w_out"])
    return out, aux
