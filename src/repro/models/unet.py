"""StableDiff U-Net in JAX with block-granular partial execution for PAS.

Topology follows SD v1.x/v2.x/XL (configurable via ``UNetConfig``):
``conv_in`` + per-level [ResBlock(+Transformer2D)] stacks with downsamples,
a middle block, and an up path consuming skip connections in reverse.  An
attention site is one Transformer2D: a group norm and ``proj_in``, the
level's depth of BasicTransformerBlocks (self-attention, cross-attention,
GEGLU, each behind a layer norm), ``proj_out`` and the residual; heads and
depth are per level (SDXL: heads 5/10/20 of 64, depths 1/2/10).  SDXL's
``text_time`` added conditioning (a pooled text embedding and six
embedded size/crop ids through a 2-layer MLP) is added to the time
embedding.

The paper's Fig. 3/5 block indexing: the down path produces ``n_skip``
skip tensors (12 for SD v1.4); partial execution with budget ``l`` runs
down-blocks 1..l, enters the up path at the cached main-branch feature of
up-step ``n_skip - l``, and runs the remaining up-steps — exactly the
paper's "retain the top blocks, reuse the sketch" scheme (DeepCache-style
caching, but phase-aware scheduling decides *when*).

Activations use layout [B, H*W, C] throughout (the paper's address-centric
``(L, C)`` storage format, Sec. IV-B): convolutions are executed as
Uni-conv — K*K shifted 1x1 matmuls accumulated at remapped addresses —
which is also what the Pallas kernel implements on TPU.
"""
from __future__ import annotations

import math
from typing import Any, Sequence

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.common.types import UNetConfig
from repro.models.layers import _dense_init

Params = dict[str, Any]


def _resolve_backend(backend):
    """Lazy import: ``repro.models.backend`` builds its XLA backend from the
    primitives defined below, so the dispatch module cannot be imported at
    module load without a cycle."""
    from repro.models.backend import resolve_backend

    return resolve_backend(backend)


# ---------------------------------------------------------------------------
# Uni-conv: address-centric convolution on the (L, C) layout  (paper Sec. IV)
# ---------------------------------------------------------------------------


def uniconv_apply(
    w: jax.Array,  # [F=R*S, Cin, Cout]
    b: jax.Array | None,  # [Cout]
    x: jax.Array,  # [B, L=H*W, Cin]
    hw: tuple[int, int],
    ksize: int,
    stride: int = 1,
) -> jax.Array:
    """K x K conv decomposed into F 1x1 matmuls with output-address remap.

    This is the pure-XLA expression of the paper's address-centric dataflow;
    ``repro.kernels.uniconv`` is the Pallas version with explicit VMEM
    tiling.  Edge flags (the paper's address detector) become masks derived
    from the row/col decomposition of ``l``.
    """
    h, wdim = hw
    bsz, l, cin = x.shape
    assert l == h * wdim, (l, h, wdim)
    r = ksize
    pad = (ksize - 1) // 2
    out = None
    rows = jnp.arange(h)
    cols = jnp.arange(wdim)
    # grid of kernel offsets, e.g. 9 positions for 3x3
    for f in range(r * r):
        oy, ox = f // r - pad, f % r - pad  # kernel offset relative to center
        part = x @ w[f]  # [B, L, Cout] — plain matmul (the 1x1 kernel)
        part2d = part.reshape(bsz, h, wdim, -1)
        # address remap: contribution of input l lands at output l - (oy, ox)
        sy, sx = -oy, -ox
        shifted = jnp.roll(part2d, shift=(sy, sx), axis=(1, 2))
        # edge flags (the paper's address detector): mask wrapped lanes
        rmask = (rows >= sy) & (rows < h + sy)
        cmask = (cols >= sx) & (cols < wdim + sx)
        mask = rmask[:, None] & cmask[None, :]
        shifted = jnp.where(mask[None, :, :, None], shifted, 0.0)
        out = shifted if out is None else out + shifted
    assert out is not None
    if stride > 1:
        out = out[:, ::stride, ::stride, :]
        h, wdim = out.shape[1], out.shape[2]
    out = out.reshape(bsz, h * wdim, -1)
    if b is not None:
        out = out + b
    return out


def init_conv(key, ksize: int, cin: int, cout: int, dtype) -> Params:
    std = 1.0 / math.sqrt(cin * ksize * ksize)
    w = jax.random.normal(key, (ksize * ksize, cin, cout), jnp.float32) * std
    return {"w": w.astype(dtype), "b": jnp.zeros((cout,), dtype)}


# ---------------------------------------------------------------------------
# primitive layers
# ---------------------------------------------------------------------------


def group_norm(x: jax.Array, p: Params, groups: int, eps: float = 1e-5) -> jax.Array:
    """x: [B, L, C] — one-pass sum/sq-sum statistics (paper Eq. 4)."""
    bsz, l, c = x.shape
    xg = x.astype(jnp.float32).reshape(bsz, l, groups, c // groups)
    s = jnp.mean(xg, axis=(1, 3), keepdims=True)
    sq = jnp.mean(xg * xg, axis=(1, 3), keepdims=True)
    var = jnp.maximum(sq - s * s, 0.0)
    y = (xg - s) * jax.lax.rsqrt(var + eps)
    y = y.reshape(bsz, l, c) * p["scale"] + p["bias"]
    return y.astype(x.dtype)


def init_gn(c: int) -> Params:
    return {"scale": jnp.ones((c,), jnp.float32), "bias": jnp.zeros((c,), jnp.float32)}


def layer_norm(x: jax.Array, p: Params, eps: float = 1e-5) -> jax.Array:
    xf = x.astype(jnp.float32)
    s = jnp.mean(xf, axis=-1, keepdims=True)
    sq = jnp.mean(xf * xf, axis=-1, keepdims=True)
    y = (xf - s) * jax.lax.rsqrt(jnp.maximum(sq - s * s, 0.0) + eps)
    return (y * p["scale"] + p["bias"]).astype(x.dtype)


def timestep_embedding(t: jax.Array, dim: int) -> jax.Array:
    half = dim // 2
    freqs = jnp.exp(-math.log(10000.0) * jnp.arange(half, dtype=jnp.float32) / half)
    ang = t.astype(jnp.float32)[:, None] * freqs[None]
    return jnp.concatenate([jnp.cos(ang), jnp.sin(ang)], axis=-1)


# ---------------------------------------------------------------------------
# ResBlock
# ---------------------------------------------------------------------------


def init_res(key, cin: int, cout: int, tdim: int, groups: int, dtype) -> Params:
    ks = jax.random.split(key, 4)
    p = {
        "gn1": init_gn(cin),
        "conv1": init_conv(ks[0], 3, cin, cout, dtype),
        "t_proj": {
            "w": _dense_init(ks[1], (tdim, cout), dtype),
            "b": jnp.zeros((cout,), dtype),
        },
        "gn2": init_gn(cout),
        "conv2": init_conv(ks[2], 3, cout, cout, dtype),
    }
    if cin != cout:
        p["skip"] = init_conv(ks[3], 1, cin, cout, dtype)
    return p


def apply_res(p: Params, x: jax.Array, temb: jax.Array, hw, groups: int, backend=None) -> jax.Array:
    bk = _resolve_backend(backend)
    h = bk.group_norm(x, p["gn1"], groups, silu=True)
    h = bk.conv(p["conv1"]["w"], p["conv1"]["b"], h, hw, 3)
    h = h + (jax.nn.silu(temb) @ p["t_proj"]["w"] + p["t_proj"]["b"])[:, None, :]
    h = bk.group_norm(h, p["gn2"], groups, silu=True)
    h = bk.conv(p["conv2"]["w"], p["conv2"]["b"], h, hw, 3)
    if "skip" in p:
        x = bk.conv(p["skip"]["w"], p["skip"]["b"], x, hw, 1)
    return x + h


# ---------------------------------------------------------------------------
# Transformer block (self-attn + cross-attn + GEGLU)
# ---------------------------------------------------------------------------


def init_tf(key, c: int, n_heads: int, ctx_dim: int, dtype) -> Params:
    """A depth-1 Transformer2D: its norm and projections with one block."""
    ks = jax.random.split(key, 12)
    return {
        "gn": init_gn(c),
        "proj_in": init_conv(ks[0], 1, c, c, dtype),
        "ln1": {"scale": jnp.ones((c,), jnp.float32), "bias": jnp.zeros((c,), jnp.float32)},
        "self_q": _dense_init(ks[1], (c, c), dtype),
        "self_k": _dense_init(ks[2], (c, c), dtype),
        "self_v": _dense_init(ks[3], (c, c), dtype),
        "self_o": _dense_init(ks[4], (c, c), dtype),
        "ln2": {"scale": jnp.ones((c,), jnp.float32), "bias": jnp.zeros((c,), jnp.float32)},
        "cross_q": _dense_init(ks[5], (c, c), dtype),
        "cross_k": _dense_init(ks[6], (ctx_dim, c), dtype),
        "cross_v": _dense_init(ks[7], (ctx_dim, c), dtype),
        "cross_o": _dense_init(ks[8], (c, c), dtype),
        "ln3": {"scale": jnp.ones((c,), jnp.float32), "bias": jnp.zeros((c,), jnp.float32)},
        "ff_in": _dense_init(ks[9], (c, 8 * c), dtype),  # GEGLU: 2 * 4c
        "ff_out": _dense_init(ks[10], (4 * c, c), dtype),
        "proj_out": init_conv(ks[11], 1, c, c, dtype),
    }


def init_site(key, c: int, n_heads: int, ctx_dim: int, depth: int, dtype) -> list[Params]:
    """One attention site, a Transformer2D of ``depth`` BasicTransformerBlocks,
    as a list of block dicts: the first also holds ``gn`` and ``proj_in``,
    the last ``proj_out``.  At depth 1 the one dict is :func:`init_tf`'s,
    drawn from ``key`` itself."""
    if depth == 1:
        return [init_tf(key, c, n_heads, ctx_dim, dtype)]
    blocks = [init_tf(k, c, n_heads, ctx_dim, dtype) for k in jax.random.split(key, depth)]
    for i, blk in enumerate(blocks):
        if i:
            del blk["gn"], blk["proj_in"]
        if i < depth - 1:
            del blk["proj_out"]
    return blocks


def _mha(q, k, v, o_proj, n_heads: int):
    bsz, lq, c = q.shape
    lk = k.shape[1]
    dh = c // n_heads
    qh = q.reshape(bsz, lq, n_heads, dh).transpose(0, 2, 1, 3) * dh**-0.5
    kh = k.reshape(bsz, lk, n_heads, dh).transpose(0, 2, 1, 3)
    vh = v.reshape(bsz, lk, n_heads, dh).transpose(0, 2, 1, 3)
    logits = jnp.einsum("bhqd,bhkd->bhqk", qh, kh).astype(jnp.float32)
    w = jax.nn.softmax(logits, axis=-1).astype(v.dtype)
    out = jnp.einsum("bhqk,bhkd->bhqd", w, vh).transpose(0, 2, 1, 3).reshape(bsz, lq, c)
    return out @ o_proj


def apply_site(
    blocks: Sequence[Params], x: jax.Array, ctx: jax.Array, hw, n_heads: int, groups: int,
    backend=None,
) -> jax.Array:
    """One Transformer2D (:func:`init_site`'s list): norm and ``proj_in``,
    every block in turn, ``proj_out``, residual."""
    bk = _resolve_backend(backend)
    first, last = blocks[0], blocks[-1]
    h = bk.group_norm(x, first["gn"], groups)
    h = bk.conv(first["proj_in"]["w"], first["proj_in"]["b"], h, hw, 1)
    for p in blocks:
        h = _basic_block(p, h, ctx, n_heads, bk)
    h = bk.conv(last["proj_out"]["w"], last["proj_out"]["b"], h, hw, 1)
    return h + x


def _basic_block(p: Params, h: jax.Array, ctx: jax.Array, n_heads: int, bk) -> jax.Array:
    """LN, self-attention, LN, cross-attention, LN, GEGLU, each residual."""
    z = layer_norm(h, p["ln1"])
    h = h + bk.attention(z @ p["self_q"], z @ p["self_k"], z @ p["self_v"], p["self_o"], n_heads)
    z = layer_norm(h, p["ln2"])
    h = h + bk.attention(
        z @ p["cross_q"], ctx @ p["cross_k"], ctx @ p["cross_v"], p["cross_o"], n_heads
    )
    z = layer_norm(h, p["ln3"])
    ff = z @ p["ff_in"]
    gate, val = jnp.split(ff, 2, axis=-1)
    gelu = lambda t: t * jax.nn.sigmoid(1.702 * t)  # paper's sigmoid GELU
    return h + (gelu(gate) * val) @ p["ff_out"]


# ---------------------------------------------------------------------------
# SDXL's text_time added conditioning
# ---------------------------------------------------------------------------


#: width of each time id's sinusoid (diffusers' ``addition_time_embed_dim``)
TIME_ID_DIM = 256


def init_add_embedding(key, cfg: UNetConfig, dtype) -> Params:
    """``add_embedding``: (pooled ++ embedded time ids) -> time_dim -> time_dim."""
    k1, k2 = jax.random.split(key)
    d_in = cfg.pooled_dim + len(cfg.time_ids) * TIME_ID_DIM
    return {
        "w1": _dense_init(k1, (d_in, cfg.time_dim), dtype),
        "b1": jnp.zeros((cfg.time_dim,), dtype),
        "w2": _dense_init(k2, (cfg.time_dim, cfg.time_dim), dtype),
        "b2": jnp.zeros((cfg.time_dim,), dtype),
    }


def added_embedding(cfg: UNetConfig, p: Params, added: jax.Array) -> jax.Array:
    """``added`` [B, pooled_dim + n_ids] (the pooled text embedding, then the
    time ids) -> [B, time_dim]: each id embedded ``TIME_ID_DIM`` wide (cos
    first), concatenated after the pooled embedding, then the MLP."""
    b = added.shape[0]
    pooled, ids = added[:, : cfg.pooled_dim], added[:, cfg.pooled_dim :]
    emb = timestep_embedding(ids.reshape(-1), TIME_ID_DIM).reshape(b, -1)
    z = jnp.concatenate([pooled, emb.astype(pooled.dtype)], axis=-1)
    return jax.nn.silu(z @ p["w1"] + p["b1"]) @ p["w2"] + p["b2"]


# ---------------------------------------------------------------------------
# U-Net assembly
# ---------------------------------------------------------------------------


def _level_channels(cfg: UNetConfig) -> list[int]:
    return [cfg.base_channels * m for m in cfg.channel_mult]


def init_unet(key, cfg: UNetConfig) -> Params:
    dtype = jnp.dtype(cfg.dtype)
    chans = _level_channels(cfg)
    ks = iter(jax.random.split(key, 256))
    tdim = cfg.time_dim

    params: Params = {
        "time_mlp": {
            "w1": _dense_init(next(ks), (cfg.base_channels, tdim), dtype),
            "b1": jnp.zeros((tdim,), dtype),
            "w2": _dense_init(next(ks), (tdim, tdim), dtype),
            "b2": jnp.zeros((tdim,), dtype),
        },
        "conv_in": init_conv(next(ks), 3, cfg.in_channels, cfg.base_channels, dtype),
        "down": [],
        "mid": {},
        "up": [],
        "gn_out": init_gn(cfg.base_channels),
        "conv_out": init_conv(next(ks), 3, cfg.base_channels, cfg.out_channels, dtype),
    }

    # down path
    ch = cfg.base_channels
    for lvl, cout in enumerate(chans):
        for _ in range(cfg.n_res_blocks):
            blk = {"res": init_res(next(ks), ch, cout, tdim, cfg.groups, dtype)}
            if lvl in cfg.attn_levels:
                blk["tf"] = init_site(next(ks), cout, cfg.heads_at(lvl), cfg.ctx_dim,
                                      cfg.depth_at(lvl), dtype)
            params["down"].append(blk)
            ch = cout
        if lvl != cfg.n_levels - 1:
            params["down"].append({"downsample": init_conv(next(ks), 3, ch, ch, dtype)})

    # middle
    params["mid"] = {
        "res1": init_res(next(ks), ch, ch, tdim, cfg.groups, dtype),
        "tf": init_site(next(ks), ch, cfg.mid_heads, cfg.ctx_dim, cfg.mid_tf_depth, dtype),
        "res2": init_res(next(ks), ch, ch, tdim, cfg.groups, dtype),
    }

    # up path: skip channels are consumed in reverse production order
    skip_ch = [cfg.base_channels]
    c2 = cfg.base_channels
    for lvl, cout in enumerate(chans):
        for _ in range(cfg.n_res_blocks):
            c2 = cout
            skip_ch.append(c2)
        if lvl != cfg.n_levels - 1:
            skip_ch.append(c2)

    ch_up = ch
    for lvl in reversed(range(cfg.n_levels)):
        cout = chans[lvl]
        for i in range(cfg.n_res_blocks + 1):
            sc = skip_ch.pop()
            blk = {"res": init_res(next(ks), ch_up + sc, cout, tdim, cfg.groups, dtype)}
            if lvl in cfg.attn_levels:
                blk["tf"] = init_site(next(ks), cout, cfg.heads_at(lvl), cfg.ctx_dim,
                                      cfg.depth_at(lvl), dtype)
            if i == cfg.n_res_blocks and lvl != 0:
                blk["upsample"] = init_conv(next(ks), 3, cout, cout, dtype)
            params["up"].append(blk)
            ch_up = cout
    if cfg.added_dim:
        params["add_embedding"] = init_add_embedding(next(ks), cfg, dtype)
    return params


def tf_blocks(cfg: UNetConfig, entry_step: int = 0) -> int:
    """BasicTransformerBlocks one row's pass runs: FULL (``entry_step ==
    0``) or partial, entering up-step ``entry_step`` (sd_v14: 16 FULL)."""
    n_up = n_up_steps(cfg)
    n_skips = n_up - entry_step
    blocks, skips = 0, 1  # conv_in's output is the first skip
    for lvl, has_attn, _ in _down_plan(cfg):
        if entry_step and skips >= n_skips:
            break
        blocks += cfg.depth_at(lvl) if has_attn else 0
        skips += 1
    if entry_step == 0:
        blocks += cfg.mid_tf_depth
    for lvl, has_attn, _ in _up_plan(cfg)[entry_step:]:
        blocks += cfg.depth_at(lvl) if has_attn else 0
    return blocks


def n_up_steps(cfg: UNetConfig) -> int:
    return cfg.n_levels * (cfg.n_res_blocks + 1)


def _down_plan(cfg: UNetConfig) -> list[tuple[int, bool, bool]]:
    """(level, has_attn, is_downsample) per down entry (after conv_in)."""
    plan = []
    for lvl in range(cfg.n_levels):
        for _ in range(cfg.n_res_blocks):
            plan.append((lvl, lvl in cfg.attn_levels, False))
        if lvl != cfg.n_levels - 1:
            plan.append((lvl, False, True))
    return plan


def _up_plan(cfg: UNetConfig) -> list[tuple[int, bool, bool]]:
    plan = []
    for lvl in reversed(range(cfg.n_levels)):
        for i in range(cfg.n_res_blocks + 1):
            up_after = i == cfg.n_res_blocks and lvl != 0
            plan.append((lvl, lvl in cfg.attn_levels, up_after))
    return plan


def _upsample2x(x: jax.Array, hw) -> tuple[jax.Array, tuple[int, int]]:
    h, w = hw
    x2 = x.reshape(x.shape[0], h, w, x.shape[-1])
    x2 = jnp.repeat(jnp.repeat(x2, 2, axis=1), 2, axis=2)  # nearest interpolation
    return x2.reshape(x.shape[0], 4 * h * w, x.shape[-1]), (2 * h, 2 * w)


def unet_apply(
    cfg: UNetConfig,
    params: Params,
    x: jax.Array,  # [B, L0, Cin] latent in (L, C) layout
    t: jax.Array,  # [B] timesteps
    ctx: jax.Array,  # [B, ctx_len, ctx_dim]
    *,
    entry_step: int = 0,  # first up-step to execute (0 = full run)
    entry_feat: jax.Array | None = None,  # cached main-branch feature
    capture_steps: Sequence[int] = (),
    backend=None,  # KernelBackend instance or name; None = "xla"
    added: jax.Array | None = None,  # [B, cfg.added_dim] text_time conditioning
) -> tuple[jax.Array, dict[int, jax.Array]]:
    """Full or partial U-Net forward.

    ``entry_step == 0``: the full network runs (down, mid, up).
    ``entry_step == e > 0``: only the down blocks producing skips consumed by
    up-steps e..end run; the main branch enters up-step ``e`` with
    ``entry_feat`` (the paper's cached sketch feature).

    ``backend`` selects the kernel backend (``repro.models.backend``) every
    conv / group-norm / attention call routes through; the default XLA
    backend traces the identical program as the pre-dispatch inline code.

    ``added`` is the ``text_time`` conditioning (see
    :func:`added_embedding`), required exactly when ``cfg`` has one.

    Returns (eps_prediction, {captured step -> main-branch feature}).
    """
    bk = _resolve_backend(backend)
    size = cfg.latent_size
    hw = (size, size)
    groups = cfg.groups

    temb = timestep_embedding(t, cfg.base_channels).astype(x.dtype)
    tm = params["time_mlp"]
    temb = jax.nn.silu(temb @ tm["w1"] + tm["b1"]) @ tm["w2"] + tm["b2"]
    if (added is None) != (not cfg.added_dim):
        raise ValueError(f"{cfg.name} takes {'no' if not cfg.added_dim else 'an'} added "
                         f"conditioning; {'none' if added is None else 'one'} was given")
    if added is not None:
        temb = temb + added_embedding(cfg, params["add_embedding"], added).astype(temb.dtype)

    up_plan = _up_plan(cfg)
    n_up = len(up_plan)
    n_skips_needed = n_up - entry_step  # up-steps consume skips in reverse

    # ---- down path (possibly truncated) -----------------------------------
    h = bk.conv(params["conv_in"]["w"], params["conv_in"]["b"], x, hw, 3)
    skips = [h]
    hws = [hw]
    down_plan = _down_plan(cfg)
    for entry, (lvl, has_attn, is_down) in zip(params["down"], down_plan):
        if len(skips) >= n_skips_needed and entry_step > 0:
            break
        if is_down:
            h = bk.conv(
                entry["downsample"]["w"], entry["downsample"]["b"], h, hw, 3, stride=2
            )
            hw = (hw[0] // 2, hw[1] // 2)
        else:
            h = apply_res(entry["res"], h, temb, hw, groups, backend=bk)
            if has_attn:
                h = apply_site(entry["tf"], h, ctx, hw, cfg.heads_at(lvl), groups, backend=bk)
        skips.append(h)
        hws.append(hw)

    captured: dict[int, jax.Array] = {}

    # ---- middle ------------------------------------------------------------
    if entry_step == 0:
        m = params["mid"]
        h = apply_res(m["res1"], h, temb, hw, groups, backend=bk)
        h = apply_site(m["tf"], h, ctx, hw, cfg.mid_heads, groups, backend=bk)
        h = apply_res(m["res2"], h, temb, hw, groups, backend=bk)
    else:
        assert entry_feat is not None, "partial run needs the cached feature"
        h = entry_feat
        hw = hws[n_skips_needed - 1]  # resolution of the entry up-step

    # ---- up path -----------------------------------------------------------
    for step in range(entry_step, n_up):
        if step in capture_steps:
            captured[step] = h
        entry = params["up"][step]
        skip = skips.pop()
        hw = hws.pop()
        h = jnp.concatenate([h, skip], axis=-1)
        h = apply_res(entry["res"], h, temb, hw, groups, backend=bk)
        lvl, has_attn, up_after = up_plan[step]
        if has_attn:
            h = apply_site(entry["tf"], h, ctx, hw, cfg.heads_at(lvl), groups, backend=bk)
        if up_after:
            h, hw = _upsample2x(h, hw)
            h = bk.conv(entry["upsample"]["w"], entry["upsample"]["b"], h, hw, 3)

    h = bk.group_norm(h, params["gn_out"], groups, silu=True)
    eps = bk.conv(params["conv_out"]["w"], params["conv_out"]["b"], h, hw, 3)
    return eps, captured
