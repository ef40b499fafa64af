"""The SDXL base U-Net, diffusers' ``UNet2DConditionModel`` as
stabilityai/stable-diffusion-xl-base-1.0 publishes it: the benchmark's
reading of a configuration file, the program's model config, the analytic
work model and the plain float32 reference.

It exports the architecture contract (``spec.arch``): ``dims``,
``program_config``, ``class_flops``, ``kernel_calls`` and ``Sampler``.

What sets it apart from SD 1.x (``arch/sd_unet.py``, whose primitives it
reuses): heads per level (``attention_head_dim`` [5, 10, 20] are head
counts, every head 64 wide); one Transformer2D per attention site, a
group norm and ``proj_in``, ``transformer_layers_per_block`` of that
level's BasicTransformerBlocks, ``proj_out`` and the residual (the mid
block takes the last level's depth and heads, the up path each level's
own); and the ``text_time`` added conditioning: six time ids, each a
sinusoid ``addition_time_embed_dim`` wide (cos first), after the pooled
text embedding, through Linear-SiLU-Linear into the time embedding's
width, added to the time embedding.

The work model counts MACs by ``arch/sd_unet.py``'s convention with
depth-N sites: ``proj_in`` and ``proj_out`` once per site, the blocks'
projections, attention and GEGLU once per block; the time and added
embeddings' MLPs are not counted (one row of each per pass).

The reference imports nothing of the program.  It reads the served
parameter tree by name, holds the matrices in bfloat16 (exact: the served
weights are bfloat16) and computes to float32 accuracy as
``arch/sd_unet.py`` does; a site's blocks run as one ``lax.scan`` over
weights stacked once, at load, so its programs do not unroll every block.
"""
from __future__ import annotations

import dataclasses
import functools
import hashlib
import math

import numpy as np

from benchmarks.chip import spec
from benchmarks.chip.reference import (
    FULL, REFINE, SKETCH, alphas_cumprod, pndm_update, request_inputs, tier_branches,
    timesteps,
)

#: SD 1.x's module: its MAC primitives and reference layers are this one's
SD = spec.arch({"arch": "sd_unet"})

# ---------------------------------------------------------------------------
# reading the configuration file
# ---------------------------------------------------------------------------

#: published keys whose other values this module would misread, with the
#: one value it implements, which is also diffusers' default when absent
HONOURED = {
    "num_attention_heads": None,
    "reverse_transformer_layers_per_block": None,
    "class_embed_type": None,
    "num_class_embeds": None,
    "class_embeddings_concat": False,
    "encoder_hid_dim": None,
    "encoder_hid_dim_type": None,
    "time_cond_proj_dim": None,
    "time_embedding_type": "positional",
    "time_embedding_dim": None,
    "time_embedding_act_fn": None,
    "timestep_post_act": None,
    "center_input_sample": False,
    "flip_sin_to_cos": True,
    "freq_shift": 0,
    "act_fn": "silu",
    "conv_in_kernel": 3,
    "conv_out_kernel": 3,
    "downsample_padding": 1,
    "mid_block_type": "UNetMidBlock2DCrossAttn",
    "mid_block_scale_factor": 1,
    "mid_block_only_cross_attention": None,
    "only_cross_attention": False,
    "dual_cross_attention": False,
    "cross_attention_norm": None,
    "resnet_time_scale_shift": "default",
    "resnet_skip_time_act": False,
    "resnet_out_scale_factor": 1.0,
}


@dataclasses.dataclass(frozen=True)
class UNetDims:
    """The published U-Net's shape, read from its diffusers ``config.json``
    keys; the field names ``arch/sd_unet.py``'s primitives read are its."""

    in_channels: int
    out_channels: int
    block_out_channels: tuple[int, ...]
    layers_per_block: int
    attn_levels: tuple[int, ...]
    heads: tuple[int, ...]  # per level
    depths: tuple[int, ...]  # BasicTransformerBlocks per site, per level
    mid_depth: int
    cross_attention_dim: int
    ctx_len: int
    time_dim: int
    groups: int
    norm_eps: float
    sample_size: int
    pooled_dim: int
    time_ids: tuple[int, ...]
    time_id_dim: int

    @property
    def n_levels(self) -> int:
        return len(self.block_out_channels)

    @property
    def n_up(self) -> int:
        return self.n_levels * (self.layers_per_block + 1)


def _refuse(config: dict, key: str, value, why: str):
    raise ValueError(f"configuration {config.get('name')!r}: key \"arch\" is 'sdxl_unet', "
                     f"which cannot honour {key}={value!r}: {why}")


def _per_level(config: dict, key: str) -> tuple[int, ...]:
    v = config.get(key)
    n = len(config["block_out_channels"])
    if isinstance(v, int):
        return (v,) * n
    if not (isinstance(v, list) and len(v) == n and all(isinstance(x, int) for x in v)):
        _refuse(config, key, v, f"an int or a list of {n} ints, one per level")
    return tuple(v)


def dims(config: dict) -> UNetDims:
    """Diffusers keys -> :class:`UNetDims`.  ``attention_head_dim`` is the
    number of heads per level (diffusers' naming quirk for this release);
    ``transformer_layers_per_block`` the depth per level, the mid block's
    the last; the pooled embedding is what ``projection_class_embeddings_
    input_dim`` leaves after the time ids.  A key whose value this reading
    would get wrong is refused, naming the key."""
    for key, want in HONOURED.items():
        if config.get(key, want) != want:
            _refuse(config, key, config[key], f"this architecture implements {want!r} only")
    for key, want in (("addition_embed_type", "text_time"), ("addition_time_embed_dim", 256)):
        if config.get(key) != want:
            _refuse(config, key, config.get(key), f"this architecture implements {want!r} only")
    heads = _per_level(config, "attention_head_dim")
    if not isinstance(config["attention_head_dim"], list):
        _refuse(config, "attention_head_dim", config["attention_head_dim"],
                "a list of head counts, one per level")
    depths = _per_level(config, "transformer_layers_per_block")
    chans = config["block_out_channels"]
    if any(c % h for c, h in zip(chans, heads)):
        _refuse(config, "attention_head_dim", list(heads), "heads must divide the channels")
    down = config["down_block_types"]
    up = [t.replace("Up", "Down") for t in reversed(config["up_block_types"])]
    if up != down:
        _refuse(config, "up_block_types", config["up_block_types"],
                "the mirror of down_block_types only")
    if not down[-1].startswith("CrossAttn"):
        _refuse(config, "down_block_types", down, "a cross-attention last level only")
    sched = config["scheduler"]
    if sched["_class_name"] != "PNDMScheduler":
        _refuse(config, "scheduler._class_name", sched["_class_name"], "PNDMScheduler only")
    pred = sched.get("prediction_type", config.get("prediction_type", "epsilon"))
    if pred != "epsilon":
        _refuse(config, "prediction_type", pred, "epsilon prediction only")
    time_ids = tuple(config["serving"]["time_ids"])
    time_id_dim = config["addition_time_embed_dim"]
    pooled = config["projection_class_embeddings_input_dim"] - len(time_ids) * time_id_dim
    if len(time_ids) != 6 or pooled <= 0:
        _refuse(config, "projection_class_embeddings_input_dim",
                config["projection_class_embeddings_input_dim"],
                f"the pooled width plus {len(time_ids)} time ids of {time_id_dim}")
    return UNetDims(
        in_channels=config["in_channels"],
        out_channels=config["out_channels"],
        block_out_channels=tuple(chans),
        layers_per_block=config["layers_per_block"],
        attn_levels=tuple(i for i, t in enumerate(down) if t.startswith("CrossAttn")),
        heads=heads,
        depths=depths,
        mid_depth=depths[-1],
        cross_attention_dim=config["cross_attention_dim"],
        ctx_len=config["serving"]["text_max_length"],
        time_dim=4 * chans[0],
        groups=config["norm_num_groups"],
        norm_eps=config["norm_eps"],
        sample_size=config["sample_size"],
        pooled_dim=pooled,
        time_ids=time_ids,
        time_id_dim=time_id_dim,
    )


def program_config(config: dict):
    """The program's ``UNetConfig`` for the configuration file."""
    from repro.common.types import UNetConfig

    d = dims(config)
    base = d.block_out_channels[0]
    return UNetConfig(
        name=config["name"],
        in_channels=d.in_channels,
        out_channels=d.out_channels,
        base_channels=base,
        channel_mult=tuple(c // base for c in d.block_out_channels),
        n_res_blocks=d.layers_per_block,
        attn_levels=d.attn_levels,
        level_heads=d.heads,
        level_depths=d.depths,
        ctx_dim=d.cross_attention_dim,
        ctx_len=d.ctx_len,
        time_dim=d.time_dim,
        groups=d.groups,
        latent_size=d.sample_size,
        dtype=config["serving"]["weight_dtype"],
        pooled_dim=d.pooled_dim,
        time_ids=d.time_ids,
    )


# ---------------------------------------------------------------------------
# the work model: multiply-accumulates per latent row, and kernel calls
# ---------------------------------------------------------------------------


def block_macs(l: int, c: int, ctx_len: int, ctx_dim: int) -> int:
    """One BasicTransformerBlock over ``l`` positions of width ``c``."""
    return SD.tf_macs(l, c, ctx_len, ctx_dim) - 2 * SD.conv_macs(l, c, c, 1)


def site_macs(l: int, c: int, depth: int, d: UNetDims) -> int:
    """One Transformer2D: ``proj_in``/``proj_out`` once, ``depth`` blocks."""
    return 2 * SD.conv_macs(l, c, c, 1) + depth * block_macs(l, c, d.ctx_len,
                                                            d.cross_attention_dim)


def blocks(d: UNetDims, l: int = -1) -> list[tuple]:
    """The blocks a pass with budget ``l`` runs (``l < 0``: FULL), as
    ``(kind, positions, channels...)``: ``("conv", L_in, L_out, cin, cout,
    k)``, ``("res", L, cin, cout)``, ``("tf", L, c, heads, depth)``.  A
    partial pass runs ``conv_in``, the first ``l - 1`` down entries, the top
    ``l`` up-steps and ``conv_out``, as in ``arch/sd_unet.py``."""
    chans = list(d.block_out_channels)
    n_up = d.n_up
    full = l < 0 or l > n_up
    l0 = d.sample_size**2
    out: list[tuple] = [("conv", l0, l0, d.in_channels, chans[0], 3)]
    down: list[list[tuple]] = []
    ch, cur = chans[0], l0
    for lvl, cout in enumerate(chans):
        for _ in range(d.layers_per_block):
            entry = [("res", cur, ch, cout)]
            if lvl in d.attn_levels:
                entry.append(("tf", cur, cout, d.heads[lvl], d.depths[lvl]))
            down.append(entry)
            ch = cout
        if lvl != d.n_levels - 1:
            down.append([("conv", cur, cur // 4, ch, ch, 3)])
            cur //= 4
    for entry in down if full else down[: l - 1]:
        out += entry
    if full:
        out += [("res", cur, ch, ch), ("tf", cur, ch, d.heads[-1], d.mid_depth),
                ("res", cur, ch, ch)]
    skips = SD.skip_channels(d)
    up: list[list[tuple]] = []
    ch_up = ch
    for lvl in reversed(range(d.n_levels)):
        cout = chans[lvl]
        cur_l = (d.sample_size >> lvl) ** 2
        for i in range(d.layers_per_block + 1):
            entry = [("res", cur_l, ch_up + skips.pop(), cout)]
            if lvl in d.attn_levels:
                entry.append(("tf", cur_l, cout, d.heads[lvl], d.depths[lvl]))
            if i == d.layers_per_block and lvl != 0:
                entry.append(("conv", cur_l, cur_l * 4, cout, cout, 3))
            up.append(entry)
            ch_up = cout
    for entry in up if full else up[n_up - l :]:
        out += entry
    out.append(("conv", l0, l0, chans[0], d.out_channels, 3))
    return out


def macs(d: UNetDims, l: int = -1) -> int:
    """MACs per row of a pass with budget ``l`` (``l < 0``: FULL)."""
    total = 0
    for b in blocks(d, l):
        if b[0] == "conv":
            _, _, l_out, cin, cout, k = b
            total += SD.conv_macs(l_out, cin, cout, k)
        elif b[0] == "res":
            total += SD.res_macs(*b[1:])
        else:
            _, p, c, _, depth = b
            total += site_macs(p, c, depth, d)
    return total


def class_flops(d: UNetDims, l_sketch: int, l_refine: int) -> dict[str, int]:
    """Model FLOPs (2 per MAC) of one row's FULL / SKETCH / REFINE pass."""
    return {"full": 2 * macs(d), "sketch": 2 * macs(d, l_sketch),
            "refine": 2 * macs(d, l_refine)}


def kernel_calls(d: UNetDims, l: int = -1) -> dict[str, list[tuple]]:
    """The calls of a pass with budget ``l`` (``l < 0``: FULL), by the
    ``work/<kind>.py`` module that counts them: ``conv`` ``(L_in, L_out,
    cin, cout, k)``, every convolution (the residual blocks' 3x3 and 1x1,
    each site's 1x1 ``proj_in``/``proj_out``, the strided and upsampling
    3x3); ``attention`` ``(Lq, Lk, heads, head_dim)``, with the site's own
    heads, each block's self-attention over its ``L`` positions and
    cross-attention over the ``ctx_len`` text positions; ``matmul`` ``(L,
    cin, cout)``, each block's q/k/v/o projections and GEGLU feed-forward,
    the cross-attention's k and v over the text."""
    conv: list[tuple] = []
    attention: list[tuple] = []
    matmul: list[tuple] = []
    ctx, cd = d.ctx_len, d.cross_attention_dim
    for b in blocks(d, l):
        if b[0] == "conv":
            conv.append(b[1:])
        elif b[0] == "res":
            _, p, cin, cout = b
            conv += [(p, p, cin, cout, 3), (p, p, cout, cout, 3)]
            if cin != cout:
                conv.append((p, p, cin, cout, 1))
        else:
            _, p, c, heads, depth = b
            conv += [(p, p, c, c, 1), (p, p, c, c, 1)]
            for _ in range(depth):
                attention += [(p, p, heads, c // heads), (p, ctx, heads, c // heads)]
                # self q, k, v, o and cross q; cross k, v over the text; cross o; GEGLU
                matmul += [(p, c, c)] * 5 + [(ctx, cd, c)] * 2
                matmul += [(p, c, c), (p, c, 8 * c), (p, 4 * c, c)]
    return {"conv": conv, "attention": attention, "matmul": matmul}


# ---------------------------------------------------------------------------
# the reference U-Net (jax.numpy, NHWC)
# ---------------------------------------------------------------------------

#: a BasicTransformerBlock's weights, the ones stacked along a site's depth
BLOCK_KEYS = ("ln1", "self_q", "self_k", "self_v", "self_o", "ln2", "cross_q", "cross_k",
              "cross_v", "cross_o", "ln3", "ff_in", "ff_out")


def sinusoid(t, dim: int):
    """[..., dim]: cos then sin of ``t`` at ``dim // 2`` frequencies."""
    import jax.numpy as jnp

    half = dim // 2
    freqs = jnp.exp(-math.log(10000.0) * jnp.arange(half, dtype=jnp.float32) / half)
    ang = t.astype(jnp.float32)[..., None] * freqs
    return jnp.concatenate([jnp.cos(ang), jnp.sin(ang)], axis=-1)


class UNet(SD.UNet):
    """The forward pass on dims ``d``, on the tree :func:`stack_sites` makes."""

    def attention(self, q, k, v, o, heads):
        """Softmax attention with ``heads`` heads, in blocks of queries so
        that no score block passes 2**27 elements (512 MiB)."""
        import jax
        import jax.numpy as jnp

        b, lq, c = q.shape
        dh = c // heads
        split = lambda t: t.reshape(b, t.shape[1], heads, dh).transpose(0, 2, 1, 3)  # noqa: E731
        prec = jax.lax.Precision.HIGHEST
        qh, kh, vh = self.q(split(q)), self.q(split(k)), self.q(split(v))
        block = max(1, min(lq, 2**27 // (b * heads * kh.shape[2])))
        outs = []
        for s0 in range(0, lq, block):
            s = jnp.einsum("bhqd,bhkd->bhqk", qh[:, :, s0 : s0 + block], kh, precision=prec)
            w = jax.nn.softmax(s / math.sqrt(dh), axis=-1)
            outs.append(jnp.einsum("bhqk,bhkd->bhqd", self.q(w), vh, precision=prec))
        out = jnp.concatenate(outs, axis=2)
        return self.mm(out.transpose(0, 2, 1, 3).reshape(b, lq, c), o)

    def block(self, p, h, ctx, heads):
        """One BasicTransformerBlock on [B, L, C]."""
        import jax
        import jax.numpy as jnp

        z = self.layer_norm(p["ln1"], h)
        h = h + self.attention(self.mm(z, p["self_q"]), self.mm(z, p["self_k"]),
                               self.mm(z, p["self_v"]), p["self_o"], heads)
        z = self.layer_norm(p["ln2"], h)
        h = h + self.attention(self.mm(z, p["cross_q"]), self.mm(ctx, p["cross_k"]),
                               self.mm(ctx, p["cross_v"]), p["cross_o"], heads)
        z = self.layer_norm(p["ln3"], h)
        gate, val = jnp.split(self.mm(z, p["ff_in"]), 2, axis=-1)
        return h + self.mm(gate * jax.nn.sigmoid(1.702 * gate) * val, p["ff_out"])

    def site(self, p, x, ctx, heads):
        """One Transformer2D: norm, ``proj_in``, the stacked blocks in one
        scan, ``proj_out``, residual."""
        import jax

        b, hh, ww, c = x.shape
        h = self.conv(p["proj_in"], self.group_norm(p["gn"], x, False), 1).reshape(b, hh * ww, c)
        h, _ = jax.lax.scan(lambda h, bp: (self.block(bp, h, ctx, heads), None), h, p["blocks"])
        return self.conv(p["proj_out"], h.reshape(b, hh, ww, c), 1) + x

    def added_embedding(self, p, added):
        """[B, time_dim] of ``added`` [B, pooled + n_ids]: the pooled
        embedding, then each time id's sinusoid, through the MLP."""
        import jax
        import jax.numpy as jnp

        d = self.d
        pooled, ids = added[:, : d.pooled_dim], added[:, d.pooled_dim :]
        emb = sinusoid(ids, d.time_id_dim).reshape(added.shape[0], -1)
        ae = p["add_embedding"]
        z = jnp.concatenate([pooled, emb], axis=-1)
        return self.mm(jax.nn.silu(self.mm(z, ae["w1"]) + ae["b1"]), ae["w2"]) + ae["b2"]

    def __call__(self, p, x, t, ctx, added, entry_step=0, entry_feat=None, capture=()):
        """eps [B, H, W, C] of a FULL pass (``entry_step == 0``) or of a
        partial pass entering up-step ``entry_step`` with ``entry_feat``;
        plus the main-branch feature entering each up-step in ``capture``."""
        import jax.numpy as jnp

        d = self.d
        temb = self.time_embedding(p, t) + self.added_embedding(p, added)
        n_skips = d.n_up - entry_step
        h = self.conv(p["conv_in"], x, 3)
        skips = [h]
        down = iter(p["down"])
        for lvl in range(d.n_levels):
            for _ in range(d.layers_per_block):
                if entry_step and len(skips) >= n_skips:
                    break
                e = next(down)
                h = self.res(e["res"], h, temb)
                if "tf" in e:
                    h = self.site(e["tf"], h, ctx, d.heads[lvl])
                skips.append(h)
            if lvl != d.n_levels - 1 and not (entry_step and len(skips) >= n_skips):
                h = self.conv(next(down)["downsample"], h, 3, stride=2)
                skips.append(h)
        if entry_step == 0:
            m = p["mid"]
            h = self.res(m["res1"], h, temb)
            h = self.site(m["tf"], h, ctx, d.heads[-1])
            h = self.res(m["res2"], h, temb)
        else:
            h = entry_feat
        captured = {}
        for step in range(entry_step, d.n_up):
            if step in capture:
                captured[step] = h
            e = p["up"][step]
            h = self.res(e["res"], jnp.concatenate([h, skips.pop()], axis=-1), temb)
            if "tf" in e:  # up-steps run the levels top down, layers_per_block + 1 each
                lvl = d.n_levels - 1 - step // (d.layers_per_block + 1)
                h = self.site(e["tf"], h, ctx, d.heads[lvl])
            if "upsample" in e:
                h = jnp.repeat(jnp.repeat(h, 2, axis=1), 2, axis=2)
                h = self.conv(e["upsample"], h, 3)
        h = self.group_norm(p["gn_out"], h, True)
        return self.conv(p["conv_out"], h, 3), captured


# ---------------------------------------------------------------------------
# the weights the reference holds
# ---------------------------------------------------------------------------


def _sites(tree: dict):
    """``(holder, key)`` of every attention site in a served tree."""
    for e in (*tree["down"], tree["mid"], *tree["up"]):
        if "tf" in e:
            yield e, "tf"


def hold_in_bf16(tree: dict) -> None:
    """Replace, leaf by leaf and in the tree itself, every float32 leaf but
    the norms' ``scale``/``bias`` by its bfloat16 value, then each site's
    list of block dicts by one dict whose ``blocks`` stack the blocks'
    weights along the depth.  Each float32 leaf is freed as soon as it is
    replaced, so the tree never needs room for two copies.  The served
    weights are bfloat16 values, so the rounding is exact; it is checked.
    A tree this has already held is left as it is."""
    import jax
    import jax.numpy as jnp

    to_bf16 = _to_bf16()
    exact = []

    def walk(node):
        for k in range(len(node)) if isinstance(node, list) else list(node):
            v = node[k]
            if isinstance(v, (dict, list)):
                walk(v)
            elif k not in ("scale", "bias") and v.dtype == jnp.float32:
                node[k], ok = to_bf16(v)
                exact.append(ok)

    walk(tree)
    if exact and not bool(jnp.all(jnp.stack(exact))):
        raise ValueError("a served weight is not a bfloat16 value; the reference would not "
                         "hold the served weights exactly")
    for holder, key in _sites(tree):
        site = holder[key]
        if isinstance(site, dict):
            continue
        stacked = jax.tree.map(lambda *xs: jnp.stack(xs),
                               *[{k: b[k] for k in BLOCK_KEYS} for b in site])
        holder[key] = {"gn": site[0]["gn"], "proj_in": site[0]["proj_in"],
                       "proj_out": site[-1]["proj_out"], "blocks": stacked}


@functools.cache
def _to_bf16():
    import jax
    import jax.numpy as jnp

    def conv(a):
        b = a.astype(jnp.bfloat16)
        return b, jnp.all(b.astype(jnp.float32) == a)

    return jax.jit(conv)


def request_added(prompt: str, seed: int, d: UNetDims) -> np.ndarray:
    """[pooled + n_ids] added conditioning of a request: the pooled text
    embedding drawn next on the stream ``reference.request_inputs`` draws
    the conditioning and the noise from (so they are drawn again first, and
    thrown away), then the time ids."""
    mix = int.from_bytes(hashlib.sha256(prompt.encode()).digest()[:8], "little")
    rng = np.random.default_rng((seed, mix))
    rng.normal(size=(d.ctx_len, d.cross_attention_dim))
    rng.normal(size=(d.sample_size**2, d.in_channels))
    pooled = rng.normal(size=(d.pooled_dim,)).astype(np.float32)
    return np.concatenate([pooled, np.asarray(d.time_ids, np.float32)])


class Sampler:
    """Runs requests through the reference, one jitted program per branch
    class (the weights are an argument, never a constant), as
    ``arch/sd_unet.py``'s ``Sampler`` does, with the added conditioning:
    the unconditional rows get the request's time ids and a zero pooled
    embedding (SDXL's ``force_zeros_for_empty_prompt``).

    ``params`` is the float32 served tree; it is held as
    :func:`hold_in_bf16` makes it, in place, so the float32 copy it was
    given is freed leaf by leaf (``check.compare`` hands over a float32
    copy of the served weights while the served ones are still on the
    device).  Setting ``params`` again holds the new tree the same way.
    ``run_many`` advances ``batch`` requests in lockstep, each branch class
    one call over ``2 * rows`` rows at a time."""

    def __init__(self, config: dict, dims: UNetDims, params32, quant: str | None = None,
                 batch: int = 1, rows: int = 2):
        import jax
        import jax.numpy as jnp

        self.config, self.d, self.batch, self.rows = config, dims, batch, min(rows, batch)
        self.params = params32
        s = config["serving"]
        self.guidance = s["guidance_scale"]
        self.e_sk = dims.n_up - s["l_sketch"]
        self.e_rf = dims.n_up - s["l_refine"]
        self.acp = alphas_cumprod(config["scheduler"])
        keep = np.concatenate([np.zeros(dims.pooled_dim, np.float32),
                               np.ones(len(dims.time_ids), np.float32)])
        net = UNet(dims, quant)

        def cfg_eps(p, x, t, ctx, added, entry_step, feat):
            b = x.shape[0]
            x2 = jnp.concatenate([x, x], axis=0)
            t2 = jnp.concatenate([t, t], axis=0)
            ctx2 = jnp.concatenate([ctx, jnp.zeros_like(ctx)], axis=0)
            added2 = jnp.concatenate([added, added * keep], axis=0)
            capture = () if entry_step else (self.e_sk, self.e_rf)
            eps2, cap = net(p, x2, t2, ctx2, added2, entry_step, feat, capture)
            e_c, e_u = eps2[:b], eps2[b:]
            return e_u + self.guidance * (e_c - e_u), cap

        self._call = {
            FULL: jax.jit(functools.partial(cfg_eps, entry_step=0, feat=None)),
            SKETCH: jax.jit(functools.partial(cfg_eps, entry_step=self.e_sk)),
            REFINE: jax.jit(functools.partial(cfg_eps, entry_step=self.e_rf)),
        }

    @property
    def params(self):
        return self._params

    @params.setter
    def params(self, tree):
        hold_in_bf16(tree)
        self._params = tree

    def run(self, prompt: str, seed: int, tier: str, steps: int) -> np.ndarray:
        """One request's final latent, [L, C] float32."""
        return self.run_many([(prompt, seed, tier, steps)])[0]

    def run_many(self, requests: list[tuple[str, int, str, int]]) -> list[np.ndarray]:
        """Final latents, [L, C] float32, of ``(prompt, seed, tier, steps)``
        requests, ``batch`` at a time."""
        out: list[np.ndarray] = []
        for k in range(0, len(requests), self.batch):
            out += self._lockstep(requests[k : k + self.batch])
        return out

    def _lockstep(self, requests) -> list[np.ndarray]:
        import jax.numpy as jnp

        d, n = self.d, len(requests)
        hw = (d.sample_size, d.sample_size, d.in_channels)
        ctxs, addeds, xs, plans, tss = [], [], [], [], []
        for prompt, seed, tier, steps in requests:
            ctx, noise = request_inputs(prompt, seed, d.ctx_len, d.cross_attention_dim,
                                        d.sample_size, d.in_channels)
            ctxs.append(ctx)
            addeds.append(request_added(prompt, seed, d))
            xs.append(noise.astype(np.float64).reshape(hw))
            plans.append(tier_branches(self.config["pas_tiers"][tier], steps))
            tss.append(timesteps(steps, self.config["scheduler"]["num_train_timesteps"]))
        feats: list[dict] = [{} for _ in range(n)]
        ets: list[list] = [[] for _ in range(n)]
        R = self.rows
        for i in range(max(len(p) for p in plans)):
            for cls in (FULL, SKETCH, REFINE):
                rows = [r for r in range(n) if i < len(plans[r]) and plans[r][i] == cls]
                for k in range(0, len(rows), R):
                    part = rows[k : k + R]
                    pad = R - len(part)
                    x_b = np.zeros((R,) + hw, np.float32)
                    t_b = np.zeros((R,), np.int32)
                    for j, r in enumerate(part):
                        x_b[j], t_b[j] = xs[r], tss[r][i]
                    ctx_b = jnp.asarray(np.stack([ctxs[r] for r in part]
                                                 + [np.zeros_like(ctxs[0])] * pad))
                    add_b = jnp.asarray(np.stack([addeds[r] for r in part]
                                                 + [np.zeros_like(addeds[0])] * pad))
                    args = (self.params, jnp.asarray(x_b), jnp.asarray(t_b), ctx_b, add_b)
                    if cls == FULL:
                        eps, cap = self._call[FULL](*args)
                        for j, r in enumerate(part):
                            feats[r] = {e: (v[j], v[R + j]) for e, v in cap.items()}
                    else:
                        entry = self.e_sk if cls == SKETCH else self.e_rf
                        zero = [jnp.zeros_like(feats[part[0]][entry][0])] * pad
                        feat = jnp.stack([feats[r][entry][0] for r in part] + zero
                                         + [feats[r][entry][1] for r in part] + zero)
                        eps, _ = self._call[cls](*args, feat=feat)
                    eps = np.asarray(eps, np.float64)
                    for j, r in enumerate(part):
                        t = int(tss[r][i])
                        t_prev = int(tss[r][i + 1]) if i + 1 < len(tss[r]) else -1
                        xs[r] = pndm_update(self.acp, ets[r], xs[r], eps[j], t, t_prev)
        return [x.reshape(-1, d.in_channels).astype(np.float32) for x in xs]
