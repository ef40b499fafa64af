"""The SD 1.x U-Net, diffusers' ``UNet2DConditionModel`` as
CompVis/stable-diffusion-v1-4 publishes it: the benchmark's reading of a
configuration file, the program's model config, the analytic work model
and the plain float32 reference.

It exports the architecture contract (``spec.arch``): ``dims``,
``program_config``, ``class_flops``, ``kernel_calls`` and ``Sampler``.
Every other module of the benchmark reaches the architecture through
those names only.

The work model is a copy of the program's analytic MAC model
(``core/framework.py``: ``unet_mac_breakdown`` and ``cost_function``) on
the benchmark's own reading of the configuration, so that a change to the
program cannot move the numerator of ``mfu``.  A FULL pass runs the whole
network; a partial pass with budget ``l`` (SKETCH: ``l_sketch``, REFINE:
``l_refine``) runs ``conv_in``, the first ``l - 1`` down entries, the top
``l`` up-steps and ``conv_out``.

The reference imports nothing of the program.  It is written out in
``jax.numpy`` on NHWC arrays with ``lax.conv_general_dilated``
convolutions, two-pass group norms and plain softmax attention, every
matmul and convolution to float32 accuracy (``"highest"``, or its exact
equivalent for bfloat16 weights).  It reads the weights by their names in
the served parameter tree, which is the only thing it shares with the
program.  ``quant="fp8"`` is the control: every matmul and convolution
operand is rounded to float8 (e4m3, one absmax scale per tensor) first,
the step that would tempt a lower-precision serving path.
"""
from __future__ import annotations

import dataclasses
import functools
import math

import numpy as np

from benchmarks.chip.reference import (
    FULL, REFINE, SKETCH, alphas_cumprod, pndm_update, quantizer, request_inputs,
    tier_branches, timesteps,
)

# ---------------------------------------------------------------------------
# reading the configuration file
# ---------------------------------------------------------------------------

#: published keys whose other values this module would misread, with the
#: one value it implements (``None``: the key must be absent or null)
HONOURED = {
    "transformer_layers_per_block": 1,
    "num_attention_heads": None,
    "addition_embed_type": None,
    "addition_time_embed_dim": None,
    "projection_class_embeddings_input_dim": None,
    "class_embed_type": None,
    "encoder_hid_dim": None,
    "center_input_sample": False,
    "flip_sin_to_cos": True,
    "freq_shift": 0,
    "act_fn": "silu",
    "downsample_padding": 1,
    "mid_block_scale_factor": 1,
}


@dataclasses.dataclass(frozen=True)
class UNetDims:
    """The published U-Net's shape, read from its diffusers ``config.json``
    keys (the names ``configs/*.json`` keeps)."""

    in_channels: int
    out_channels: int
    block_out_channels: tuple[int, ...]
    layers_per_block: int
    attn_levels: tuple[int, ...]
    heads: int
    cross_attention_dim: int
    ctx_len: int
    time_dim: int
    groups: int
    norm_eps: float
    sample_size: int

    @property
    def n_levels(self) -> int:
        return len(self.block_out_channels)

    @property
    def n_up(self) -> int:
        return self.n_levels * (self.layers_per_block + 1)


def _refuse(key: str, value, why: str):
    raise ValueError(f"arch sd_unet cannot honour {key}={value!r}: {why}")


def dims(config: dict) -> UNetDims:
    """Diffusers keys -> :class:`UNetDims`.  SD 1.x's ``attention_head_dim``
    is the number of heads (a naming quirk of that release), the time
    embedding is 4x the first block width, and the text encoder's 77
    positions set the conditioning length.  A published key whose value
    this reading would get wrong is refused, naming the key."""
    for key, want in HONOURED.items():
        if config.get(key, want) != want:
            _refuse(key, config[key], f"this architecture implements {want!r} only")
    heads = config["attention_head_dim"]
    if not isinstance(heads, int):
        _refuse("attention_head_dim", heads, "one head count for every level only")
    down = config["down_block_types"]
    up = [t.replace("Up", "Down") for t in reversed(config["up_block_types"])]
    if up != down:
        _refuse("up_block_types", config["up_block_types"], "the mirror of down_block_types only")
    sched = config["scheduler"]
    if sched["_class_name"] != "PNDMScheduler":
        _refuse("scheduler._class_name", sched["_class_name"], "PNDMScheduler only")
    pred = sched.get("prediction_type", config.get("prediction_type", "epsilon"))
    if pred != "epsilon":
        _refuse("prediction_type", pred, "epsilon prediction only")
    return UNetDims(
        in_channels=config["in_channels"],
        out_channels=config["out_channels"],
        block_out_channels=tuple(config["block_out_channels"]),
        layers_per_block=config["layers_per_block"],
        attn_levels=tuple(i for i, t in enumerate(down) if t.startswith("CrossAttn")),
        heads=heads,
        cross_attention_dim=config["cross_attention_dim"],
        ctx_len=config["serving"]["text_max_length"],
        time_dim=4 * config["block_out_channels"][0],
        groups=config["norm_num_groups"],
        norm_eps=config["norm_eps"],
        sample_size=config["sample_size"],
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
        n_heads=d.heads,
        tf_depth=1,
        ctx_dim=d.cross_attention_dim,
        ctx_len=d.ctx_len,
        time_dim=d.time_dim,
        groups=d.groups,
        latent_size=d.sample_size,
        dtype=config["serving"]["weight_dtype"],
    )


# ---------------------------------------------------------------------------
# the work model: multiply-accumulates per latent row, and kernel calls
# ---------------------------------------------------------------------------


def conv_macs(l: int, cin: int, cout: int, k: int) -> int:
    return l * cin * cout * k * k


def tf_macs(l: int, c: int, ctx_len: int, ctx_dim: int) -> int:
    macs = 2 * conv_macs(l, c, c, 1)  # proj in/out
    macs += 4 * l * c * c  # self q, k, v, o
    macs += 2 * l * l * c  # self-attention scores and values
    macs += l * c * c + 2 * ctx_len * ctx_dim * c + l * c * c  # cross q, k, v, o
    macs += 2 * l * ctx_len * c  # cross-attention scores and values
    macs += l * c * 8 * c + l * 4 * c * c  # GEGLU feed-forward
    return macs


def res_macs(l: int, cin: int, cout: int) -> int:
    macs = conv_macs(l, cin, cout, 3) + conv_macs(l, cout, cout, 3)
    if cin != cout:
        macs += conv_macs(l, cin, cout, 1)
    return macs


@dataclasses.dataclass(frozen=True)
class MACBreakdown:
    conv_in: int
    down: tuple[int, ...]  # per down entry after conv_in
    mid: int
    up: tuple[int, ...]  # per up-step
    conv_out: int

    @property
    def total(self) -> int:
        return self.conv_in + sum(self.down) + self.mid + sum(self.up) + self.conv_out

    def partial(self, l: int) -> int:
        """MACs of a partial pass with budget ``l`` (``l < 0``: FULL)."""
        n_up = len(self.up)
        if l < 0 or l > n_up:
            return self.total
        return self.conv_in + sum(self.down[: l - 1]) + sum(self.up[n_up - l :]) + self.conv_out


def skip_channels(d: UNetDims) -> list[int]:
    """Channels of the skips the down path produces, in production order."""
    chans = list(d.block_out_channels)
    out = [chans[0]]
    for lvl, cout in enumerate(chans):
        out += [cout] * d.layers_per_block
        if lvl != d.n_levels - 1:
            out.append(cout)
    return out


def breakdown(d: UNetDims) -> MACBreakdown:
    """MACs per row of each block of the U-Net with dims ``d``."""
    chans = list(d.block_out_channels)
    l = d.sample_size**2
    conv_in = conv_macs(l, d.in_channels, chans[0], 3)
    down = []
    ch, cur = chans[0], l
    for lvl, cout in enumerate(chans):
        for _ in range(d.layers_per_block):
            m = res_macs(cur, ch, cout)
            if lvl in d.attn_levels:
                m += tf_macs(cur, cout, d.ctx_len, d.cross_attention_dim)
            down.append(m)
            ch = cout
        if lvl != d.n_levels - 1:
            down.append(conv_macs(cur // 4, ch, ch, 3))
            cur //= 4
    mid = 2 * res_macs(cur, ch, ch) + tf_macs(cur, ch, d.ctx_len, d.cross_attention_dim)
    skips = skip_channels(d)
    up = []
    ch_up = ch
    for lvl in reversed(range(d.n_levels)):
        cout = chans[lvl]
        cur_l = (d.sample_size >> lvl) ** 2
        for i in range(d.layers_per_block + 1):
            m = res_macs(cur_l, ch_up + skips.pop(), cout)
            if lvl in d.attn_levels:
                m += tf_macs(cur_l, cout, d.ctx_len, d.cross_attention_dim)
            if i == d.layers_per_block and lvl != 0:
                m += conv_macs(cur_l * 4, cout, cout, 3)
            up.append(m)
            ch_up = cout
    conv_out = conv_macs(l, chans[0], d.out_channels, 3)
    return MACBreakdown(conv_in, tuple(down), mid, tuple(up), conv_out)


def class_flops(d: UNetDims, l_sketch: int, l_refine: int) -> dict[str, int]:
    """Model FLOPs (2 per MAC) of one row's FULL / SKETCH / REFINE pass."""
    br = breakdown(d)
    return {
        "full": 2 * br.total,
        "sketch": 2 * br.partial(l_sketch),
        "refine": 2 * br.partial(l_refine),
    }


def blocks(d: UNetDims, l: int = -1) -> list[tuple]:
    """The blocks a pass with budget ``l`` runs (``l < 0``: FULL), as
    ``(kind, positions, channels...)``: ``("conv", L_in, L_out, cin, cout,
    k)``, ``("res", L, cin, cout)``, ``("tf", L, c)``.  A strided
    convolution reads 4x the positions it writes; an upsampling one reads
    the map before its nearest-neighbour upsampling."""
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
                entry.append(("tf", cur, cout))
            down.append(entry)
            ch = cout
        if lvl != d.n_levels - 1:
            down.append([("conv", cur, cur // 4, ch, ch, 3)])
            cur //= 4
    for entry in down if full else down[: l - 1]:
        out += entry
    if full:
        out += [("res", cur, ch, ch), ("tf", cur, ch), ("res", cur, ch, ch)]
    skips = skip_channels(d)
    up: list[list[tuple]] = []
    ch_up = ch
    for lvl in reversed(range(d.n_levels)):
        cout = chans[lvl]
        cur_l = (d.sample_size >> lvl) ** 2
        for i in range(d.layers_per_block + 1):
            entry = [("res", cur_l, ch_up + skips.pop(), cout)]
            if lvl in d.attn_levels:
                entry.append(("tf", cur_l, cout))
            if i == d.layers_per_block and lvl != 0:
                entry.append(("conv", cur_l, cur_l * 4, cout, cout, 3))
            up.append(entry)
            ch_up = cout
    for entry in up if full else up[n_up - l :]:
        out += entry
    out.append(("conv", l0, l0, chans[0], d.out_channels, 3))
    return out


def kernel_calls(d: UNetDims, l: int = -1) -> dict[str, list[tuple]]:
    """The calls of a pass with budget ``l`` (``l < 0``: FULL), by the
    ``work/<kind>.py`` module that counts them: ``conv`` ``(L_in, L_out,
    cin, cout, k)``, every convolution (the residual blocks' 3x3 and 1x1,
    each transformer's 1x1 ``proj_in``/``proj_out``, the strided and
    upsampling 3x3); ``attention`` ``(Lq, Lk, heads, head_dim)``, each
    transformer's self-attention over its ``L`` positions and
    cross-attention over the ``ctx_len`` text positions; ``matmul`` ``(L,
    cin, cout)``, each transformer's q/k/v/o projections and GEGLU
    feed-forward, the cross-attention's k and v over the text."""
    conv: list[tuple] = []
    attention: list[tuple] = []
    matmul: list[tuple] = []
    for b in blocks(d, l):
        if b[0] == "conv":
            conv.append(b[1:])
        elif b[0] == "res":
            _, p, cin, cout = b
            conv += [(p, p, cin, cout, 3), (p, p, cout, cout, 3)]
            if cin != cout:
                conv.append((p, p, cin, cout, 1))
        elif b[0] == "tf":
            _, p, c = b
            ctx, cd, dh = d.ctx_len, d.cross_attention_dim, c // d.heads
            conv += [(p, p, c, c, 1), (p, p, c, c, 1)]
            attention += [(p, p, d.heads, dh), (p, ctx, d.heads, dh)]
            # self q, k, v, o and cross q; cross k, v over the text; cross o; GEGLU
            matmul += [(p, c, c)] * 5 + [(ctx, cd, c)] * 2
            matmul += [(p, c, c), (p, c, 8 * c), (p, 4 * c, c)]
    return {"conv": conv, "attention": attention, "matmul": matmul}


# ---------------------------------------------------------------------------
# the reference U-Net (jax.numpy, NHWC)
# ---------------------------------------------------------------------------


class UNet:
    """The forward pass on dims ``d``."""

    def __init__(self, d: UNetDims, quant: str | None = None):
        self.d, self.quant = d, quant
        self.q = quantizer(quant)

    # -- primitives -----------------------------------------------------------

    def _weight_product(self, op, x, w):
        """``op(x, w)`` for an activation ``x`` and a weight ``w``, to float32
        accuracy.  The served weights are bfloat16 values, so ``w`` is
        exact in bfloat16 and ``x`` splits exactly into three bfloat16
        parts (24 bits of mantissa): three bfloat16 products summed in
        float32 give what ``"highest"`` gives in half its passes.  The
        control, whose float8 weights carry a float32 scale, takes
        ``"highest"`` itself."""
        import jax
        import jax.numpy as jnp

        if self.quant is not None:
            return op(self.q(x), self.q(w), jax.lax.Precision.HIGHEST, jnp.float32)
        wb = w.astype(jnp.bfloat16)
        hi = x.astype(jnp.bfloat16)
        r = x - hi.astype(jnp.float32)
        mid = r.astype(jnp.bfloat16)
        lo = (r - mid.astype(jnp.float32)).astype(jnp.bfloat16)
        return sum(op(part, wb, jax.lax.Precision.DEFAULT, jnp.float32) for part in (hi, mid, lo))

    def mm(self, a, w):
        import jax.numpy as jnp

        return self._weight_product(
            lambda x, y, prec, out: jnp.matmul(x, y, precision=prec, preferred_element_type=out),
            a, w)

    def conv(self, p, x, k, stride=1):
        import jax

        w = p["w"].reshape(k, k, x.shape[-1], -1)
        pad = (k - 1) // 2

        def op(a, b, prec, out):
            return jax.lax.conv_general_dilated(
                a, b, (stride, stride), [(pad, pad), (pad, pad)],
                dimension_numbers=("NHWC", "HWIO", "NHWC"), precision=prec,
                preferred_element_type=out,
            )

        return self._weight_product(op, x, w) + p["b"]

    def group_norm(self, p, x, silu):
        import jax
        import jax.numpy as jnp

        b, h, w, c = x.shape
        g = self.d.groups
        xg = x.reshape(b, h * w, g, c // g)
        mean = xg.mean(axis=(1, 3), keepdims=True)
        var = ((xg - mean) ** 2).mean(axis=(1, 3), keepdims=True)
        y = ((xg - mean) / jnp.sqrt(var + self.d.norm_eps)).reshape(b, h, w, c)
        y = y * p["scale"] + p["bias"]
        return jax.nn.silu(y) if silu else y

    @staticmethod
    def layer_norm(p, x):
        import jax.numpy as jnp

        mean = x.mean(-1, keepdims=True)
        var = ((x - mean) ** 2).mean(-1, keepdims=True)
        return (x - mean) / jnp.sqrt(var + 1e-5) * p["scale"] + p["bias"]

    def attention(self, q, k, v, o):
        """Softmax attention, in blocks of queries so that no score block
        passes 2**27 elements (512 MiB): rows are independent, so the
        blocks change nothing but the memory it takes."""
        import jax
        import jax.numpy as jnp

        b, lq, c = q.shape
        n = self.d.heads
        dh = c // n
        split = lambda t: t.reshape(b, t.shape[1], n, dh).transpose(0, 2, 1, 3)
        prec = jax.lax.Precision.HIGHEST
        qh, kh, vh = self.q(split(q)), self.q(split(k)), self.q(split(v))
        block = max(1, min(lq, 2**27 // (b * n * kh.shape[2])))
        outs = []
        for s0 in range(0, lq, block):
            s = jnp.einsum("bhqd,bhkd->bhqk", qh[:, :, s0 : s0 + block], kh, precision=prec)
            w = jax.nn.softmax(s / math.sqrt(dh), axis=-1)
            outs.append(jnp.einsum("bhqk,bhkd->bhqd", self.q(w), vh, precision=prec))
        out = jnp.concatenate(outs, axis=2)
        return self.mm(out.transpose(0, 2, 1, 3).reshape(b, lq, c), o)

    # -- blocks ---------------------------------------------------------------

    def res(self, p, x, temb):
        import jax

        h = self.conv(p["conv1"], self.group_norm(p["gn1"], x, True), 3)
        h = h + (self.mm(jax.nn.silu(temb), p["t_proj"]["w"]) + p["t_proj"]["b"])[:, None, None, :]
        h = self.conv(p["conv2"], self.group_norm(p["gn2"], h, True), 3)
        if "skip" in p:
            x = self.conv(p["skip"], x, 1)
        return x + h

    def transformer(self, p, x, ctx):
        import jax
        import jax.numpy as jnp

        b, hh, ww, c = x.shape
        h = self.conv(p["proj_in"], self.group_norm(p["gn"], x, False), 1).reshape(b, hh * ww, c)
        z = self.layer_norm(p["ln1"], h)
        h = h + self.attention(self.mm(z, p["self_q"]), self.mm(z, p["self_k"]),
                               self.mm(z, p["self_v"]), p["self_o"])
        z = self.layer_norm(p["ln2"], h)
        h = h + self.attention(self.mm(z, p["cross_q"]), self.mm(ctx, p["cross_k"]),
                               self.mm(ctx, p["cross_v"]), p["cross_o"])
        z = self.layer_norm(p["ln3"], h)
        gate, val = jnp.split(self.mm(z, p["ff_in"]), 2, axis=-1)
        h = h + self.mm(gate * jax.nn.sigmoid(1.702 * gate) * val, p["ff_out"])
        h = self.conv(p["proj_out"], h.reshape(b, hh, ww, c), 1)
        return h + x

    def time_embedding(self, p, t):
        import jax
        import jax.numpy as jnp

        half = self.d.block_out_channels[0] // 2
        freqs = jnp.exp(-math.log(10000.0) * jnp.arange(half, dtype=jnp.float32) / half)
        ang = t.astype(jnp.float32)[:, None] * freqs[None]
        e = jnp.concatenate([jnp.cos(ang), jnp.sin(ang)], axis=-1)
        tm = p["time_mlp"]
        return self.mm(jax.nn.silu(self.mm(e, tm["w1"]) + tm["b1"]), tm["w2"]) + tm["b2"]

    # -- the pass -------------------------------------------------------------

    def __call__(self, p, x, t, ctx, entry_step=0, entry_feat=None, capture=()):
        """eps [B, H, W, C] of a FULL pass (``entry_step == 0``) or of a
        partial pass entering up-step ``entry_step`` with ``entry_feat``;
        plus the main-branch feature entering each up-step in ``capture``."""
        import jax.numpy as jnp

        d = self.d
        temb = self.time_embedding(p, t)
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
                for tp in e.get("tf", []):
                    h = self.transformer(tp, h, ctx)
                skips.append(h)
            if lvl != d.n_levels - 1 and not (entry_step and len(skips) >= n_skips):
                h = self.conv(next(down)["downsample"], h, 3, stride=2)
                skips.append(h)
        if entry_step == 0:
            m = p["mid"]
            h = self.res(m["res1"], h, temb)
            for tp in m["tf"]:
                h = self.transformer(tp, h, ctx)
            h = self.res(m["res2"], h, temb)
        else:
            h = entry_feat
        captured = {}
        for step in range(entry_step, d.n_up):
            if step in capture:
                captured[step] = h
            e = p["up"][step]
            h = self.res(e["res"], jnp.concatenate([h, skips.pop()], axis=-1), temb)
            for tp in e.get("tf", []):
                h = self.transformer(tp, h, ctx)
            if "upsample" in e:
                h = jnp.repeat(jnp.repeat(h, 2, axis=1), 2, axis=2)
                h = self.conv(e["upsample"], h, 3)
        h = self.group_norm(p["gn_out"], h, True)
        return self.conv(p["conv_out"], h, 3), captured


class Sampler:
    """Runs requests through the reference, one jitted program per branch
    class (the weights are an argument, never a constant).

    Given a request as the client sent it (prompt, seed, quality tier,
    steps), it derives the conditioning and the initial noise as the
    deployment states them, resolves the tier's phase-aware-sampling (PAS)
    plan, and runs the sampler step by step: each step is one
    classifier-free-guided U-Net pass (FULL, or a partial SKETCH/REFINE
    pass entering the up path at the feature the last FULL pass
    captured), then a PNDM update.

    ``run_many`` advances a batch of requests in lockstep, step index by
    step index; at each index every branch class that some request runs
    is one call over a fixed batch of ``batch`` requests (``2 * batch``
    rows with guidance), rows of other requests filled with zeros and
    thrown away.  Each request's result is its own: rows never mix."""

    def __init__(self, config: dict, dims: UNetDims, params32, quant: str | None = None,
                 batch: int = 1):
        import jax
        import jax.numpy as jnp

        self.config, self.d, self.params, self.batch = config, dims, params32, batch
        s = config["serving"]
        self.guidance = s["guidance_scale"]
        self.e_sk = dims.n_up - s["l_sketch"]
        self.e_rf = dims.n_up - s["l_refine"]
        self.acp = alphas_cumprod(config["scheduler"])
        net = UNet(dims, quant)

        def cfg_eps(p, x, t, ctx, entry_step, feat):
            b = x.shape[0]
            x2 = jnp.concatenate([x, x], axis=0)
            t2 = jnp.concatenate([t, t], axis=0)
            ctx2 = jnp.concatenate([ctx, jnp.zeros_like(ctx)], axis=0)
            capture = () if entry_step else (self.e_sk, self.e_rf)
            eps2, cap = net(p, x2, t2, ctx2, entry_step, feat, capture)
            e_c, e_u = eps2[:b], eps2[b:]
            return e_u + self.guidance * (e_c - e_u), cap

        self._call = {
            FULL: jax.jit(functools.partial(cfg_eps, entry_step=0, feat=None)),
            SKETCH: jax.jit(functools.partial(cfg_eps, entry_step=self.e_sk)),
            REFINE: jax.jit(functools.partial(cfg_eps, entry_step=self.e_rf)),
        }

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

        d, n, B = self.d, len(requests), self.batch
        hw = (d.sample_size, d.sample_size, d.in_channels)
        ctxs, xs, plans, tss = [], [], [], []
        for prompt, seed, tier, steps in requests:
            ctx, noise = request_inputs(prompt, seed, d.ctx_len, d.cross_attention_dim,
                                        d.sample_size, d.in_channels)
            ctxs.append(ctx)
            xs.append(noise.astype(np.float64).reshape(hw))
            plans.append(tier_branches(self.config["pas_tiers"][tier], steps))
            tss.append(timesteps(steps, self.config["scheduler"]["num_train_timesteps"]))
        feats: list[dict] = [{} for _ in range(n)]
        ets: list[list] = [[] for _ in range(n)]
        for i in range(max(len(p) for p in plans)):
            for cls in (FULL, SKETCH, REFINE):
                rows = [r for r in range(n) if i < len(plans[r]) and plans[r][i] == cls]
                if not rows:
                    continue
                pos = {r: j for j, r in enumerate(rows)}
                x_b = np.zeros((B,) + hw, np.float32)
                t_b = np.zeros((B,), np.int32)
                for r in rows:
                    x_b[pos[r]], t_b[pos[r]] = xs[r], tss[r][i]
                ctx_r = jnp.asarray(np.stack([ctxs[r] for r in rows]
                                             + [np.zeros_like(ctxs[0])] * (B - len(rows))))
                if cls == FULL:
                    eps, cap = self._call[FULL](self.params, jnp.asarray(x_b), jnp.asarray(t_b),
                                                ctx_r)
                    for r in rows:
                        j = pos[r]
                        feats[r] = {e: (v[j], v[B + j]) for e, v in cap.items()}
                else:
                    entry = self.e_sk if cls == SKETCH else self.e_rf
                    cond = [feats[r][entry][0] for r in rows]
                    unc = [feats[r][entry][1] for r in rows]
                    pad = [jnp.zeros_like(cond[0])] * (B - len(rows))
                    feat = jnp.stack(cond + pad + unc + pad)
                    eps, _ = self._call[cls](self.params, jnp.asarray(x_b), jnp.asarray(t_b),
                                             ctx_r, feat=feat)
                eps = np.asarray(eps, np.float64)
                for r in rows:
                    t = int(tss[r][i])
                    t_prev = int(tss[r][i + 1]) if i + 1 < len(tss[r]) else -1
                    xs[r] = pndm_update(self.acp, ets[r], xs[r], eps[pos[r]], t, t_prev)
        return [x.reshape(-1, d.in_channels).astype(np.float32) for x in xs]
