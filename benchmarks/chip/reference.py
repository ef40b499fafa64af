"""Plain float32 reference of a served Stable Diffusion request.

It imports nothing of the program.  Given a request as the client sent it
(prompt, seed, quality tier, steps) and the weights the benchmark made, it
derives the conditioning and the initial noise as the deployment states
them, resolves the tier's phase-aware-sampling (PAS) plan, and runs the
sampler step by step: each step is one classifier-free-guided U-Net pass
(FULL, or a partial SKETCH/REFINE pass entering the up path at the feature
the last FULL pass captured), then a PNDM update.

The U-Net is written out in ``jax.numpy`` on NHWC arrays with
``lax.conv_general_dilated`` convolutions, two-pass group norms and plain
softmax attention, every matmul and convolution to float32 accuracy
(``"highest"``, or its exact equivalent for bfloat16 weights).  It reads the weights by their names in the served parameter
tree, which is the only thing it shares with the program.

``quant="fp8"`` is the control: every matmul and convolution operand is
rounded to float8 (e4m3, one absmax scale per tensor) first, the step that
would tempt a lower-precision serving path.
"""
from __future__ import annotations

import functools
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
# the U-Net (jax.numpy, NHWC)
# ---------------------------------------------------------------------------


def _quantizer(quant: str | None):
    import jax.numpy as jnp

    if quant is None:
        return lambda a: a
    if quant != "fp8":
        raise ValueError(f"unknown reference precision {quant!r}")

    def q(a):
        s = jnp.maximum(jnp.max(jnp.abs(a)), 1e-30) / 448.0
        return (a / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s

    return q


class UNet:
    """The forward pass on dims ``d`` (``spec.UNetDims``)."""

    def __init__(self, d, quant: str | None = None):
        self.d, self.quant = d, quant
        self.q = _quantizer(quant)

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

    ``run_many`` advances a batch of requests in lockstep, step index by
    step index; at each index every branch class that some request runs
    is one call over a fixed batch of ``batch`` requests (``2 * batch``
    rows with guidance), rows of other requests filled with zeros and
    thrown away.  Each request's result is its own: rows never mix."""

    def __init__(self, config: dict, dims, params32, quant: str | None = None,
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
