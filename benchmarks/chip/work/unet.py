"""Analytic multiply-accumulate count of one U-Net pass per latent row.

A copy of the program's analytic MAC model (``core/framework.py``:
``unet_mac_breakdown`` and ``cost_function``) on the benchmark's own
reading of the configuration, so that a change to the program cannot move
the numerator of ``mfu``.  A FULL pass runs the whole network; a partial
pass with budget ``l`` (SKETCH: ``l_sketch``, REFINE: ``l_refine``) runs
``conv_in``, the first ``l - 1`` down entries, the top ``l`` up-steps and
``conv_out``.
"""
from __future__ import annotations

import dataclasses


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


def skip_channels(d) -> list[int]:
    """Channels of the skips the down path produces, in production order."""
    chans = list(d.block_out_channels)
    out = [chans[0]]
    for lvl, cout in enumerate(chans):
        out += [cout] * d.layers_per_block
        if lvl != d.n_levels - 1:
            out.append(cout)
    return out


def breakdown(d) -> MACBreakdown:
    """MACs per row of each block of the U-Net with dims ``d``
    (:class:`spec.UNetDims`)."""
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


def class_flops(d, l_sketch: int, l_refine: int) -> dict[str, int]:
    """Model FLOPs (2 per MAC) of one row's FULL / SKETCH / REFINE pass."""
    br = breakdown(d)
    return {
        "full": 2 * br.total,
        "sketch": 2 * br.partial(l_sketch),
        "refine": 2 * br.partial(l_refine),
    }


def blocks(d, l: int = -1) -> list[tuple]:
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
