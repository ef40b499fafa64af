"""The benchmark's reading of ``BENCHMARK.json`` and of one cell's files.

A cell (an entry of ``workloads``) names a configuration and a traffic mix;
each is a JSON file of its own (``configs/<file>``, ``traffic/<name>.json``),
and each metric is a reader ``metrics/<name>.py``.  Nothing here knows a
cell, a configuration or a metric by name: a later cell adds files and
entries, never code.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
from pathlib import Path

HERE = Path(__file__).resolve().parent
#: the checkout root (``benchmarks/chip/`` is two levels below it)
ROOT = HERE.parents[1]


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


def unet_dims(config: dict) -> UNetDims:
    """Diffusers keys -> :class:`UNetDims`.  SD 1.x's ``attention_head_dim``
    is the number of heads (a naming quirk of that release), the time
    embedding is 4x the first block width, and the text encoder's 77
    positions set the conditioning length."""
    down = config["down_block_types"]
    return UNetDims(
        in_channels=config["in_channels"],
        out_channels=config["out_channels"],
        block_out_channels=tuple(config["block_out_channels"]),
        layers_per_block=config["layers_per_block"],
        attn_levels=tuple(i for i, t in enumerate(down) if t.startswith("CrossAttn")),
        heads=config["attention_head_dim"],
        cross_attention_dim=config["cross_attention_dim"],
        ctx_len=config["serving"]["text_max_length"],
        time_dim=4 * config["block_out_channels"][0],
        groups=config["norm_num_groups"],
        norm_eps=config["norm_eps"],
        sample_size=config["sample_size"],
    )


def load_benchmark() -> dict:
    with open(ROOT / "BENCHMARK.json") as f:
        return json.load(f)


@dataclasses.dataclass
class Cell:
    """One workload with its configuration, traffic and metric entries."""

    name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: list[dict]
    per_layer: list[dict]


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(name: str) -> Cell:
    bench = load_benchmark()
    by_name = {w["name"]: w for w in bench["workloads"]}
    if name not in by_name:
        raise SystemExit(f"unknown workload {name!r}; BENCHMARK.json has {sorted(by_name)}")
    w = by_name[name]
    (cfg_entry,) = [c for c in bench["configs"] if c["name"] == w["config"]]
    with open(ROOT / cfg_entry["file"]) as f:
        config = json.load(f)
    with open(HERE / "traffic" / f"{w['traffic']}.json") as f:
        traffic = json.load(f)
    return Cell(
        name=name,
        chips=w["chips"],
        config=config,
        traffic=traffic,
        end_to_end=[m for m in bench["end_to_end"] if _applies(m, name)],
        per_layer=[m for m in bench["per_layer"] if _applies(m, name)],
    )


def load_json(relpath: str) -> dict:
    with open(HERE / relpath) as f:
        return json.load(f)


def load_module(kind: str, name: str):
    """``<kind>/<name>.py`` as a module (names may hold dots)."""
    path = HERE / kind / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"chipbench_{kind}_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod
