"""The benchmark's reading of ``BENCHMARK.json`` and of one cell's files.

A cell (an entry of ``workloads``) names a configuration and a traffic mix;
each is a JSON file of its own (``configs/<file>``, ``traffic/<name>.json``),
and each metric is a reader ``metrics/<name>.py``.  A configuration file
names its architecture under ``"arch"``: a module ``arch/<arch>.py`` that
exports ``dims`` (its reading of the file), ``program_config`` (the
program's model config), ``class_flops`` and ``kernel_calls`` (its work
model) and ``Sampler`` (its plain reference).  Nothing here knows a cell,
a configuration, an architecture or a metric by name: a later cell adds
files and entries, never code.
"""
from __future__ import annotations

import dataclasses
import functools
import importlib.util
import json
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parent
#: the checkout root (``benchmarks/chip/`` is two levels below it)
ROOT = HERE.parents[1]


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
    arch(config).dims(config)  # refuse a configuration its module would misread
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
    """``<kind>/<name>.py`` as a module (names may hold dots), registered in
    ``sys.modules`` as it runs, as dataclasses and pickling expect."""
    path = HERE / kind / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"chipbench_{kind}_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = mod
    spec.loader.exec_module(mod)
    return mod


def arch(config: dict):
    """The architecture module a configuration file names under ``"arch"``."""
    name = config.get("arch")
    if not isinstance(name, str) or not name.isidentifier() \
            or not (HERE / "arch" / f"{name}.py").is_file():
        known = sorted(p.stem for p in (HERE / "arch").glob("*.py"))
        raise ValueError(f"configuration {config.get('name')!r}: key \"arch\" is {name!r}; "
                         f"it must name a module arch/<arch>.py, one of {known}")
    return _arch(name)


@functools.cache
def _arch(name: str):
    """One module object per architecture, so that its classes stay one."""
    return load_module("arch", name)
