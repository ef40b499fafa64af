"""Reduction of a JAX profiler trace (``.xplane.pb``) to device metrics.

Read with ``jax.profiler.ProfileData`` alone.  A device plane
(``/device:TPU:<n>``) holds an ``XLA Modules`` line (one event per
execution of a compiled program, named ``<program>(<id>)``) and an
``XLA Ops`` line (one event per operation, named by its HLO text,
``%<instruction>.<n> = ...``; a Pallas kernel's instruction is named after
its jitted wrapper, ``uniconv``, ``flash_attention``, ``stream_group_norm``).
Operations nest there: a ``conditional`` spans the branch it runs, so each
operation is charged its self time, its span less its children's.  Host
planes hold one line per thread, with the ``bench.*`` spans the benchmark
opens.

Everything is clipped to the window: the ``bench.window`` host span when
there is one, else the whole trace.  Busy time is the union of operation
intervals on a device, averaged over the devices; idle gaps are the holes
in that union, each labelled with the innermost ``bench.*`` host span
around its midpoint (else the shortest host event there).
"""
from __future__ import annotations

import dataclasses
import glob
import os
import re

DEVICE_PLANE = re.compile(r"^/device:(TPU|GPU):\d+$")
_SUFFIX = re.compile(r"\(\d+\)$")
_INSTRUCTION = re.compile(r"^%?([A-Za-z_][\w\-]*?)(\.\d+)?(\s|=|$)")


def op_kind(name: str) -> str:
    """``%flash_attention.50 = f32[...] custom-call(...)`` -> ``flash_attention``."""
    m = _INSTRUCTION.match(name)
    return m.group(1) if m else name


def _self_times(events: list[tuple[int, int, str]]) -> list[tuple[int, int, str, int]]:
    """(start, end, name, self ns) of nested events: each event's span less
    the spans of the events directly inside it."""
    out: list[list] = []
    stack: list[list] = []
    for s, e, name in sorted(events, key=lambda x: (x[0], -x[1])):
        while stack and s >= stack[-1][1]:
            stack.pop()
        item = [s, e, name, e - s]
        if stack:
            stack[-1][3] -= e - s
        stack.append(item)
        out.append(item)
    return [tuple(x) for x in out]


def find_xplane(log_dir: str) -> str:
    paths = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"), recursive=True)
    if len(paths) != 1:
        raise FileNotFoundError(f"expected one .xplane.pb under {log_dir}, found {paths}")
    return paths[0]


def _union(intervals: list[tuple[int, int]]) -> list[tuple[int, int]]:
    out: list[list[int]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def _clip(s: int, e: int, lo: int, hi: int) -> int:
    return max(0, min(e, hi) - max(s, lo))


@dataclasses.dataclass
class Reduction:
    window_s: float
    busy_s: float  # mean over devices
    n_devices: int
    #: program name -> (executions starting in the window, seconds inside it)
    programs: dict[str, tuple[int, float]]
    #: operation kind -> self seconds inside the window (summed over devices)
    ops: dict[str, float]
    #: (seconds, label) of the longest idle gaps on the first device
    gaps: list[tuple[float, str]]

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s

    def program(self, prefix: str) -> tuple[int, float]:
        """(executions, seconds) of the programs whose name starts with
        ``prefix``, over all devices."""
        n = sum(c for k, (c, _) in self.programs.items() if k.startswith(prefix))
        return n, sum(s for k, (_, s) in self.programs.items() if k.startswith(prefix))

    def op_seconds(self, pattern: str) -> float:
        """Self seconds of the operations whose kind matches ``pattern`` (a
        regular expression matched against the whole kind)."""
        rx = re.compile(pattern)
        return sum(s for k, s in self.ops.items() if rx.fullmatch(k))

    def breakdown(self) -> dict:
        top = sorted(self.ops.items(), key=lambda kv: -kv[1])[:10]
        return {
            "device_ops": [[k, s] for k, s in top],
            "idle_gaps": [[label, s] for s, label in self.gaps[:10]],
        }


def _host_events(data) -> list[tuple[int, int, str]]:
    out = []
    for plane in data.planes:
        if DEVICE_PLANE.match(plane.name):
            continue
        for line in plane.lines:
            for ev in line.events:
                out.append((ev.start_ns, ev.end_ns, ev.name))
    return out


def _label(mid: int, host: list[tuple[int, int, str]]) -> str:
    around = [(e - s, name) for s, e, name in host if s <= mid < e]
    ours = [x for x in around if x[1].startswith("bench.") and x[1] != "bench.window"]
    if ours:
        return min(ours)[1]
    others = [x for x in around if x[1] != "bench.window"]
    return min(others)[1] if others else "no host span"


def reduce(path: str, window: str | None = "bench.window") -> Reduction:
    import jax

    data = jax.profiler.ProfileData.from_file(path)
    host = _host_events(data)
    spans = [(s, e) for s, e, name in host if name == window]
    devices = [p for p in data.planes if DEVICE_PLANE.match(p.name)]
    if not devices:
        raise ValueError(f"no device plane in {path}")
    if spans:
        lo, hi = spans[0]
    else:
        evs = [(ev.start_ns, ev.end_ns) for p in devices for ln in p.lines for ev in ln.events]
        lo, hi = min(s for s, _ in evs), max(e for _, e in evs)
    programs: dict[str, list] = {}
    ops: dict[str, float] = {}
    busy = []
    gaps: list[tuple[float, str]] = []
    for i, plane in enumerate(sorted(devices, key=lambda p: p.name)):
        lines = {ln.name: ln for ln in plane.lines}
        op_line = lines.get("XLA Ops") or lines.get("XLA Modules")
        intervals = []
        if op_line is not None:
            evs = [(ev.start_ns, ev.end_ns, ev.name) for ev in op_line.events
                   if _clip(ev.start_ns, ev.end_ns, lo, hi)]
            for s, e, name, self_ns in _self_times(evs):
                intervals.append((max(s, lo), min(e, hi)))
                share = _clip(s, e, lo, hi) / (e - s) if e > s else 0.0
                kind = op_kind(name)
                ops[kind] = ops.get(kind, 0.0) + self_ns * share * 1e-9
        mod_line = lines.get("XLA Modules")
        if mod_line is not None:
            for ev in mod_line.events:
                inside = _clip(ev.start_ns, ev.end_ns, lo, hi)
                if not inside:
                    continue
                acc = programs.setdefault(_SUFFIX.sub("", ev.name), [0, 0.0])
                acc[0] += lo <= ev.start_ns < hi
                acc[1] += inside * 1e-9
        merged = _union(intervals)
        busy.append(sum(e - s for s, e in merged) * 1e-9)
        if i == 0:
            edges = [lo] + [x for iv in merged for x in iv] + [hi]
            holes = [(edges[k], edges[k + 1]) for k in range(0, len(edges), 2)]
            holes = sorted((h for h in holes if h[1] > h[0]), key=lambda h: h[0] - h[1])[:10]
            gaps = [((e - s) * 1e-9, _label((s + e) // 2, host)) for s, e in holes]
    return Reduction(
        window_s=(hi - lo) * 1e-9,
        busy_s=sum(busy) / len(busy),
        n_devices=len(devices),
        programs={k: (c, s) for k, (c, s) in programs.items()},
        ops=ops,
        gaps=gaps,
    )
