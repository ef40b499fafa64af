"""Arithmetic the metric readers (``metrics/<name>.py``) share.

Each reader is ``read(run) -> float | None`` over a ``run.RunRecord``; it
returns ``None`` when the run holds nothing for it to read, and the
harness then leaves the metric out of the line.
"""
from __future__ import annotations

import math

from benchmarks.chip import load


def latencies(run) -> list[float]:
    """Due time to ``done`` per request the window offered; ``inf`` for
    one that failed."""
    return [r.done - r.due_abs if r.status == "done" else math.inf for r in run.window_reqs()]


def counter_delta(a: dict, b: dict, key: str) -> int:
    return b[key] - a[key]


def p95(values: list[float]) -> float | None:
    return load.percentile(values, 95) if values else None
