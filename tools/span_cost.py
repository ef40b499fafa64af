"""Host cost of one ``ServingMetrics.span``, with the profiler off and on.

    PYTHONPATH=src python tools/span_cost.py [--n 200000]

Times ``--n`` empty spans carrying two ids (as ``engine.dispatch`` does)
with no profiler trace running, then inside ``jax.profiler.trace``, and an
empty loop for reference.  Prints one JSON line: microseconds per span,
the loop's cost taken off, and the platform the process saw.
"""
from __future__ import annotations

import argparse
import json
import tempfile
import time

import jax

from repro.serving.metrics import ServingMetrics


def per_iteration_us(n: int, body) -> float:
    t0 = time.perf_counter()
    body(n)
    return (time.perf_counter() - t0) / n * 1e6


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--n", type=int, default=200_000)
    args = ap.parse_args()
    m = ServingMetrics()

    def loop(n):
        for _ in range(n):
            pass

    def spans(n):
        for _ in range(n):
            with m.span("engine.dispatch", cls="full", lanes=4):
                pass

    spans(1000)  # first use imports the profiler bindings
    bare = per_iteration_us(args.n, loop)
    off = per_iteration_us(args.n, spans)
    with tempfile.TemporaryDirectory() as d:
        with jax.profiler.trace(d):
            on = per_iteration_us(args.n, spans)
    print(json.dumps({
        "platform": jax.devices()[0].platform,
        "n": args.n,
        "span_us_profiler_off": off - bare,
        "span_us_profiler_on": on - bare,
        "loop_us": bare,
    }), flush=True)


if __name__ == "__main__":
    main()
