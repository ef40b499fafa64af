"""Chip benchmark: one run of one cell of ``BENCHMARK.json``.

Usage::

    python benchmarks/chip/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Set-up (timed as ``setup_s``): JAX on the TPU, the weights made on the
device from ``--seed``, the engine, driver and HTTP frontend built, and
every program of the cell warmed by a few short requests through the
served path.  Then the window: the cell's traffic for ``--seconds``
seconds, sent over HTTP by the benchmark's own client; with ``--trace 1``
under the JAX profiler.  After the window every request still in flight
is waited for, the peak device memory is read, the program's state is
freed, and a sample of the finished requests, drawn from the seed, is
compared with the plain float32 reference of the configuration's
architecture (``arch/<name>.py``).

The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics`` (the cell's end-to-end metrics with
``--trace 0``, its per-layer metrics with ``--trace 1``), ``device``,
with ``--trace 1`` also ``breakdown``, and last ``checks``: each number
compared beside its limit.  With no TPU, or fewer chips than the cell
asks for, it exits non-zero and prints no result.
"""
from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import asyncio  # noqa: E402
import dataclasses  # noqa: E402
import json  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[2]
for p in (ROOT, ROOT / "src"):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))

from benchmarks.chip import check, load, spec, system  # noqa: E402

#: how long after the window requests still in flight are waited for
DRAIN_S = 60.0


def log(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


@dataclasses.dataclass
class RunRecord:
    """Everything a metric reader may read about one run."""

    cell: spec.Cell
    arch: object  # the configuration's ``arch/<name>.py`` module
    dims: object  # its ``dims`` of the configuration
    seconds: float
    setup_s: float
    reqs: list
    t0: float
    t1: float
    stats0: dict
    stats1: dict
    stats_end: dict
    ticks: list
    peaks: dict
    trace: object | None = None

    def window_reqs(self) -> list:
        """Requests the window offered: everything sent, less those the
        closed loop withdrew at the close before the engine started them."""
        return [r for r in self.reqs if r.sent is not None and r.status != "withdrawn"]

    def window_ticks(self) -> list:
        return [t for t in self.ticks if self.t0 <= t.t0 < self.t1 and t.cls is not None]


async def warm_up(served: system.Served, cell: spec.Cell, seed: int) -> None:
    """Run every program the cell's traffic uses once: one short request
    per lane, in the traffic's tiers (so every branch class runs and every
    lane retires), and, while those hold the lanes, one request of each
    step count the traffic can draw in a PAS tier, withdrawn as soon as it
    is queued (the host plans a PAS request with a small program sized by
    its step count)."""
    traffic = cell.traffic
    warm = load.make_requests(
        dict(traffic, steps=[{"p": 1.0, "value": traffic["warmup_steps"]}]),
        seed + 1, served.engine_config.n_lanes, tag="warm-up ",
    )
    tasks = [asyncio.create_task(load.generate(served.port, r)) for r in warm]
    pas = sorted(t for t in traffic["tiers"] if cell.config["pas_tiers"][t] is not None)
    if pas:
        while any(r.first_step is None and r.status == "pending" for r in warm):
            await asyncio.sleep(0.01)
        log(f"warm-up requests admitted at {time.perf_counter() - T_PROCESS:.3f} s")
        for n in load.step_support(traffic["steps"]):
            shape = load.Request(k=-1, prompt=f"warm-up plan {n}", seed=0, tier=pas[0], steps=n)
            await load.generate(served.port, shape, withdraw_when_queued=True)
    await asyncio.gather(*tasks)
    bad = [r for r in warm if r.status != "done"]
    if bad:
        raise RuntimeError(f"warm-up request failed: {bad[0].error}")


async def window(served: system.Served, traffic: dict, seed: int, seconds: float,
                 trace_dir: str | None = None) -> dict:
    """One window of ``traffic`` and its drain; with ``trace_dir``, under
    the profiler, the window itself marked by a ``bench.window`` span."""
    import jax

    if trace_dir is not None:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0  # Python calls untraced: they would slow the host path
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
    stats0 = await served.stats()
    t0 = time.perf_counter()
    if traffic["loop"] == "open":
        reqs = load.open_schedule(traffic, seed, seconds)
        driver = load.run_open(served.port, reqs, t0, seconds, DRAIN_S)
    else:
        reqs = load.make_requests(traffic, seed, traffic["max_requests"])
        driver = load.run_closed(served.port, reqs, t0, seconds, traffic["outstanding"], DRAIN_S)
    span = jax.profiler.TraceAnnotation("bench.window")
    span.__enter__()
    task = asyncio.create_task(driver)
    await asyncio.sleep(max(t0 + seconds - time.perf_counter(), 0.0))
    span.__exit__(None, None, None)
    t1 = time.perf_counter()
    stats1 = await served.stats()
    await task
    stats_end = await served.stats()
    if trace_dir is not None:
        jax.profiler.stop_trace()
    return dict(reqs=reqs, t0=t0, t1=t1, stats0=stats0, stats1=stats1, stats_end=stats_end)


async def serve(served: system.Served, cell: spec.Cell, seed: int, seconds: float,
                trace_dir: str | None) -> dict:
    """Set-up's last part, the window and the drain, in one event loop."""
    await served.start()
    log(f"server up at {time.perf_counter() - T_PROCESS:.3f} s")
    await warm_up(served, cell, seed)
    setup_s = time.perf_counter() - T_PROCESS
    out = await window(served, cell.traffic, seed, seconds, trace_dir)
    await served.stop()
    return dict(out, setup_s=setup_s)


def record(cell: spec.Cell, out: dict, served: system.Served, seconds: float,
           peaks: dict) -> RunRecord:
    arch = spec.arch(cell.config)
    return RunRecord(
        cell=cell, arch=arch, dims=arch.dims(cell.config), seconds=seconds,
        setup_s=out.get("setup_s", 0.0), reqs=out["reqs"], t0=out["t0"], t1=out["t1"],
        stats0=out["stats0"], stats1=out["stats1"], stats_end=out["stats_end"],
        ticks=served.ticks, peaks=peaks,
    )


def run(cell: spec.Cell, seed: int, seconds: float, trace: bool, *,
        require_tpu: bool = True, limits: dict | None = None) -> dict:
    """One run of ``cell``; returns the result object."""
    hooks = system.configure_jax()
    device = system.device_stamp(cell.chips, require_tpu)
    log(f"device {device} at {time.perf_counter() - T_PROCESS:.3f} s; compile cache "
        f"{system.CACHE_DIR}; {system.env_summary()}")
    peaks = spec.load_json("peaks.json")
    if device["kind"] not in peaks and require_tpu:
        raise SystemExit(f"no peak numbers for device kind {device['kind']!r} in peaks.json")
    served = system.Served(cell.config, seed)
    log(f"weights and engine built at {time.perf_counter() - T_PROCESS:.3f} s")
    trace_dir = None
    if trace:
        system.RUNS_DIR.mkdir(exist_ok=True)
        trace_dir = tempfile.mkdtemp(prefix="trace-", dir=system.RUNS_DIR)
    out = asyncio.run(serve(served, cell, seed, seconds, trace_dir))
    compiles = hooks["compiles"]
    in_window = [c for c in compiles if out["t0"] <= c[0] < out["t1"]]
    log(f"set-up {out['setup_s']:.3f} s; persistent cache {hooks['cache']['hits']} hits, "
        f"{hooks['cache']['misses']} misses; compiles or cache loads before the window: "
        f"{system.compile_summary([c for c in compiles if c[0] < out['t0']])}")
    log(f"compiles inside the window: {len(in_window)}"
        + (f" ({sorted({c[2] for c in in_window})})" if in_window else ""))
    device["memory_peak_bytes"] = served.memory_peak_bytes()

    rec = record(cell, out, served, seconds, peaks.get(device["kind"], {}))
    latents = served.latents
    params = served.params
    served.release()
    t_ref = time.perf_counter()
    checks = check.compare(cell, rec, latents, params, seed,
                           limits if limits is not None else check.load_limits(cell.name), log)
    del params
    log(f"reference compiles or cache loads: {system.compile_summary(compiles, since=t_ref)}")
    breakdown = None
    if trace:
        from benchmarks.chip import trace as TR

        rec.trace = TR.reduce(TR.find_xplane(trace_dir))
        shutil.rmtree(trace_dir, ignore_errors=True)
        device["busy_s"] = rec.trace.busy_s
        device["window_s"] = rec.trace.window_s
        breakdown = rec.trace.breakdown()
    metrics = {}
    for m in cell.per_layer if trace else cell.end_to_end:
        value = spec.load_module("metrics", m["name"]).read(rec)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    wreqs = rec.window_reqs()
    result = {
        "correct": checks["correct"],
        "attempted": len(wreqs),
        "failed": sum(r.status == "failed" for r in wreqs),
        "metrics": metrics,
        "device": device,
    }
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["checks"] = checks["numbers"]
    log(f"run ends at {time.perf_counter() - T_PROCESS:.3f} s; persistent cache "
        f"{hooks['cache']['hits']} hits, {hooks['cache']['misses']} misses in all")
    for name, c in checks["numbers"].items():
        log(f"check {name}: {c['value']!r} (limit {c['limit']!r})")
    return result


def main(argv: list[str] | None = None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    cell = spec.load_cell(args.workload)
    result = run(cell, args.seed, args.seconds, bool(args.trace))
    print(json.dumps(result), flush=True)


if __name__ == "__main__":
    main()
