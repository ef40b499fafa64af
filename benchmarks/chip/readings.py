"""The readings a cell's correctness limit is set from, in one process.

Usage (on the chip)::

    python benchmarks/chip/readings.py --workload sd_v14.tiers-steady \\
        --seeds 101,102,...,112 --control-seeds 3 --seconds 20

Set-up once; then for each seed: the seed's weights swapped into the
engine (the micro-step takes them as an argument, so nothing compiles
again), one window of the cell's own traffic at the cell's own load and
its drain, the sample that a benchmark run would compare drawn the same
way, and the verdict of ``check.judge`` on it against the float32
reference of the configuration's ``arch/<name>.py``: the sound program's
reading.  For the first ``--control-seeds`` seeds the same requests also
run through that reference at float8 (``quant="fp8"``), put in the
program's place, and ``check.judge`` gives the control's verdict and
reading.  One JSON line per seed; ``PERF.md`` records the readings and the
limit set between them.
"""
from __future__ import annotations

import argparse
import asyncio
import dataclasses
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
for p in (ROOT, ROOT / "src"):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))

from benchmarks.chip import check, run, spec, system  # noqa: E402


async def readings(served, cell, seeds: list[int], seconds: float, control_seeds: int):
    """One line per seed (see the module docstring)."""
    await served.start()
    await run.warm_up(served, cell, seeds[0])
    arch = spec.arch(cell.config)
    dims = arch.dims(cell.config)
    limits = check.load_limits(cell.name)
    quiet = lambda msg: None  # noqa: E731
    f32 = fp8 = None
    for i, seed in enumerate(seeds):
        if i:
            served.params = system.make_weights(served.ucfg, seed)
            served.engine._params = served.params
        served.latents.clear()
        out = await run.window(served, cell.traffic, seed, seconds)
        t = time.perf_counter()
        rec = run.record(cell, out, served, seconds, {})
        picked = check.sample(rec.window_reqs(), cell.traffic["check_sample"], seed)
        p32 = check.params_f32(served.params)
        if f32 is None:
            n = cell.traffic["check_sample"]
            f32 = arch.Sampler(cell.config, dims, p32, batch=n)
            fp8 = arch.Sampler(cell.config, dims, p32, quant="fp8", batch=n)
        f32.params = fp8.params = p32
        asked = [(r.prompt, r.seed, r.tier, r.steps) for r in picked]
        wants = f32.run_many(asked)
        sound = check.judge(cell, picked, served.latents, wants, limits, quiet)
        row = {"seed": seed, "requests": [[r.tier, r.steps] for r in picked],
               "program": sound["gaps"], "program_correct": sound["correct"]}
        if i < control_seeds:
            made = fp8.run_many(asked)
            stand_ins = [dataclasses.replace(r, digest=check.digest(c))
                         for r, c in zip(picked, made)]
            control = check.judge(cell, stand_ins, {r.rid: c for r, c in zip(picked, made)},
                                  wants, limits, quiet)
            row.update(control=control["gaps"], control_correct=control["correct"])
        del p32
        row["reference_s"] = time.perf_counter() - t
        print(json.dumps(row), flush=True)
    await served.stop()


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True, help="comma-separated")
    ap.add_argument("--control-seeds", type=int, default=3)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args()
    cell = spec.load_cell(args.workload)
    seeds = [int(s) for s in args.seeds.split(",")]
    system.configure_jax()
    run.log(f"device {system.device_stamp(cell.chips)}")
    served = system.Served(cell.config, seeds[0])
    asyncio.run(readings(served, cell, seeds, args.seconds, args.control_seeds))


if __name__ == "__main__":
    main()
