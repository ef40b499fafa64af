"""Rate sweep of an open-loop cell, after one set-up, to find its knee.

Usage (on the chip)::

    python benchmarks/chip/sweep.py --workload sd_v14.tiers-steady --seed 7 \\
        --seconds 51 --rates 0.4,0.5,0.6 --schedules 2

For each offered rate, in rising order, one window of the cell's traffic
at that rate and its drain for each of ``--schedules`` arrival schedules
(the traffic file's ``schedule_seed`` and the next ones); one JSON line
per window: requests offered and
finished, the latency median and 95th percentile, the backlog left at the
window's close (sent, not finished), and the median latency of the
window's last quarter of arrivals over that of its first quarter.  A rate
past the knee leaves a backlog that grows through the window, so its
late arrivals wait longer than its early ones.  The cell's traffic file
then fixes ``rate_per_s`` at about four fifths of the knee.
"""
from __future__ import annotations

import argparse
import asyncio
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
for p in (ROOT, ROOT / "src"):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))

from benchmarks.chip import load, run, spec, system  # noqa: E402


def summarize(rate: float, schedule: int, out: dict) -> dict:
    reqs = [r for r in out["reqs"] if r.sent is not None]
    lat = [r.done - r.due_abs if r.status == "done" else float("inf") for r in reqs]
    backlog = sum(r.done is None or r.done > out["t1"] for r in reqs)
    by_due = sorted(reqs, key=lambda r: r.due)
    q = max(1, len(by_due) // 4)

    def med(rs):
        xs = [r.done - r.due_abs if r.status == "done" else float("inf") for r in rs]
        return load.percentile(xs, 50)

    return {
        "rate_per_s": rate,
        "schedule_seed": schedule,
        "offered": len(reqs),
        "finished": sum(r.status == "done" for r in reqs),
        "latency_p50_s": load.percentile(lat, 50),
        "latency_p95_s": load.percentile(lat, 95),
        "backlog_at_close": backlog,
        "late_over_early_p50": med(by_due[-q:]) / med(by_due[:q]),
    }


async def sweep(served, cell, seed: int, seconds: float, rates: list[float],
                schedules: int) -> list[dict]:
    await served.start()
    await run.warm_up(served, cell, seed)
    rows = []
    for i, rate in enumerate(rates):
        for j in range(schedules):
            schedule = cell.traffic["schedule_seed"] + j
            traffic = dict(cell.traffic, rate_per_s=rate, schedule_seed=schedule)
            out = await run.window(served, traffic, seed + i * schedules + j, seconds)
            rows.append(summarize(rate, schedule, out))
            print(json.dumps(rows[-1]), flush=True)
    await served.stop()
    return rows


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", required=True, help="comma-separated requests per second")
    ap.add_argument("--schedules", type=int, default=1)
    args = ap.parse_args()
    cell = spec.load_cell(args.workload)
    if cell.traffic["loop"] != "open":
        raise SystemExit("a rate sweep needs an open-loop cell")
    system.configure_jax()
    run.log(f"device {system.device_stamp(cell.chips)}")
    served = system.Served(cell.config, args.seed)
    rates = sorted(float(x) for x in args.rates.split(","))
    asyncio.run(sweep(served, cell, args.seed, args.seconds, rates, args.schedules))


if __name__ == "__main__":
    main()
