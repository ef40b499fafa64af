"""Percent of lane slots a tick advanced inside the window:
``lane_steps_advanced / (micro_steps * lanes)`` from ``/stats`` counters
at the window's edges."""
from benchmarks.chip import readers


def read(run):
    ticks = readers.counter_delta(run.stats0, run.stats1, "micro_steps")
    adv = readers.counter_delta(run.stats0, run.stats1, "lane_steps_advanced")
    if ticks <= 0:
        return None
    return 100.0 * adv / (ticks * run.cell.config["serving"]["lanes"])
