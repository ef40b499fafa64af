"""Percent of lane slots inside the window held by a request that the
tick did not advance, because the tick ran another branch class:
``(lane_slots_active - lane_steps_advanced) / (micro_steps * lanes)`` from
``/stats`` counters at the window's edges.  ``None`` where the program
has no ``lane_slots_active`` counter."""


def read(run):
    a, b = run.stats0, run.stats1
    if "lane_slots_active" not in a or "lane_slots_active" not in b:
        return None
    ticks = b["micro_steps"] - a["micro_steps"]
    if ticks <= 0:
        return None
    held = b["lane_slots_active"] - a["lane_slots_active"]
    advanced = b["lane_steps_advanced"] - a["lane_steps_advanced"]
    return 100.0 * (held - advanced) / (ticks * run.cell.config["serving"]["lanes"])
