"""Percent of the U-Net rows the micro-step computed inside the window
that no lane kept: ``1 - 2 * lane_steps_advanced / unet_rows`` (two CFG
rows per advanced lane) from ``/stats`` counters at the window's edges.
``None`` where the program has no ``unet_rows`` counter."""


def read(run):
    a, b = run.stats0, run.stats1
    if "unet_rows" not in a or "unet_rows" not in b:
        return None
    rows = b["unet_rows"] - a["unet_rows"]
    if rows <= 0:
        return None
    advanced = b["lane_steps_advanced"] - a["lane_steps_advanced"]
    return 100.0 * (1.0 - 2.0 * advanced / rows)
