"""Percent of the chip's bf16 peak that useful model FLOPs reached over
the window: each tick inside it counts the lanes it advanced x 2 CFG rows
x its branch class's analytic FLOPs per row (the architecture's
``class_flops``), over the window's length times the peak of
``peaks.json``."""


def read(run):
    ticks = run.window_ticks()
    if not ticks or not run.peaks:
        return None
    s = run.cell.config["serving"]
    per_row = run.arch.class_flops(run.dims, s["l_sketch"], s["l_refine"])
    useful = sum(2 * t.advanced * per_row[t.cls] for t in ticks)
    return 100.0 * useful / ((run.t1 - run.t0) * run.peaks["bf16_flops_per_s"])
