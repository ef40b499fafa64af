"""Percent of the chip's bf16 peak that the micro-step program reached
while it ran: every row each tick inside the window ran, masked lanes
included (lanes x 2 CFG rows x the analytic FLOPs per row of the tick's
branch class, the architecture's ``class_flops``, from the
``ticks_by_class`` counter at the window's edges), over the device
seconds of the ``jit_micro_step`` programs in the window times the peak
of ``peaks.json``.  ``None`` without a trace or where the program has no
``ticks_by_class`` counter."""


def read(run):
    if run.trace is None or not run.peaks:
        return None
    a, b = run.stats0.get("ticks_by_class"), run.stats1.get("ticks_by_class")
    if a is None or b is None:
        return None
    _, seconds = run.trace.program("jit_micro_step")
    if seconds <= 0:
        return None
    s = run.cell.config["serving"]
    per_row = run.arch.class_flops(run.dims, s["l_sketch"], s["l_refine"])
    flops = sum(2 * s["lanes"] * per_row[c] * (b[c] - a[c]) for c in per_row)
    return 100.0 * flops / (seconds * run.peaks["bf16_flops_per_s"])
