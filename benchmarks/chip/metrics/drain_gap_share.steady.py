"""Percent of the window the host spent between a retirement sync (a
finished latent read back) and the next micro-step dispatch: the
``drain_gap_s`` counter at the window's edges over the window's length.
``None`` where the program has no ``drain_gap_s`` counter."""


def read(run):
    a, b = run.stats0.get("drain_gap_s"), run.stats1.get("drain_gap_s")
    if a is None or b is None or run.t1 <= run.t0:
        return None
    return 100.0 * (b - a) / (run.t1 - run.t0)
