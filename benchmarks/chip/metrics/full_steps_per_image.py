"""Executed FULL lane-steps per finished request: the ``full_steps``
counter from the window's start to the end of the drain, over the
requests that finished."""
from benchmarks.chip import readers


def read(run):
    done = sum(r.status == "done" for r in run.window_reqs())
    if not done:
        return None
    return readers.counter_delta(run.stats0, run.stats_end, "full_steps") / done
