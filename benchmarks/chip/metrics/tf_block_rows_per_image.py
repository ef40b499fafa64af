"""BasicTransformerBlock rows per finished request: the ``tf_block_rows``
counter (each dispatch's computed rows times the blocks its class's pass
runs) from the window's start to the end of the drain, over the requests
that finished.  ``None`` where the program has no such counter."""
from benchmarks.chip import readers


def read(run):
    done = sum(r.status == "done" for r in run.window_reqs())
    if not done or "tf_block_rows" not in run.stats0 or "tf_block_rows" not in run.stats_end:
        return None
    return readers.counter_delta(run.stats0, run.stats_end, "tf_block_rows") / done
