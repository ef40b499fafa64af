"""How late the load generator sent (send time minus due time), 95th
percentile, in milliseconds: a starved generator shows here, not as a
fast server."""
from benchmarks.chip import readers


def read(run):
    return readers.p95([(r.sent - r.due_abs) * 1e3 for r in run.window_reqs()])
