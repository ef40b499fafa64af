"""95th percentile of the ``queue_wait_s`` the driver stamps on each
``done`` event (submission to lane admission), over the window's
finished requests."""
from benchmarks.chip import readers


def read(run):
    return readers.p95([r.queue_wait_s for r in run.window_reqs() if r.status == "done"])
