"""95th percentile of due time to ``done``, as ``latency_p50_s``."""
from benchmarks.chip import load, readers


def read(run):
    lat = readers.latencies(run)
    return load.percentile(lat, 95) if lat else None
