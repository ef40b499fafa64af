"""Median time from each request's due time to its ``done`` event, over
every request the window offered; a failed request counts as ``inf``."""
from benchmarks.chip import load, readers


def read(run):
    lat = readers.latencies(run)
    return load.percentile(lat, 50) if lat else None
