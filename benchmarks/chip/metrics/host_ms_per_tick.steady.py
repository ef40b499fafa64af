"""Host milliseconds per tick spent admitting requests and planning the
tick (the program's ``engine.admit`` and ``engine.plan`` spans), inside
the window: the host work the device waits for after each drain.  From
the ``spans`` and ``micro_steps`` counters at the window's edges; ``None``
where the program has no ``spans``."""

NAMES = ("engine.admit", "engine.plan")


def read(run):
    a, b = run.stats0.get("spans"), run.stats1.get("spans")
    if a is None or b is None:
        return None
    ticks = run.stats1["micro_steps"] - run.stats0["micro_steps"]
    if ticks <= 0:
        return None
    seconds = sum(b.get(k, {}).get("s", 0.0) - a.get(k, {}).get("s", 0.0) for k in NAMES)
    return 1e3 * seconds / ticks
