"""The readers of the program's spans and counters (``lane_wait.steady``,
``micro_step_mfu.steady``, ``drain_gap_share.steady``,
``host_ms_per_tick.steady``) on the toy run of ``test_correctness.py``,
and on a program that has none of those counters.

The CPU gives no device trace, so ``micro_step_mfu.steady`` reads a
reduction that holds the micro-step program only, timed by the host's
``engine.dispatch`` spans; the test checks the reader's arithmetic, not a
device time."""
import copy

import pytest

from benchmarks.chip import check, spec, system, trace
from benchmarks.chip.test_correctness import toy_run

NAMES = ("lane_wait.steady", "micro_step_mfu.steady", "drain_gap_share.steady",
         "host_ms_per_tick.steady")


@pytest.fixture(scope="module")
def rec(tmp_path_factory):
    mp = pytest.MonkeyPatch()
    tmp = tmp_path_factory.mktemp("span-metrics")
    mp.setattr(system, "CACHE_DIR", tmp / "jax_cache")
    mp.setattr(system, "RUNS_DIR", tmp / "runs")
    kept = {}
    compare = check.compare

    def keep(cell, rec, *a, **k):
        kept["rec"] = rec
        return compare(cell, rec, *a, **k)

    mp.setattr(check, "compare", keep)
    try:
        toy_run(seed=2**31 + 91)
    finally:
        mp.undo()
    rec = kept["rec"]
    a, b = rec.stats0["spans"], rec.stats1["spans"]
    seconds = b["engine.dispatch"]["s"] - a["engine.dispatch"]["s"]
    rec.trace = trace.Reduction(
        window_s=rec.t1 - rec.t0, busy_s=seconds, n_devices=1,
        programs={"jit_micro_step": (rec.stats1["micro_steps"] - rec.stats0["micro_steps"],
                                     seconds)},
        ops={}, gaps=[],
    )
    rec.peaks = spec.load_json("peaks.json")["TPU v5 lite"]
    return rec


def read(name, run):
    return spec.load_module("metrics", name).read(run)


def delta(rec, key):
    return rec.stats1[key] - rec.stats0[key]


def test_readers_return_numbers_on_the_toy_run(rec):
    ticks = delta(rec, "micro_steps")
    assert ticks > 0
    lanes = rec.cell.config["serving"]["lanes"]
    wait = read("lane_wait.steady", rec)
    assert wait == pytest.approx(
        100 * (delta(rec, "lane_slots_active") - delta(rec, "lane_steps_advanced"))
        / (ticks * lanes))
    assert 0 <= wait < 100
    util = 100 * delta(rec, "lane_steps_advanced") / (ticks * lanes)
    assert wait + util <= 100 + 1e-9

    s = rec.cell.config["serving"]
    per_row = spec.arch(rec.cell.config).class_flops(rec.dims, s["l_sketch"], s["l_refine"])
    t0, t1 = rec.stats0["ticks_by_class"], rec.stats1["ticks_by_class"]
    assert sum(t1[c] - t0[c] for c in per_row) == ticks
    flops = sum(2 * lanes * per_row[c] * (t1[c] - t0[c]) for c in per_row)
    mfu = read("micro_step_mfu.steady", rec)
    seconds = rec.trace.program("jit_micro_step")[1]
    assert mfu == pytest.approx(100 * flops / (seconds * rec.peaks["bf16_flops_per_s"]))
    assert mfu > 0

    drain = read("drain_gap_share.steady", rec)
    assert drain == pytest.approx(100 * delta(rec, "drain_gap_s") / (rec.t1 - rec.t0))
    assert 0 <= drain < 100

    host = read("host_ms_per_tick.steady", rec)
    sp0, sp1 = rec.stats0["spans"], rec.stats1["spans"]
    want = sum(sp1[k]["s"] - sp0[k]["s"] for k in ("engine.admit", "engine.plan"))
    assert host == pytest.approx(1e3 * want / ticks)
    assert host > 0


def test_readers_find_nothing_in_a_program_without_the_counters(rec):
    bare = copy.copy(rec)
    new_keys = ("lane_slots_active", "ticks_by_class", "drain_gap_s", "drains", "spans")
    bare.stats0 = {k: v for k, v in rec.stats0.items() if k not in new_keys}
    bare.stats1 = {k: v for k, v in rec.stats1.items() if k not in new_keys}
    assert [read(n, bare) for n in NAMES] == [None] * 4
