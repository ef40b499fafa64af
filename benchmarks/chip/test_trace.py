"""The trace reduction on a small trace recorded on one TPU v5e
(``testdata/record_trace.py``): four ticks of a program named
``micro_step`` holding the Pallas kernels, each inside a
``bench.engine_step`` span, with 20 ms host sleeps (``bench.host_wait``)
between them."""
from pathlib import Path

import pytest

from benchmarks.chip import trace

HERE = Path(__file__).resolve().parent


@pytest.fixture(scope="module")
def red():
    return trace.reduce(str(HERE / "testdata" / "tiny.xplane.pb"), window=None)


def test_window_busy_and_idle(red):
    assert red.n_devices == 1
    assert 0 < red.busy_s < red.window_s
    # three 20 ms sleeps between four short ticks: the device is mostly idle
    assert 0.9 < red.idle_share < 1.0
    assert red.window_s > 0.06


def test_programs_and_kernels_are_found_by_name(red):
    n, seconds = red.program("jit_micro_step")
    assert n == 4
    # the program's span holds its operations and the short gaps between them
    assert red.busy_s <= seconds < 1.05 * red.busy_s
    for kernel in ("uniconv", "flash_attention", "stream_group_norm"):
        assert red.op_seconds(kernel) > 0, kernel
    # self times never exceed the busy union
    assert sum(red.ops.values()) <= red.busy_s * 1.000001


def test_idle_gaps_are_labelled_by_the_host_span_around_them(red):
    longest = red.gaps[:3]
    assert [label for _, label in longest] == ["bench.host_wait"] * 3
    assert all(0.015 < s < 0.05 for s, _ in longest)
    b = red.breakdown()
    assert len(b["device_ops"]) <= 10 and len(b["idle_gaps"]) <= 10


def test_op_kind_and_self_time():
    assert trace.op_kind("%flash_attention.50 = f32[64,4096,40] custom-call(...)") == "flash_attention"
    assert trace.op_kind("%cond.10 = (f32[8]) conditional(...)") == "cond"
    assert trace.op_kind("%copy-start = (f32[128,128]) copy-start(...)") == "copy-start"
    nested = trace._self_times([(0, 100, "cond"), (10, 40, "a"), (50, 90, "b"), (60, 70, "c")])
    assert {name: s for _, _, name, s in nested} == {"cond": 30, "a": 30, "b": 30, "c": 10}
