"""Fault-tolerance runtime: stragglers, elastic re-mesh, resume loop."""
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.checkpoint.manager import CheckpointManager
from repro.runtime.fault_tolerance import (
    ElasticPlan,
    FaultTolerantLoop,
    PreemptionGuard,
    StragglerDetector,
)


# ---------------------------------------------------------------------------
# StragglerDetector
# ---------------------------------------------------------------------------


def test_straggler_flags_slow_step():
    det = StragglerDetector(threshold=2.0, warmup=3)
    for i in range(10):
        assert not det.observe(i, 1.0)
    assert det.observe(10, 5.0)
    assert det.flagged[-1][0] == 10


def test_straggler_excluded_from_ewma():
    det = StragglerDetector(threshold=2.0, warmup=2, alpha=0.5)
    for i in range(5):
        det.observe(i, 1.0)
    det.observe(5, 100.0)  # straggler
    assert det.mean < 2.0, "hiccup must not poison the moving mean"
    assert det.observe(6, 100.0), "next hiccup is still flagged"


def test_no_flags_during_warmup():
    det = StragglerDetector(warmup=5)
    assert not det.observe(0, 1.0)
    assert not det.observe(1, 50.0)  # within warmup


# ---------------------------------------------------------------------------
# ElasticPlan
# ---------------------------------------------------------------------------


def test_elastic_drops_tp_rows():
    p = ElasticPlan.plan(data=16, model=16, failed=3, global_batch=256)
    assert p.new_model == 16
    assert p.new_data == 15  # 3 failed chips -> 1 TP row lost (kept 15)
    # batch trimmed to the largest multiple of the surviving rows
    assert p.new_global_batch == 15 * (256 // 15)
    assert p.batch_per_data_shard == 256 // 15


def test_elastic_keeps_all_healthy_rows():
    """Healthy rows are never dropped: batch is trimmed instead (dropping
    rows until the old batch divides can waste half the fleet)."""
    p = ElasticPlan.plan(data=16, model=16, failed=17, global_batch=256)
    assert p.new_data == 14  # 17 failed -> exactly 2 rows lost, 14 kept
    assert p.new_global_batch == 14 * (256 // 14)
    assert p.new_global_batch % p.new_data == 0


def test_elastic_raises_when_everything_dead():
    with pytest.raises(RuntimeError):
        ElasticPlan.plan(data=2, model=16, failed=32, global_batch=64)


@given(
    data=st.integers(2, 32), model=st.sampled_from([4, 8, 16]),
    failed=st.integers(0, 40), batch=st.sampled_from([128, 256, 512]),
)
@settings(max_examples=200, deadline=None)
def test_elastic_plan_invariants(data, model, failed, batch):
    lost = -(-failed // model)
    try:
        p = ElasticPlan.plan(data, model, failed, batch)
    except RuntimeError:
        assert data - lost < 1 or batch < data - lost
        return
    assert p.new_data == data - lost  # every healthy row kept
    assert p.new_model == model
    assert p.new_global_batch % p.new_data == 0
    assert 0 < p.new_global_batch <= batch
    assert batch - p.new_global_batch < p.new_data  # minimal trim


# ---------------------------------------------------------------------------
# FaultTolerantLoop: checkpoint-resume with mid-run kill
# ---------------------------------------------------------------------------


def test_loop_resumes_from_checkpoint(tmp_path):
    cm = CheckpointManager(str(tmp_path), keep=3)
    calls = []

    def step_fn(state, step):
        calls.append(step)
        if step == 7:
            raise KeyboardInterrupt  # simulated node failure
        return {"x": state["x"] + 1}

    loop = FaultTolerantLoop(ckpt=cm, save_every=3, max_steps=10)
    with pytest.raises(KeyboardInterrupt):
        loop.run({"x": np.zeros(2)}, step_fn)
    assert cm.list_steps()[-1] == 6  # last committed step

    # "restart": the loop resumes from step 6, not 0
    calls.clear()

    def step_ok(state, step):
        calls.append(step)
        return {"x": state["x"] + 1}

    loop2 = FaultTolerantLoop(ckpt=cm, save_every=3, max_steps=10)
    out = loop2.run({"x": np.zeros(2)}, step_ok)
    assert calls[0] == 6
    assert float(out["x"][0]) == 6 + 4  # 6 restored + steps 6..9


def test_loop_preemption_checkpoints(tmp_path):
    cm = CheckpointManager(str(tmp_path))
    guard = PreemptionGuard(install=False)

    def step_fn(state, step):
        if step == 4:
            guard.requested = True  # SIGTERM arrives mid-step
        return state

    loop = FaultTolerantLoop(ckpt=cm, save_every=100, max_steps=10)
    loop.run({"x": np.zeros(1)}, step_fn, guard=guard)
    assert cm.list_steps() == [5], "preemption must publish step+1 immediately"


# ---------------------------------------------------------------------------
# RestartBackoff (the router's respawn schedule)
# ---------------------------------------------------------------------------


def test_backoff_walks_up_and_caps():
    from repro.runtime.fault_tolerance import RestartBackoff

    b = RestartBackoff(base_s=0.5, factor=2.0, max_s=30.0)
    delays = [b.next_delay() for _ in range(8)]
    assert delays == [0.5, 1.0, 2.0, 4.0, 8.0, 16.0, 30.0, 30.0]


def test_backoff_reset_after_recovery():
    from repro.runtime.fault_tolerance import RestartBackoff

    b = RestartBackoff(base_s=1.0, factor=3.0, max_s=10.0)
    assert b.next_delay() == 1.0
    assert b.next_delay() == 3.0
    b.reset()
    assert b.next_delay() == 1.0, "an isolated crash pays base_s again"


def test_backoff_validates_parameters():
    from repro.runtime.fault_tolerance import RestartBackoff

    with pytest.raises(ValueError):
        RestartBackoff(base_s=0.0)
    with pytest.raises(ValueError):
        RestartBackoff(factor=0.5)
    with pytest.raises(ValueError):
        RestartBackoff(base_s=2.0, max_s=1.0)
