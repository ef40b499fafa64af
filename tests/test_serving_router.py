"""Replica router: routing policy units + multi-process supervision.

The fast tests pin the pure routing policy — the payload-signature parity
with the replica-side synthesis (the property the warmth hint relies on),
the schedule-bucket math, warmth ordering and replica selection — plus the
jax-free import property of the gateway process.  The ``slow`` tests run
the real thing: a ``repro.launch.router`` process over real replica
processes, a SIGKILL mid-stream with failover + respawn, and the rolling
drain exit code.
"""
import asyncio
import hashlib
import json
import os
import signal
import subprocess
import sys
import time

import numpy as np
import pytest

from repro.runtime.fault_tolerance import RestartBackoff
from repro.serving.router import (
    ReplicaHandle,
    one_chip_env,
    payload_warmth,
    pick_replica,
    request_signature,
    signature_distance,
    visited_buckets,
)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

ROUTING = {"ctx_len": 8, "ctx_dim": 32, "timesteps_train": 1000, "max_steps": 8}


def _slots(mode="cross", threshold=0.5, t_bucket=125, slots=()):
    return {
        "mode": mode,
        "threshold": threshold,
        "t_bucket": t_bucket,
        "rings": [list(slots)],
    }


def _slot(bucket, sig, offset=0, rid=0):
    return {"bucket": bucket, "offset": offset, "rid": rid, "sig": list(map(float, sig))}


# ---------------------------------------------------------------------------
# Signature parity: the router must score with the replica's own key space
# ---------------------------------------------------------------------------


def test_request_signature_matches_frontend_synthesis():
    """The router-side signature must be bit-identical to what the replica's
    RequestFactory will derive for the same payload (same sha256 prompt mix,
    same rng stream, same pooling) — otherwise warmth hints score garbage."""
    from repro.serving.cache import prompt_signature

    prompt, seed = "a cat in a hat", 4242
    mix = int.from_bytes(hashlib.sha256(prompt.encode()).digest()[:8], "little")
    rng = np.random.default_rng((seed, mix))
    ctx = rng.normal(size=(8, 32)).astype(np.float32) * 0.2
    want = np.asarray(prompt_signature(ctx))
    got = request_signature({"prompt": prompt, "seed": seed}, 8, 32)
    np.testing.assert_allclose(got, want, rtol=0, atol=0)


def test_signature_distance_matches_cache_module():
    from repro.serving.cache import signature_distance as cache_dist

    rng = np.random.default_rng(0)
    for _ in range(5):
        a = rng.normal(size=32).astype(np.float32)
        b = rng.normal(size=32).astype(np.float32)
        assert signature_distance(a, b) == pytest.approx(float(cache_dist(a, b)), abs=1e-6)


def test_router_process_is_jax_free():
    """The gateway supervises engine subprocesses; importing it must never
    pay (or require) the jax import."""
    out = subprocess.run(
        [sys.executable, "-c",
         "import sys; import repro.serving.router; import repro.launch.router; "
         "sys.exit(1 if 'jax' in sys.modules else 0)"],
        env=dict(os.environ, PYTHONPATH="src" + os.pathsep + os.environ.get("PYTHONPATH", "")),
        cwd=REPO, timeout=120,
    )
    assert out.returncode == 0, "importing the router pulled jax into the process"


# ---------------------------------------------------------------------------
# Schedule-bucket math
# ---------------------------------------------------------------------------


def test_visited_buckets_full_schedule():
    off, buckets = visited_buckets({"timesteps": 4}, ROUTING, 125)
    # stride 250: timesteps [750, 500, 250, 0] -> buckets {6, 4, 2, 0}
    assert off == 0
    assert buckets == [0, 2, 4, 6]


def test_visited_buckets_img2img_truncates_to_late_steps():
    off, buckets = visited_buckets(
        {"timesteps": 4, "task": "img2img", "strength": 0.5}, ROUTING, 125
    )
    # executed = round(0.5 * 4) = 2 of 4: offset 2, the LAST two steps
    # of the base schedule (t = 250, 0 -> buckets {2, 0})
    assert off == 2
    assert buckets == [0, 2]


def test_visited_buckets_defaults_to_engine_max_steps():
    off, buckets = visited_buckets({}, ROUTING, 125)
    assert off == 0
    assert len(buckets) > 0


# ---------------------------------------------------------------------------
# Warmth scoring
# ---------------------------------------------------------------------------


def test_warmth_zero_for_intra_mode_and_zero_threshold():
    p = {"prompt": "x", "seed": 1, "timesteps": 4}
    sig = request_signature(p, 8, 32)
    slot = _slot(0, sig)
    assert payload_warmth(p, ROUTING, _slots(mode="intra", slots=[slot])) == 0.0
    assert payload_warmth(p, ROUTING, _slots(threshold=0.0, slots=[slot])) == 0.0
    assert payload_warmth(p, ROUTING, _slots(slots=[])) == 0.0
    assert payload_warmth(p, ROUTING, {}) == 0.0


def test_warmth_counts_matching_buckets():
    p = {"prompt": "warm prompt", "seed": 9, "timesteps": 4}
    sig = request_signature(p, 8, 32)
    # schedule visits buckets {0, 2, 4, 6}; two of them have an exact-match
    # slot -> warmth 0.5; a wrong-offset slot must not count
    slots = [_slot(0, sig), _slot(4, sig), _slot(2, sig, offset=1)]
    w = payload_warmth(p, ROUTING, _slots(slots=slots))
    assert w == pytest.approx(0.5)


def test_warmth_respects_signature_threshold():
    p = {"prompt": "near prompt", "seed": 3, "timesteps": 4}
    sig = request_signature(p, 8, 32)
    far = sig + 10.0  # relative distance >> threshold
    assert payload_warmth(p, ROUTING, _slots(slots=[_slot(0, far)])) == 0.0
    near = sig * 1.001  # well within 0.5
    assert payload_warmth(p, ROUTING, _slots(slots=[_slot(0, near)])) > 0.0


def test_warmth_orders_replicas_for_identical_payload():
    """The end-to-end hint: a replica holding this payload's slots must
    outscore a cold one at equal load."""
    p = {"prompt": "routing target", "seed": 77, "timesteps": 4}
    sig = request_signature(p, 8, 32)
    warm = _slots(slots=[_slot(b, sig) for b in (0, 2, 4, 6)])
    cold = _slots(slots=[_slot(b, sig + 50.0) for b in (0, 2, 4, 6)])
    w_warm = payload_warmth(p, ROUTING, warm)
    w_cold = payload_warmth(p, ROUTING, cold)
    assert w_warm == pytest.approx(1.0)
    assert w_cold == 0.0
    assert pick_replica([0.5, 0.5], [w_cold, w_warm]) == 1


def test_warmth_tolerates_truncated_and_annotated_summaries():
    """``slots_summary`` payloads are capped and key-delta rows carry extra
    bookkeeping (``slot``, ``gen``, ``version``): the scorer must use the
    rows that made it through and ignore everything it does not know."""
    p = {"prompt": "routing target", "seed": 77, "timesteps": 4}
    sig = request_signature(p, 8, 32)
    summary = _slots(slots=[dict(_slot(0, sig), slot=3, gen=41)])
    summary["version"] = 41
    summary["truncated"] = True
    assert payload_warmth(p, ROUTING, summary) > 0.0


# ---------------------------------------------------------------------------
# Gossip mirror: incremental /cache/keys deltas -> slots-summary shape
# ---------------------------------------------------------------------------


class _FakeKeysHandle(ReplicaHandle):
    """A ReplicaHandle whose ``/cache/keys`` endpoint is a scripted queue —
    no subprocess, no socket; ``since`` arguments are recorded."""

    def __init__(self, deltas):
        super().__init__(0, ["true"], "/tmp")
        self._deltas = list(deltas)
        self.seen_since: list[int] = []

    @property
    def ready(self) -> bool:
        return True

    def client(self):
        outer = self

        class _C:
            async def cache_keys(self, since: int = 0):
                outer.seen_since.append(int(since))
                return outer._deltas.pop(0)

        return _C()


def _delta(version, rows, **meta):
    base = {"mode": "cross", "threshold": 0.5, "t_bucket": 125}
    base.update(meta)
    return {**base, "version": version, "rings": [rows]}


def _key_row(slot, gen, bucket, sig, rid=0, offset=0):
    return {
        "slot": slot, "gen": gen, "bucket": bucket, "offset": offset,
        "rid": rid, "sig": list(map(float, sig)),
    }


def test_gossip_mirror_merges_deltas_by_slot():
    sig = np.zeros(4)
    h = _FakeKeysHandle([
        _delta(5, [_key_row(0, 4, 1, sig), _key_row(1, 5, 2, sig)]),
        _delta(9, [_key_row(1, 9, 7, sig, rid=3), _key_row(2, 8, 4, sig)]),
    ])
    assert h.gossip_summary() == {}  # nothing gossiped yet: caller falls back
    asyncio.run(h.refresh_keys())
    asyncio.run(h.refresh_keys())
    assert h.seen_since == [0, 5]  # cursor advanced, deltas stayed incremental
    assert h.keys_version == 9
    summary = h.gossip_summary()
    assert summary["mode"] == "cross" and summary["version"] == 9
    rows = {r["slot"]: r for r in summary["rings"][0]}
    assert sorted(rows) == [0, 1, 2]
    assert rows[1]["bucket"] == 7 and rows[1]["rid"] == 3  # newest gen wins


def test_gossip_mirror_version_regression_resets_to_full_fetch():
    """A version that went backwards = replica restarted: the mirror must
    be discarded and rebuilt from since=0, never blended with stale keys."""
    sig = np.zeros(4)
    h = _FakeKeysHandle([
        _delta(7, [_key_row(0, 7, 1, sig), _key_row(3, 6, 9, sig)]),
        _delta(2, [_key_row(0, 2, 5, sig)]),  # regression trips the reset...
        _delta(2, [_key_row(1, 2, 6, sig)]),  # ...and this full refetch wins
    ])
    asyncio.run(h.refresh_keys())
    asyncio.run(h.refresh_keys())
    assert h.seen_since == [0, 7, 0]
    assert h.keys_version == 2
    rows = {r["slot"]: r for r in h.gossip_summary()["rings"][0]}
    assert sorted(rows) == [1], "stale pre-restart keys must not survive"
    assert rows[1]["bucket"] == 6


def test_gossip_summary_feeds_the_warmth_scorer():
    """End to end over the mirror: a payload whose signature matches the
    gossiped keys scores warm through ``payload_warmth`` without ever
    fetching ``/stats``."""
    p = {"prompt": "routing target", "seed": 77, "timesteps": 4}
    sig = request_signature(p, 8, 32)
    rows = [_key_row(s, s + 1, b, sig) for s, b in enumerate((0, 2, 4, 6))]
    h = _FakeKeysHandle([_delta(4, rows)])
    asyncio.run(h.refresh_keys())
    assert payload_warmth(p, ROUTING, h.gossip_summary()) == pytest.approx(1.0)


# ---------------------------------------------------------------------------
# Replica selection
# ---------------------------------------------------------------------------


def test_pick_replica_least_loaded_when_cold():
    assert pick_replica([0.9, 0.2, 0.5]) == 1
    assert pick_replica([0.0, 0.0]) == 0  # tie -> lower index
    assert pick_replica([]) is None


def test_pick_replica_warmth_can_beat_load():
    # warmth 1.0 at weight 1.0 outbids a 0.6 load gap
    assert pick_replica([0.8, 0.2], [1.0, 0.0], warmth_weight=1.0) == 0
    # ... but not at weight 0 (pure least-loaded)
    assert pick_replica([0.8, 0.2], [1.0, 0.0], warmth_weight=0.0) == 1


def test_pick_replica_score_tie_prefers_lower_load():
    # scores equal (0.5*1 - 0.5 == 0.0*1 - 0.0): take the emptier replica
    assert pick_replica([0.5, 0.0], [0.5, 0.0], warmth_weight=1.0) == 1


# ---------------------------------------------------------------------------
# RestartBackoff wiring (handle-level; full respawn is in the slow tests)
# ---------------------------------------------------------------------------


def test_replica_handle_backoff_resets_on_ready():
    h = ReplicaHandle(0, ["true"], "/tmp", backoff=RestartBackoff(base_s=1.0, max_s=8.0))
    assert [h.backoff.next_delay() for _ in range(4)] == [1.0, 2.0, 4.0, 8.0]
    h.backoff.reset()
    assert h.backoff.next_delay() == 1.0


@pytest.mark.parametrize("chip", [None, 3])
def test_replica_spawn_pins_its_chip(tmp_path, chip):
    """A pinned replica process sees exactly its own chip in the TPU
    environment; an unpinned one inherits the parent's."""
    code = (
        "import os, sys; open(sys.argv[2] + '.env', 'w').write("
        "os.environ.get('TPU_VISIBLE_CHIPS', '-') + ' ' + "
        "os.environ.get('TPU_CHIPS_PER_PROCESS_BOUNDS', '-'))"
    )
    env_before = dict(os.environ)
    h = ReplicaHandle(0, [sys.executable, "-c", code], str(tmp_path), chip=chip)
    h.spawn()
    assert h.proc.wait(60) == 0
    h._close_log()
    with open(h._port_file + ".env") as f:
        seen = f.read()
    inherited = os.environ.get("TPU_VISIBLE_CHIPS", "-"), os.environ.get(
        "TPU_CHIPS_PER_PROCESS_BOUNDS", "-"
    )
    assert seen == ("3 1,1,1" if chip == 3 else " ".join(inherited))
    assert dict(os.environ) == env_before  # the parent's environment is untouched
    if chip is not None:
        assert one_chip_env(chip)["TPU_VISIBLE_CHIPS"] == str(chip)


# ---------------------------------------------------------------------------
# Live fleet (slow: real engine replicas, a real SIGKILL, a real drain)
# ---------------------------------------------------------------------------


def _spawn_router(tmp_path, *extra):
    env = dict(os.environ)
    env["PYTHONPATH"] = "src" + os.pathsep + env.get("PYTHONPATH", "")
    port_file = str(tmp_path / "router.port")
    proc = subprocess.Popen(
        [sys.executable, "-m", "repro.launch.router", "--replicas", "2",
         "--http", "127.0.0.1:0", "--port-file", port_file,
         "--run-dir", str(tmp_path), "--batch", "2", "--timesteps", "4",
         "--max-inflight", "8", "--cache", "cross", *extra],
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True, env=env, cwd=REPO,
    )
    deadline = time.perf_counter() + 600
    while not os.path.exists(port_file):
        assert proc.poll() is None, "router died during startup"
        assert time.perf_counter() < deadline, "router never published its port"
        time.sleep(0.5)
    with open(port_file) as f:
        return proc, int(f.read().strip())


@pytest.mark.slow
def test_router_kill_recovery_loses_no_accepted_request(tmp_path):
    """SIGKILL the replica serving an accepted stream: the stream must
    requeue + complete on the survivor, the dead replica must be evicted
    and respawned, and the rolling drain must still exit 0."""
    from repro.serving.client import FrontendClient

    router, port = _spawn_router(tmp_path)
    try:
        async def scenario():
            c = FrontendClient("127.0.0.1", port)
            await c.wait_ready(120.0)
            stats = await c.stats()
            pids = {e["idx"]: e["pid"] for e in stats["replicas"]}
            assert stats["router"]["ready"] == 2

            events, killed = [], []
            async for ev in c.generate_stream(
                prompt="kill me", seed=5, timesteps=4, task="txt2img"
            ):
                events.append(ev)
                if ev.get("event") == "queued" and not killed:
                    killed.append(ev["replica"])
                    os.kill(pids[ev["replica"]], signal.SIGKILL)

            kinds = [e["event"] for e in events]
            assert kinds[-1] == "done", f"accepted request was lost: {kinds}"
            assert "requeued" in kinds, "failover must be visible on the stream"
            digest = events[-1]["latent_digest"]

            # identical weights + deterministic synthesis: the failed-over
            # digest equals a fresh serve of the same payload
            ev2 = await c.generate(prompt="kill me", seed=5, timesteps=4, task="txt2img")
            assert ev2["latent_digest"] == digest

            # the supervisor must bring the killed replica back
            deadline = time.perf_counter() + 300
            while time.perf_counter() < deadline:
                s = await c.stats()
                if s["router"]["ready"] == 2:
                    break
                await asyncio.sleep(1.0)
            assert s["router"]["ready"] == 2, "killed replica never respawned"
            assert s["router"]["evictions"] >= 1
            assert s["router"]["respawns"] >= 1
            assert s["router"]["resubmitted"] >= 1
            assert s["router"]["failed"] == 0
            gens = {e["idx"]: e["generation"] for e in s["replicas"]}
            assert gens[killed[0]] >= 2, "victim must be a fresh generation"
            await c.shutdown()

        asyncio.run(scenario())
        out, _ = router.communicate(timeout=600)
        assert router.returncode == 0, out[-2000:]
        assert "'drained': True" in out
    finally:
        if router.poll() is None:
            router.kill()


@pytest.mark.slow
def test_router_serves_mixed_tasks_and_drains_clean(tmp_path):
    """The CI router-smoke flow: the stock client (with --router stats
    assertions) against a 2-replica fleet, one request per v2 task, then a
    rolling drain witnessed by the router's own exit code."""
    env = dict(os.environ)
    env["PYTHONPATH"] = "src" + os.pathsep + env.get("PYTHONPATH", "")
    router, _port = _spawn_router(tmp_path)
    try:
        client = subprocess.run(
            [sys.executable, "-m", "repro.serving.client",
             "--port-file", str(tmp_path / "router.port"),
             "--requests", "4", "--mode", "closed", "--concurrency", "2",
             "--t-lo", "2", "--t-hi", "4", "--task", "mix",
             "--router", "--shutdown"],
            capture_output=True, text=True, timeout=900, env=env, cwd=REPO,
        )
        assert client.returncode == 0, client.stderr[-2000:] + client.stdout[-2000:]
        assert "[client] router:" in client.stdout
        assert "[client] replica:" in client.stdout
        out, _ = router.communicate(timeout=600)
        assert router.returncode == 0, out[-2000:]
        assert "'drained': True" in out
    finally:
        if router.poll() is None:
            router.kill()
