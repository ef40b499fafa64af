"""Golden-latent regression harness (tier-1).

Checked-in tiny-config latents pin the sampler and the serving engine
bit-for-bit.  Three executions are gated:

* straight-line ``pas_denoise`` — bit-exact vs the ``line_*`` golden family
* continuous engine, cache off  — bit-exact vs the ``engine_*`` family
* engine, cache on, threshold 0 — bit-exact vs the *same* ``engine_*``
  family: the cache lookup inequality is strict, so threshold 0 never hits
  and the cache-enabled micro-step must be an exact passthrough

Bit-level comparisons against the checked-in file run in a subprocess
through ``tools/regen_golden_latents.py --check``, which pins the canonical
XLA environment before jax loads — ``XLA_FLAGS`` is process-global and
other test modules mutate it at import time (``repro.launch.dryrun``
forces 512 host devices), which shifts XLA:CPU numerics at the ulp level.
Same-process equivalences (threshold 0 vs cache off) and tolerance checks
are flag-regime independent and run in-process.

The two golden families run different XLA programs (scan vs gathered lane
micro-steps) and are only cross-checked within a small tolerance; see
``repro.serving.golden``.  Regenerate after intentional numerics changes
with ``PYTHONPATH=src python tools/regen_golden_latents.py``.
"""
import os
import subprocess
import sys

import numpy as np
import pytest

from repro.serving import golden as G

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
GOLDEN_PATH = os.path.join(REPO, "tests", "golden", G.GOLDEN_FILE)


@pytest.fixture(scope="module")
def golden():
    assert os.path.exists(GOLDEN_PATH), (
        f"missing {GOLDEN_PATH} — run tools/regen_golden_latents.py"
    )
    return G.load_golden(GOLDEN_PATH)


def test_golden_file_families_cross_check(golden):
    line, engine = golden
    assert sorted(line) == sorted(engine) == [0, 1, 2]
    for rid in line:
        np.testing.assert_allclose(line[rid], engine[rid], atol=2e-4)


@pytest.mark.parametrize("harness", ["golden", "scenarios"])
def test_golden_file_written_under_another_jax_is_refused(tmp_path, harness):
    """Seeded weights change across JAX releases, so each file records the
    release it was written under and loading it anywhere else fails."""
    from repro.serving import scenarios as S

    mod = G if harness == "golden" else S
    path = os.path.join(REPO, "tests", "golden", mod.GOLDEN_FILE)
    with np.load(path) as z:
        assert str(z[G.VERSION_KEY]) == G.jax.__version__
        arrays = dict(z)
    stale = tmp_path / "stale.npz"
    np.savez(stale, **(arrays | {G.VERSION_KEY: np.asarray("0.4.37")}))
    with pytest.raises(RuntimeError, match="written under jax 0.4.37"):
        mod.load_golden(str(stale))


def test_all_paths_bit_exact_vs_golden_file():
    """Subprocess under the canonical XLA env: straight-line sampler, engine
    with cache off, and engine at threshold 0 must reproduce the checked-in
    latents without moving a bit."""
    env = dict(os.environ)
    env["PYTHONPATH"] = "src" + os.pathsep + env.get("PYTHONPATH", "")
    out = subprocess.run(
        [sys.executable, "tools/regen_golden_latents.py", "--check"],
        capture_output=True, text=True, timeout=900, env=env, cwd=REPO,
    )
    assert out.returncode == 0, (
        f"golden drift:\n{out.stdout[-3000:]}\n{out.stderr[-2000:]}"
    )
    if not os.environ.get("GOLDEN_ATOL"):  # hardware-drift escape hatch off
        assert out.stdout.count("bit-exact") == 9  # 3 paths x 3 requests


def test_threshold_zero_is_exact_passthrough_in_any_regime():
    """Same-process comparison (immune to XLA_FLAGS pollution): arming the
    whole cache path at threshold 0 — cache-enabled micro-step, probes,
    inserts — must not move a bit vs the cache-off engine."""
    params = G.golden_params()
    off = G.run_engine(params, cache_mode="off")
    thr0 = G.run_engine(params, cache_mode="cross", cache_threshold=0.0)
    assert sorted(off) == sorted(thr0)
    for rid in off:
        np.testing.assert_array_equal(
            thr0[rid], off[rid],
            err_msg=f"rid={rid}: threshold-0 cache path diverged from cache off",
        )


def test_engine_tracks_golden_within_tolerance_in_any_regime():
    """In-process coarse anchor: whatever the process's XLA flag regime,
    the engine must stay within float-fusion distance of the goldens."""
    _, engine_golden = G.load_golden(GOLDEN_PATH)
    got = G.run_engine(G.golden_params(), cache_mode="off")
    for rid in engine_golden:
        np.testing.assert_allclose(got[rid], engine_golden[rid], atol=2e-4)
