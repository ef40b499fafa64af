"""``correct`` on the CPU at a toy size: a sound run passes, and the float8
control and each fault the served path can have do not.

The harness's look for a chip is skipped (``require_tpu=False``); the rest
of a run is driven as on the chip: the HTTP server, the driver thread, the
engine, the window and the comparison with the float32 reference, under
the limit of ``sd_v14.tiers-steady``.  Faults are planted underneath, in
the program's micro-step or engine, never in the benchmark.
"""
import json

import numpy as np
import pytest

from benchmarks.chip import check, run, spec, system

CELL = "sd_v14.tiers-steady"


def toy_cell(loop: str = "open") -> spec.Cell:
    """The toy configuration under the tiers mix, open loop at 2 requests/s,
    or closed loop with 8 outstanding, which keeps every lane busy."""
    config = spec.load_json("testdata/toy.json")
    traffic = dict(
        spec.load_json("traffic/tiers-steady.json"), rate_per_s=2.0, warmup_steps=8,
        steps=[{"p": 0.5, "value": 8}, {"p": 0.5, "uniform": [4, 6]}], check_sample=4,
    )
    if loop == "closed":
        traffic.update(loop="closed", outstanding=8, max_requests=400)
    return spec.Cell(name=CELL, chips=1, config=config, traffic=traffic,
                     end_to_end=[], per_layer=[])


@pytest.fixture()
def isolated(tmp_path, monkeypatch):
    monkeypatch.setattr(system, "CACHE_DIR", tmp_path / "jax_cache")
    monkeypatch.setattr(system, "RUNS_DIR", tmp_path / "runs")
    return tmp_path


def toy_run(seed: int = 2**31 + 77, loop: str = "open") -> dict:
    return run.run(toy_cell(loop), seed, 4.0, False, require_tpu=False,
                   limits=check.load_limits(CELL))


def test_sound_run_is_correct_and_the_control_is_not(isolated, monkeypatch):
    kept = {}
    compare = check.compare

    def keep(cell, rec, latents, params, seed, limits, log):
        kept.update(cell=cell, rec=rec, latents=latents, params=params, seed=seed)
        return compare(cell, rec, latents, params, seed, limits, log)

    monkeypatch.setattr(check, "compare", keep)
    result = toy_run()
    assert result["correct"], result["checks"]
    assert result["checks"]["compared"]["value"] == 4
    json.dumps(result)

    # the control: the reference at float8, put in the program's place
    cell, rec = kept["cell"], kept["rec"]
    control = spec.arch(cell.config).Sampler(cell.config, rec.dims,
                                             check.params_f32(kept["params"]), quant="fp8")
    latents = {}
    for r in check.sample(rec.window_reqs(), cell.traffic["check_sample"], kept["seed"]):
        latents[r.rid] = control.run(r.prompt, r.seed, r.tier, r.steps)
        r.digest = check.digest(latents[r.rid])
    out = compare(cell, rec, latents, kept["params"], kept["seed"],
                  check.load_limits(CELL), lambda m: None)
    assert not out["correct"]
    assert out["numbers"]["latent_rel_gap"]["value"] > out["numbers"]["latent_rel_gap"]["limit"]


def _break_micro_step(monkeypatch, wrap):
    from repro.serving import lanes

    real = lanes.make_micro_step
    monkeypatch.setattr(lanes, "make_micro_step", lambda *a, **k: wrap(real(*a, **k)))


def test_step_that_returns_its_state_unchanged_is_caught(isolated, monkeypatch):
    _break_micro_step(monkeypatch, lambda step: (lambda state, *rest: state))
    result = toy_run()
    assert not result["correct"]


def test_half_the_lanes_left_out_is_caught(isolated, monkeypatch):
    import jax.numpy as jnp

    def wrap(step):
        def half(state, *rest):
            keep = jnp.array(state.x)  # the state is donated
            new = step(state, *rest)
            n = keep.shape[0] // 2
            return new._replace(x=new.x.at[n:].set(keep[n:]))

        return half

    _break_micro_step(monkeypatch, wrap)
    result = toy_run(loop="closed")
    assert not result["correct"]


def test_answer_altered_where_it_is_produced_is_caught(isolated, monkeypatch):
    from repro.serving.engine import DiffusionEngine

    real = DiffusionEngine.step

    def altered(self, *a, **k):
        done = real(self, *a, **k)
        for c in done:
            c.latent = c.latent + np.float32(0.1) * np.std(c.latent)
        return done

    monkeypatch.setattr(DiffusionEngine, "step", altered)
    result = toy_run()
    assert not result["correct"]
