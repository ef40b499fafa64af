"""sd_v14 is served exactly as before per-level heads and depths, depth-N
transformer sites and the added conditioning came in: its parameter tree
(paths, shapes, dtypes, flatten order, which the benchmark's weight maker
cuts its one stream by) and the lowered text of every serving program it
runs equal what ``tools/regen_sd14_program_golden.py`` recorded
(``tests/golden/sd_v14_programs.json``).  Nothing is compiled or allocated
at sd_v14's size: shapes come from ``jax.eval_shape`` and the programs are
lowered on abstract arguments."""
import importlib.util
import json
from pathlib import Path

import pytest

from repro.configs import get_unet_config

ROOT = Path(__file__).resolve().parents[1]
GOLDEN = json.loads((ROOT / "tests" / "golden" / "sd_v14_programs.json").read_text())


def _regen():
    path = ROOT / "tools" / "regen_sd14_program_golden.py"
    spec = importlib.util.spec_from_file_location("regen_sd14_program_golden", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


REGEN = _regen()


@pytest.fixture(scope="module")
def digests():
    return REGEN.program_digests(get_unet_config("sd_v14"))


def test_param_tree_is_unchanged():
    assert REGEN.param_tree(get_unet_config("sd_v14")) == GOLDEN["param_tree"]


@pytest.mark.parametrize("program", sorted(GOLDEN["programs"]))
def test_lowered_program_is_unchanged(digests, program):
    assert digests[program] == GOLDEN["programs"][program], (
        f"the lowered text of sd_v14's {program} changed")
