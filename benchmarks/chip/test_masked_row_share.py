"""The reader of ``masked_row_share.steady`` on the toy run of
``test_correctness.py``, and on a program without the ``unet_rows`` and
``micro_dispatches`` counters, which it leaves out of the line."""
import copy

import pytest

from benchmarks.chip.test_span_metrics import delta, read, rec  # noqa: F401

NAME = "masked_row_share.steady"


def test_masked_row_share_reads_the_compact_dispatch_counters(rec):  # noqa: F811
    """Every dispatch computes ``2 * K`` rows, ``K = ceil(lanes / 2)``, and
    the share is what those rows hold beyond two per advanced lane."""
    lanes = rec.cell.config["serving"]["lanes"]
    dispatches, rows = delta(rec, "micro_dispatches"), delta(rec, "unet_rows")
    assert delta(rec, "micro_steps") <= dispatches <= 2 * delta(rec, "micro_steps")
    assert rows == 2 * -(-lanes // 2) * dispatches
    share = read(NAME, rec)
    assert share == pytest.approx(100 * (1 - 2 * delta(rec, "lane_steps_advanced") / rows))
    assert 0 <= share < 100


def test_masked_row_share_finds_nothing_without_the_counters(rec):  # noqa: F811
    bare = copy.copy(rec)
    new_keys = ("unet_rows", "micro_dispatches")
    bare.stats0 = {k: v for k, v in rec.stats0.items() if k not in new_keys}
    bare.stats1 = {k: v for k, v in rec.stats1.items() if k not in new_keys}
    assert read(NAME, bare) is None
