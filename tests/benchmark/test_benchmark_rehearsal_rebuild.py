"""Rehearsal of `ec-rebuild-4data` at toy size, sound and broken."""

import pytest

from rehearsal_util import OFF_CHIP, over, rehearse

CELL = "ec-rebuild-4data"


def test_sound_run_fails_only_for_want_of_a_tpu():
    out = rehearse(CELL, seed=3, seconds=2.5)
    assert out["correct"] is False and over(out) == OFF_CHIP
    assert out["attempted"] >= 2 and out["failed"] == 0
    assert set(out["metrics"]) == {"rebuild_MBps", "setup_s"}


@pytest.mark.parametrize("control", ["rs-10-3", "flip-shard-byte",
                                     "lose-output", "half-rows"])
def test_broken_path_comes_out_not_correct(control):
    out = rehearse(CELL, seed=12, control=control, seconds=2.5)
    assert "shard_bytes_differ" in over(out)
    assert over(out) <= OFF_CHIP | {"shard_bytes_differ", "ec_needles_differ"}
