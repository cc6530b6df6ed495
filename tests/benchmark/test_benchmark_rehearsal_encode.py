"""Rehearsal of `ec-encode-warm` at toy size, sound and with the timed
path broken underneath."""

import pytest

from rehearsal_util import OFF_CHIP, over, rehearse

CELL = "ec-encode-warm"


def test_sound_run_fails_only_for_want_of_a_tpu():
    out = rehearse(CELL, seed=2**31 + 5)
    assert out["correct"] is False and over(out) == OFF_CHIP
    assert out["device"]["platform"] == "cpu"
    assert out["attempted"] >= 1 and out["failed"] == 0
    assert set(out["metrics"]) == {"encode_MBps", "setup_s"}
    assert set(out["compared"]) >= {
        "shard_bytes_differ", "ecx_bytes_differ", "ec_needles_differ",
        "host_codec_ops", "compiles_in_window", "ec_rpcs_failed"}


def test_traced_run_reports_layer_metrics_it_can_read():
    out = rehearse(CELL, seed=7, traced=True)
    assert over(out) == OFF_CHIP
    # no device plane on a CPU backend: trace metrics are left out, not 0
    assert "ec_write_s_per_GB.encode" in out["metrics"]
    assert "device_idle_pct.encode" not in out["metrics"]
    assert "breakdown" not in out and "busy_s" not in out["device"]


@pytest.mark.parametrize("control", ["rs-10-3", "flip-shard-byte",
                                     "lose-output", "half-rows"])
def test_broken_path_comes_out_not_correct(control):
    out = rehearse(CELL, seed=11, control=control)
    # (needles read through emptied or zeroed shards differ as well)
    assert OFF_CHIP | {"shard_bytes_differ"} <= over(out) <= OFF_CHIP | {
        "shard_bytes_differ", "ec_needles_differ"}
