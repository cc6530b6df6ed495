"""Rehearsal of `live-1k-under-encode` at toy size, sound and broken."""

import pytest

from rehearsal_util import OFF_CHIP, over, rehearse

CELL = "live-1k-under-encode"


def test_sound_traced_run_fails_only_for_want_of_a_tpu():
    out = rehearse(CELL, seed=4, traced=True, seconds=2.0)
    assert out["correct"] is False and over(out) == OFF_CHIP
    assert out["attempted"] > 50 and out["failed"] == 0
    assert {"http_get_server_ms", "assign_ms",
            "bg_encode_MBps.live"} <= set(out["metrics"])
    assert out["compared"]["acked_puts_unreadable"]["value"] == 0


@pytest.mark.parametrize("control,number", [
    ("ack-not-stored", "acked_puts_unreadable"),
    ("alter-put", "acked_puts_unreadable"),
    ("flip-shard-byte", "shard_bytes_differ"),
])
def test_broken_path_comes_out_not_correct(control, number):
    out = rehearse(CELL, seed=13, control=control, seconds=2.0)
    assert number in over(out)
    assert set(out["metrics"]) == {"get_p95_ms", "put_p95_ms", "setup_s"}
