"""Rehearsal of `degraded-get-storm` at toy size, sound and broken, and
the arithmetic of the reader the cell brings."""

import pytest

from rehearsal_util import OFF_CHIP, over, rehearse, run

CELL = "degraded-get-storm"


def test_sound_traced_run_fails_only_for_want_of_a_tpu():
    out = rehearse(CELL, seed=2**31 + 5, traced=True, seconds=2.0)
    assert out["correct"] is False and over(out) == OFF_CHIP
    assert out["attempted"] > 20 and out["failed"] == 0
    compared = out["compared"]
    # off the chip the server has no device service and decodes by direct
    # dispatch: the counter of decoded intervals is the volume's, so it moved
    for name in ("gets_wrong", "requests_failed",
                 "window_without_degraded_decodes", "survivor_bytes_differ",
                 "survivor_decode_differs", "host_codec_ops"):
        assert compared[name]["value"] == 0, name
    assert {"http_get_server_ms.degraded", "ec_degraded_gather_ms.degraded",
            "ec_degraded_decode_ms.degraded", "ec_degraded_cached_pct.degraded",
            "bg_encode_MBps.degraded"} <= set(out["metrics"])
    # no device service off the chip, no device plane on a CPU backend
    assert not {"svc_read_queue_wait_ms.degraded", "gf_roofline.degraded",
                "device_idle_pct.degraded"} & set(out["metrics"])


@pytest.mark.parametrize("control", ["flip-shard-byte", "rs-10-3"])
def test_broken_survivors_come_out_not_correct(control):
    out = rehearse(CELL, seed=2**31 + 14, control=control, seconds=2.0)
    assert out["correct"] is False
    assert {"survivor_bytes_differ", "survivor_decode_differs"} <= over(out)
    assert set(out["metrics"]) == {"get_p95_ms", "setup_s"}


def _obs(read_bytes, pipeline_bytes, busy_s=0.1, labelled=True):
    obs = run.hz.Obs()
    name = "seaweedfs_ec_service_batch_bytes_sum"
    keys = ({f'{name}{{class="read"}}': read_bytes,
             f'{name}{{class="pipeline"}}': pipeline_bytes} if labelled
            else {name: read_bytes + pipeline_bytes})
    obs.prom["trace"] = [dict.fromkeys(keys, 0.0), keys]
    obs.trace = {"busy_s": busy_s, "chips": 1}
    obs.peaks = {"hbm_bytes_per_s": 819e9}
    return obs


def test_roofline_by_class_counts_each_class_with_its_own_rows():
    from benchmark import readers
    from benchmark.readers import gf_hbm_roofline_classes as reader

    # the cell's own file counts the four rows a read's program produces
    assert readers.metric_spec("gf_roofline.degraded")["args"] == {
        "phase": "trace", "rows_in": 10,
        "rows_out": {"pipeline": 4, "read": 4}}
    args = {"phase": "trace", "rows_in": 10,
            "rows_out": {"pipeline": 4, "read": 1}}
    # 10e9 bytes of slices need 14e9, 1e9 of intervals 1.1e9: 15.1e9 bytes
    # at 819e9 a second is 18.437 ms of a 100 ms busy device
    assert reader.read(_obs(1e9, 10e9), args) == pytest.approx(
        100 * (15.1e9 / 819e9) / 0.1)
    assert reader.read(_obs(1e9, 0.0), args) == pytest.approx(
        100 * (1.1e9 / 819e9) / 0.1)
    # a program that does not tell the classes apart, a slice without a
    # device plane, a slice without a byte: nothing, never 0
    assert reader.read(_obs(1e9, 10e9, labelled=False), args) is None
    assert reader.read(_obs(1e9, 10e9, busy_s=0.0), args) is None
    assert reader.read(_obs(0.0, 0.0), args) is None
