"""Rehearsal of the `ec-batch-volumes` cell at toy size: a fixed batch of
volumes, several rpcs in flight, sound and with the path broken underneath.
"""

import json
import os

import pytest

from rehearsal_util import BENCH, OFF_CHIP, over, run, toy

VOLUMES, IN_FLIGHT = 5, 3


def rehearse_batch(cell_name: str, seed: int, traced: bool = False,
                   control=None, seconds: float = 0.01):
    """`rehearsal_util.rehearse` with the batch kept a batch: five volumes,
    three rpcs in flight, and a `seconds` far too short for even one."""
    cell, config, traffic = toy(cell_name)
    traffic.update(volumes=VOLUMES, in_flight=IN_FLIGHT, keep_every=2)
    line, compared = run.run_cell(
        cell["name"], cell["chips"], config, traffic,
        run.cell_metrics(BENCH, cell["name"], traced), seed, seconds,
        traced, require_tpu=False, control=control,
        tag=f"-test{os.getpid()}")
    out = json.loads(line)
    assert out["compared"] == compared
    return out


@pytest.mark.parametrize("seconds", [0.01, 3600.0])
def test_fixed_count_whatever_seconds_says(seconds):
    out = rehearse_batch("ec-batch-4chip", seed=2**31 + 9, seconds=seconds)
    assert out["correct"] is False and over(out) == OFF_CHIP
    assert out["device"]["platform"] == "cpu"
    assert out["attempted"] == VOLUMES and out["failed"] == 0
    assert set(out["metrics"]) == {"encode_MBps", "setup_s"}
    assert set(out["compared"]) >= {
        "shard_bytes_differ", "ecx_bytes_differ", "ec_needles_differ",
        "host_codec_ops", "compiles_in_window", "ec_rpcs_failed"}


def test_traced_run_reports_layer_metrics_it_can_read():
    out = rehearse_batch("ec-batch-4chip", seed=13, traced=True)
    assert over(out) == OFF_CHIP
    # the pipeline's counters are there on any backend; the codec service
    # does not engage on a CPU backend, and there is no device plane
    assert {"ec_write_s_per_GB.batch4",
            "ec_prefetch_s_per_GB.batch4"} <= set(out["metrics"])
    assert not {"device_idle_pct.batch4", "gf_roofline.batch4",
                "svc_batch_volumes.batch4",
                "svc_padding_pct.batch4"} & set(out["metrics"])
    assert "breakdown" not in out and "busy_s" not in out["device"]


@pytest.mark.parametrize("control", ["rs-10-3", "lose-output"])
def test_broken_path_comes_out_not_correct(control):
    out = rehearse_batch("ec-batch-4chip", seed=17, control=control)
    assert out["attempted"] == VOLUMES
    assert OFF_CHIP | {"shard_bytes_differ"} <= over(out) <= OFF_CHIP | {
        "shard_bytes_differ", "ec_needles_differ"}


def test_cell_is_the_issues_and_has_no_knob_beside_it():
    """32 volumes, eight in flight, all callers started when the window
    opens: the traffic file holds what ISSUE 30 names and nothing else
    that shapes the load."""
    traffic = run.load_json("traffic", "batch-encode-8inflight-4chip.json")
    assert (traffic["volumes"], traffic["in_flight"],
            traffic["keep_every"]) == (32, 8, 8)
    assert traffic["trace"] == {"mode": "time", "start_s": 8.0,
                                "length_s": 8.0}
    assert set(traffic) == {
        "driver", "as", "volumes", "in_flight", "keep_every", "trace",
        "warmup_bytes", "rows_checked", "needles_checked"}
    cell, = [w for w in BENCH["workloads"]
             if w["config"] == "ec-batch-volumes"]
    assert (cell["name"], cell["chips"]) == ("ec-batch-4chip", 4)


def test_batch_driver_and_ratio_reader_import_no_jax():
    """As test_benchmark_contract has it for the files it lists: the chip
    belongs to the server child."""
    import subprocess
    import sys

    root = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    proc = subprocess.run(
        [sys.executable, "-X", "importtime", "-c",
         "import benchmark.drivers.ec_encode_batch\n"
         "import benchmark.readers.prom_ratio\n"],
        cwd=root, capture_output=True, text=True, timeout=120)
    assert proc.returncode == 0, proc.stderr[-2000:]
    imported = {ln.rsplit("|", 1)[-1].strip().split(".")[0]
                for ln in proc.stderr.splitlines()
                if ln.startswith("import time:")}
    assert "numpy" in imported and not {"jax", "jaxlib"} & imported


@pytest.mark.parametrize("args,deltas,want", [
    ({"num": {"name": "a_sum"}, "den": {"name": "a_count"}},
     {"a_sum": 12.0, "a_count": 4.0}, 3.0),
    ({"num": {"name": "padded"}, "den": {"name": "real"}, "minus": 1.0,
      "scale": 100.0}, {"padded": 101.0, "real": 100.0}, 1.0),
    ({"num": {"name": "padded"}, "den": {"name": "real"}, "minus": 1.0,
      "scale": 100.0}, {"padded": 0.0, "real": 100.0}, None),  # the parent
    ({"num": {"name": "a_sum"}, "den": {"name": "a_count"}}, None, None),
])
def test_ratio_reader(args, deltas, want):
    from benchmark.readers import prom_ratio

    class Obs:
        def delta(self, phase, name, *bits):
            assert phase == "window"
            return None if deltas is None else deltas[name]

    got = prom_ratio.read(Obs(), args)
    assert got == want if want is None else abs(got - want) < 1e-9
