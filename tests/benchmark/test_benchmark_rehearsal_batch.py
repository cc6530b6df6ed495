"""Rehearsal of the `ec-batch-volumes` cell at toy size: a fixed batch of
volumes, several rpcs in flight, sound and with the path broken underneath.
"""

import json
import os
import re

import pytest

from rehearsal_util import BENCH, OFF_CHIP, ROOT, over, run, toy

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


def test_traced_run_reports_layer_metrics_it_can_read(capfd):
    out = rehearse_batch("ec-batch-4chip", seed=13, traced=True)
    assert over(out) == OFF_CHIP
    # trace mode `window`: the profiler runs from before the first rpc to
    # after the last, so the slice holds every job of the batch whole
    said = capfd.readouterr().err
    assert said.index("profiler started") < said.index("encode: a batch of") \
        < said.index("profiler stopped")
    slice_s = float(re.search(r"stopped after a ([0-9.]+)s slice", said)[1])
    span_s = float(re.search(r"done in ([0-9.]+)s", said)[1])
    assert slice_s + 0.01 >= span_s > 0      # said to two and three places
    # the pipeline's counters are there on any backend; the codec service
    # does not engage on a CPU backend, and there is no device plane
    assert {"ec_write_s_per_GB.batch4",
            "ec_prefetch_s_per_GB.batch4"} <= set(out["metrics"])
    assert not {"device_idle_pct.batch4", "gf_roofline.batch4",
                "svc_batch_volumes.batch4",
                "svc_padding_pct.batch4"} & set(out["metrics"])
    assert "breakdown" not in out and "busy_s" not in out["device"]


@pytest.mark.parametrize("volumes,drops_inside", [(IN_FLIGHT, False),
                                                  (VOLUMES, True)])
def test_the_tails_drops_wait_for_the_last_rpc(monkeypatch, volumes,
                                               drops_inside):
    """The harness's housekeeping keeps out of the window's tail: once the
    queue is empty a drop waits until the last rpc has returned (in a batch
    of one round: every drop), and earlier rounds' drops go at once."""
    import time

    from benchmark.drivers import ec_encode_batch as eb

    ends, drops = [], []
    real_encode, real_drop = eb.Driver._encode, eb.Driver._drop

    def encode(self, cluster, vid):
        ok = real_encode(self, cluster, vid)
        ends.append((vid, time.monotonic()))
        return ok

    def drop(self, cluster, vid):
        drops.append((vid, time.monotonic()))
        real_drop(self, cluster, vid)

    monkeypatch.setattr(eb.Driver, "_encode", encode)
    monkeypatch.setattr(eb.Driver, "_drop", drop)
    cell, config, traffic = toy("ec-batch-4chip")
    traffic.update(volumes=volumes, in_flight=IN_FLIGHT, keep_every=8)
    line, _ = run.run_cell(
        cell["name"], cell["chips"], config, traffic,
        run.cell_metrics(BENCH, cell["name"], False), 2**31 + 23, 0.01,
        False, require_tpu=False, tag=f"-test{os.getpid()}")
    out = json.loads(line)
    assert over(out) == OFF_CHIP and out["attempted"] == volumes
    # the window's volumes have the lowest ids; the warm-up's come after
    window_ends = dict(v for v in ends if v[0] <= volumes)
    window_drops = dict(v for v in drops if v[0] <= volumes)
    assert len(window_ends) == volumes
    assert len(window_drops) >= volumes - 2      # first kept, one more maybe
    last_end = max(window_ends.values())
    inside = [vid for vid, t in window_drops.items() if t < last_end]
    assert bool(inside) is drops_inside
    # a drop that waited belongs to the last round, and none comes before
    # its own rpc has returned
    for vid, t in window_drops.items():
        assert t >= window_ends[vid]
        if vid not in inside and drops_inside:
            assert vid > volumes - IN_FLIGHT - 1


@pytest.mark.parametrize("control", ["rs-10-3", "lose-output"])
def test_broken_path_comes_out_not_correct(control):
    out = rehearse_batch("ec-batch-4chip", seed=17, control=control)
    assert out["attempted"] == VOLUMES
    assert OFF_CHIP | {"shard_bytes_differ"} <= over(out) <= OFF_CHIP | {
        "shard_bytes_differ", "ec_needles_differ"}


def test_cell_is_the_issues_and_has_no_knob_beside_it():
    """One round: eight volumes, eight in flight, all callers started when
    the window opens.  The traffic file holds what ISSUE 36 names and
    nothing else that shapes the load."""
    traffic = run.load_json("traffic", "batch-encode-8inflight-4chip.json")
    assert (traffic["volumes"], traffic["in_flight"],
            traffic["keep_every"]) == (8, 8, 8)
    assert traffic["trace"] == {"mode": "window"}
    assert set(traffic) == {
        "driver", "as", "volumes", "in_flight", "keep_every", "trace",
        "warmup_bytes", "rows_checked", "needles_checked"}
    cell, = [w for w in BENCH["workloads"]
             if w["config"] == "ec-batch-volumes"]
    assert (cell["name"], cell["chips"]) == ("ec-batch-4chip", 4)


def test_a_window_writes_less_than_the_chip_machines_burst():
    """Why the batch is one round (PERF section 6, PR 34 and 36): the chip
    machine takes 12-20 GB of new file pages at memory speed and about
    0.85 GB/s after that, so a window that writes more reads the machine
    and not the program.  Whoever raises `volumes` or `volume_bytes` meets
    this first."""
    traffic = run.load_json("traffic", "batch-encode-8inflight-4chip.json")
    entry, = [c for c in BENCH["configs"] if c["name"] == "ec-batch-volumes"]
    with open(os.path.join(ROOT, entry["file"])) as f:
        config = json.load(f)
    assert config["batch_volumes"] == traffic["volumes"]
    assert config["in_flight"] == traffic["in_flight"]
    shards_per_byte = (config["data_shards"] + config["parity_shards"]) \
        / config["data_shards"]
    written = traffic["volumes"] * config["volume_bytes"] * shards_per_byte
    assert written < 12.5e9


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


PADDING = {"num": {"name": "padded"}, "den": {"name": "real"}, "minus": 1.0,
           "scale": 100.0}
FRESH = {"num": {"name": "buffers", "labels": ['source="fresh"']},
         "den": {"name": "buffers"}, "scale": 100.0}


@pytest.mark.parametrize("args,deltas,series,want", [
    ({"num": {"name": "a_sum"}, "den": {"name": "a_count"}},
     {"a_sum": 12.0, "a_count": 4.0}, {"a_sum", "a_count"}, 3.0),
    (PADDING, {"padded": 101.0, "real": 100.0}, {"padded", "real"}, 1.0),
    # numerator 0 of a series the program has: a share of 0, not silence
    (FRESH, {"buffers": [0.0, 84.0]}, {"buffers"}, 0.0),
    (PADDING, {"padded": 0.0, "real": 100.0}, {"padded", "real"}, -100.0),
    # denominator 0: nothing happened that the ratio is about
    (FRESH, {"buffers": [0.0, 0.0]}, {"buffers"}, None),
    # series absent: the parent of PR 30 has no such counter
    (PADDING, {"padded": 0.0, "real": 100.0}, {"real"}, None),
    # no scrape at all
    ({"num": {"name": "a_sum"}, "den": {"name": "a_count"}}, None, set(),
     None),
])
def test_ratio_reader(args, deltas, series, want):
    from benchmark.readers import prom_ratio

    class Obs:
        def delta(self, phase, name, *bits):
            assert phase == "window"
            if deltas is None:
                return None
            d = deltas[name]
            return d if not isinstance(d, list) else d[0 if bits else 1]

        def has_series(self, phase, name):
            assert phase == "window"
            return name in series

    got = prom_ratio.read(Obs(), args)
    assert got == want if want is None else abs(got - want) < 1e-9


def test_has_series_tells_a_still_counter_from_a_missing_one():
    from benchmark.harness import Obs

    obs = Obs()
    assert not obs.has_series("window", "buffers")
    obs.prom["window"] = [{}, None]
    assert not obs.has_series("window", "buffers")
    obs.prom["window"] = [{}, {'buffers{source="pooled"}': 84.0, "other": 1.0}]
    assert obs.has_series("window", "buffers")
    assert not obs.has_series("window", "buff")
    assert obs.delta("window", "buffers", 'source="fresh"') == 0.0
