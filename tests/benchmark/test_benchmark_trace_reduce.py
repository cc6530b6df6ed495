"""trace_reduce.py: its arithmetic on hand-made planes, and the whole
reduction on a trace recorded on a TPU v5e (one traced `ec.encode` rpc of
a 1 GiB volume, PR 25), kept beside it."""

import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark import trace_reduce as tr  # noqa: E402
from benchmark.harness import Obs  # noqa: E402
from benchmark.readers import gf_hbm_roofline, trace_idle_pct  # noqa: E402

FIXTURE = os.path.join(ROOT, "benchmark", "fixtures", "ec_encode_rpc.xplane.pb")
EXPECTED = os.path.join(ROOT, "benchmark", "fixtures", "ec_encode_rpc.json")
MS = 1_000_000


@pytest.mark.parametrize("intervals,want_s", [
    ([], 0.0),
    ([(0, 10 * MS)], 0.010),
    ([(0, 10 * MS), (5 * MS, 20 * MS)], 0.020),          # overlap
    ([(0, 10 * MS), (2 * MS, 3 * MS)], 0.010),           # nested
    ([(30 * MS, 40 * MS), (0, 10 * MS)], 0.020),         # unordered, apart
    ([(0, 10 * MS), (10 * MS, 20 * MS)], 0.020),         # touching
])
def test_union_seconds(intervals, want_s):
    assert tr.union_seconds(intervals) == pytest.approx(want_s)


def test_gaps_are_labelled_by_what_the_host_was_doing():
    iv = [(0, 10 * MS, "a"), (40 * MS, 50 * MS, "b"), (55 * MS, 60 * MS, "c"),
          (5 * MS, 8 * MS, "inner")]
    assert tr.gaps(iv) == [["no host span; after a", pytest.approx(0.030)],
                           ["no host span; after b", pytest.approx(0.005)]]
    # 10..40: `copy` covers 10..25 (twice, on two lines) and `wait` 20..28
    host = [(0, 25 * MS, "copy"), (12 * MS, 20 * MS, "copy"),
            (20 * MS, 28 * MS, "wait"), (90 * MS, 95 * MS, "late")]
    assert tr.gaps(iv, host, limit=1) == [
        ["copy 50%, no host span 40%; after a", pytest.approx(0.030)]]


def test_short_op_names():
    assert tr.short("%xor_xor_fusion.1 = u8[1,1,16]{2,1,0} fusion(u8[] %p)") \
        == "xor_xor_fusion.1"
    assert tr.short("copy-start") == "copy-start"


def _planes(n_chips: int):
    planes = [("/host:CPU", [("python", [("$f", 0, 100 * MS)])])]
    for c in range(n_chips):
        planes.append((f"/device:TPU:{c}", [
            ("XLA Modules", [("jit_apply(123)", 10 * MS, 20 * MS),
                             ("jit_apply(456)", 50 * MS, 10 * MS)]),
            ("XLA Ops", [("%a = u8[] fusion()", 10 * MS, 8 * MS),
                         ("%b = u8[] fusion()", 20 * MS, 10 * MS),
                         ("%a = u8[] fusion()", 50 * MS, 10 * MS)]),
        ]))
    return planes


@pytest.mark.parametrize("chips", [1, 4])
def test_reduce_by_module_on_the_device_plane(chips):
    out = tr.reduce_planes(_planes(chips))
    assert out["chips"] == chips
    assert out["window_s"] == pytest.approx(0.100)       # host plane counts
    assert out["busy_s"] == pytest.approx(0.028)         # mean over chips
    assert out["module_s"] == pytest.approx(0.030)
    assert out["modules"] == [["jit_apply", pytest.approx(0.030 * chips)]]
    assert out["device_ops"][0] == ["a", pytest.approx(0.018 * chips)]
    assert out["idle_gaps"][0] == ["$f 100%, no host span 0%; after b",
                                   pytest.approx(0.020)]


def test_no_device_plane_gives_no_busy_time():
    out = tr.reduce_planes(_planes(0))
    assert out["chips"] == 0 and "busy_s" not in out
    obs = Obs()
    obs.trace = out
    assert trace_idle_pct.read(obs, {}) is None      # nothing, never 0
    assert gf_hbm_roofline.read(obs, {"rows_in": 10, "rows_out": 4}) is None


def test_trace_readers():
    obs = Obs()
    obs.trace = {"chips": 1, "window_s": 2.0, "busy_s": 0.5, "module_s": 0.5}
    obs.peaks = {"hbm_bytes_per_s": 819e9}
    obs.prom["trace"] = [
        {"seaweedfs_ec_service_batch_bytes_sum": 1e9},
        {"seaweedfs_ec_service_batch_bytes_sum": 1e9 + 8.19e9}]
    assert trace_idle_pct.read(obs, {}) == pytest.approx(75.0)
    # 8.19 GB in -> 11.466 GB needed -> 14 ms at peak, of 500 ms
    assert gf_hbm_roofline.read(
        obs, {"rows_in": 10, "rows_out": 4}) == pytest.approx(2.8)


@pytest.mark.parametrize("chips,busy_s,module_s,want", [
    (1, 0.5, 0.5, 2.8),
    # a mesh: a module's span holds its wait for the slowest chip's input
    # (three traced runs of one tree read 0.076-0.164 s of modules over the
    # same 0.050 s of ops); the kernel's time is the ops'
    (4, 0.125, 0.4, 2.8),
    (4, 0.125, 0.2, 2.8),
])
def test_roofline_is_over_the_time_ops_ran_not_the_modules_spans(
        chips, busy_s, module_s, want):
    obs = Obs()
    obs.trace = {"chips": chips, "window_s": 2.0, "busy_s": busy_s,
                 "module_s": module_s}
    obs.peaks = {"hbm_bytes_per_s": 819e9}
    obs.prom["trace"] = [{}, {"seaweedfs_ec_service_batch_bytes_sum": 8.19e9}]
    assert gf_hbm_roofline.read(
        obs, {"rows_in": 10, "rows_out": 4}) == pytest.approx(want)


def test_recorded_tpu_trace():
    planes = tr.load_planes(FIXTURE)
    names = [p for p, _ in planes]
    assert "/device:TPU:0" in names and "/host:CPU" in names
    lines = dict(planes[names.index("/device:TPU:0")][1])
    assert {"XLA Modules", "XLA Ops"} <= set(lines)
    out = tr.reduce_planes(planes)
    with open(EXPECTED) as f:
        want = json.load(f)
    assert out["chips"] == 1
    for key in ("window_s", "busy_s", "module_s"):
        assert out[key] == pytest.approx(want[key], rel=1e-9), key
    assert out["busy_s"] <= out["module_s"] + 1e-6 <= out["window_s"]
    assert [m[0] for m in out["modules"]] == [m[0] for m in want["modules"]]
    assert out["modules"][0][0] == "jit_apply"
    assert out["device_ops"][0][0] == want["device_ops"][0][0]
    # one rpc of a 1 GiB volume is seven service batches: six gaps
    assert out["idle_gaps"] == want["idle_gaps"] and len(out["idle_gaps"]) == 6
    assert out["idle_gaps"][0][0].startswith("np.asarray(jax.Array) 23%")
    obs = Obs()
    obs.trace = out
    assert trace_idle_pct.read(obs, {}) == pytest.approx(91.5377, abs=1e-3)
    assert "describe" and tr.describe(planes).count("/device:TPU:0") >= 2
