"""Device batches of more than one volume (ISSUE 30): jobs of several
streams in one device program, byte for byte the plain reference's, on a
one-device mesh and on the mesh a four-device process builds for itself;
the cap derived from the devices' memory; one width bucket and one job of
a stream to a batch; a power of two of volumes; every program a batch can
take compiled before it forms; eight encodes at once through one service."""

from __future__ import annotations

import os
import sys
import threading
import time

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from benchmark import reference_gf as ref  # noqa: E402
from helpers import empty_slice_pool, run_four_device_child  # noqa: E402

from seaweedfs_tpu.ops import codec_service  # noqa: E402
from seaweedfs_tpu.ops.codec_service import CodecService  # noqa: E402
from seaweedfs_tpu.stats.metrics import (  # noqa: E402
    EC_ENCODES_INFLIGHT,
    EC_SERVICE_BATCH_JOBS,
    EC_SERVICE_BLOCK_BYTES,
    EC_SERVICE_READBACKS,
    EC_SLICE_BUFFERS,
    EC_SLICE_POOL_BYTES,
)
from seaweedfs_tpu.storage.ec import encoder  # noqa: E402

# unequal widths: whole buckets, a few bytes over one, a few under
WIDTHS = [16384, 11200, 16400, 4096, 32768, 15984, 16384, 4800]


@pytest.fixture(autouse=True)
def _clean_service_state():
    yield
    codec_service.shutdown_all(timeout=10)


def _device_service(devices: int = 1, **kw):
    """A device-mode service on a mesh of the suite's first `devices` host
    devices, all on the column axis as the service's own mesh is."""
    import jax

    from seaweedfs_tpu.parallel.mesh import make_mesh

    return CodecService(mode="device", codec_name="tpu_xor",
                        mesh=make_mesh(jax.devices()[:devices], dp=1), **kw)


_one_device_service = _device_service


def _hold_scheduler(svc):
    """Keep submitted jobs queued until the returned release() is called, so
    a test decides what the queue holds when a batch is collected."""
    svc._cond.acquire()
    return svc._cond.release


def _jobs_per_batch():
    child = EC_SERVICE_BATCH_JOBS.labels("pipeline")
    return child.total, child.count


def _delivered(result, want) -> bool:
    return np.array_equal(np.stack([np.asarray(r) for r in result]), want)


def _same_as_reference(result, data) -> bool:
    return _delivered(result, ref.parity_of(data))


# -- V volumes in one block -------------------------------------------------------


@pytest.mark.parametrize("v", [2, 3, 8])
def test_batch_of_v_streams_equals_the_reference_job_by_job(v):
    rng = np.random.default_rng(30 + v)
    datas = [rng.integers(0, 256, (10, w), dtype=np.uint8)
             for w in WIDTHS[:v]]
    svc = _one_device_service()
    jobs0, batches0 = _jobs_per_batch()
    block0 = EC_SERVICE_BLOCK_BYTES.labels("pipeline").value
    release = _hold_scheduler(svc)
    try:
        futs = [svc.submit_parity(d, stream=f"vol{i}")
                for i, d in enumerate(datas)]
    finally:
        release()
    for fut, data in zip(futs, datas):
        assert _same_as_reference(fut.result(120), data)
    svc.close()
    jobs1, batches1 = _jobs_per_batch()
    assert jobs1 - jobs0 == v
    # one width bucket and a power of two of volumes to a batch
    assert batches1 - batches0 == _batches_of(WIDTHS[:v])
    # every job is sent at its own bucket's width and no wider
    sent = EC_SERVICE_BLOCK_BYTES.labels("pipeline").value - block0
    assert sent == 10 * sum(CodecService._pad_width(w, 1) for w in WIDTHS[:v])


def _batches_of(widths, devices: int = 1) -> int:
    """Batches the scheduler makes of jobs of these widths queued at once,
    one stream each: per bucket, its count's powers of two."""
    counts = {}
    for w in widths:
        bucket = CodecService._pad_width(w, devices)
        counts[bucket] = counts.get(bucket, 0) + 1
    return sum(bin(n).count("1") for n in counts.values())


_FOUR_DEVICE_CHILD = """
import json, sys
import numpy as np
sys.path.insert(0, %(root)r)
import jax
from benchmark import reference_gf as ref
from seaweedfs_tpu.ops.codec_service import CodecService
from seaweedfs_tpu.stats.metrics import EC_SERVICE_BATCH_JOBS, EC_SERVICE_BLOCK_BYTES
widths = %(widths)r
out = {"devices": len(jax.devices())}
for v in (2, 3, 8):
    rng = np.random.default_rng(30 + v)
    datas = [rng.integers(0, 256, (10, w), dtype=np.uint8) for w in widths[:v]]
    svc = CodecService(mode="device", codec_name="tpu_xor")  # its own mesh
    child = EC_SERVICE_BATCH_JOBS.labels("pipeline")
    b0, sent0 = child.count, EC_SERVICE_BLOCK_BYTES.labels("pipeline").value
    with svc._cond:
        futs = [svc.submit_parity(d, stream=i) for i, d in enumerate(datas)]
    same = [bool(np.array_equal(np.stack([np.asarray(r) for r in f.result(120)]),
                                ref.parity_of(d))) for f, d in zip(futs, datas)]
    sent = EC_SERVICE_BLOCK_BYTES.labels("pipeline").value - sent0
    out[str(v)] = {"same": same, "batches": child.count - b0, "mesh": svc.mesh_shape(),
                   "pad_pct": 100.0 * (sent / (10 * sum(widths[:v])) - 1)}
    # a lone whole-bucket job still goes in as it is, on four devices too
    lone = rng.integers(0, 256, (10, 16384), dtype=np.uint8)
    before = {p: c.value for p, c in __import__(
        "seaweedfs_tpu.ops.codec_service", fromlist=["x"])._INPUT_BYTES.items()}
    assert np.array_equal(np.stack([np.asarray(r) for r in svc.submit_parity(lone).result(120)]),
                          ref.parity_of(lone))
    after = {p: c.value for p, c in __import__(
        "seaweedfs_tpu.ops.codec_service", fromlist=["x"])._INPUT_BYTES.items()}
    out[str(v)]["lone_direct"] = after["direct"] - before["direct"] == lone.size
    svc.close()
# V equal jobs queued at once are ONE batch of V: its result comes back whole
from seaweedfs_tpu.parallel import mesh as mesh_mod
results = []
real = mesh_mod.jobs_apply_sharded
mesh_mod.jobs_apply_sharded = lambda *a: (results.append(real(*a)), results[-1])[1]
for v in (1, 2, 4, 8):
    rng = np.random.default_rng(38 + v)
    datas = [rng.integers(0, 256, (10, 16384), dtype=np.uint8) for _ in range(v)]
    svc = CodecService(mode="device", codec_name="tpu_xor")
    del results[:]
    with svc._cond:
        futs = [svc.submit_parity(d, stream=i) for i, d in enumerate(datas)]
    got = [f.result(120) for f in futs]
    svc.close()
    dev = results[0].dev
    out["whole%%d" %% v] = {
        "same": [bool(np.array_equal(np.stack([np.asarray(r) for r in g]), ref.parity_of(d)))
                 for g, d in zip(got, datas)],
        "batches": len(results), "shape": list(dev.shape),
        "replicated": bool(dev.sharding.is_fully_replicated),
        "on": len(dev.sharding.device_set),
        "one_readback": len({g.base.ctypes.data if g.base is not None else id(g)
                             for g in got}) == 1 and not any(g.flags["OWNDATA"] for g in got)}
print(json.dumps(out))
"""


@pytest.fixture(scope="module")
def four_device_run():
    """One child process whose CPU backend has four devices: the service
    builds its mesh from `jax.devices()`, as a server on four chips does."""
    return run_four_device_child(
        _FOUR_DEVICE_CHILD % {"root": ROOT, "widths": WIDTHS})


@pytest.mark.parametrize("v", [2, 3, 8, "whole1", "whole2", "whole4",
                               "whole8"])
def test_four_device_mesh_batches_equal_the_reference(four_device_run, v):
    assert four_device_run["devices"] == 4
    got = four_device_run[str(v)]
    if isinstance(v, str):
        # V equal jobs, one batch: the (V, R, T, 128) stack is gathered, so
        # every device holds all of it and the host fetches one array, of
        # which every job's rows are views
        v = int(v[len("whole"):])
        assert got["same"] == [True] * v
        assert got["batches"] == 1 and got["shape"] == [v, 4, 32, 128]
        assert got["replicated"] is True and got["on"] == 4
        assert got["one_readback"] is True
        return
    assert got["same"] == [True] * v
    # columns over all four devices: no padding volume, whatever V is
    assert got["mesh"] == "1x4"
    assert got["batches"] == _batches_of(WIDTHS[:v], 4)
    assert got["lone_direct"] is True


# -- the readback starts at dispatch (ISSUE 38) ---------------------------------------


def _readbacks(cls: str) -> dict:
    return {state: EC_SERVICE_READBACKS.labels(state, cls).value
            for state in ("ready", "waited")}


def _decode_rows():
    """The 4 x 10 plan of a volume that lost shards 0-3, from the plain
    reference: survivors 4-13 -> the lost rows."""
    inv = ref.mat_inv([ref.MATRIX[i] for i in range(4, 14)])
    return np.asarray(inv[:4], dtype=np.uint8)


def _submit_batches(svc, cls: str, rng, batches: int):
    """`batches` device batches of two jobs each -> [(future, want)]."""
    out = []
    rows = _decode_rows()
    for _ in range(batches):
        datas = [rng.integers(0, 256, (10, w), dtype=np.uint8)
                 for w in (4096, 4000)]
        release = _hold_scheduler(svc)
        try:
            if cls == "read":
                futs = [svc.submit_apply(rows, d, job_class="read")
                        for d in datas]
                wants = [ref.apply_rows(rows.tolist(), d) for d in datas]
            else:
                futs = [svc.submit_parity(d, stream=i)
                        for i, d in enumerate(datas)]
                wants = [ref.parity_of(d) for d in datas]
        finally:
            release()
        if cls == "read":
            # queued reads of one plan all go side by side into one block:
            # the next two wait until these are through
            for fut in futs:
                fut._job.event.wait(120)
        out.extend(zip(futs, wants))
    return out


@pytest.mark.parametrize("cls", ["pipeline", "read"])
@pytest.mark.parametrize("devices", [1, 4])
def test_readback_starts_inside_dispatch_once_a_batch(
        monkeypatch, cls, devices):
    """One `copy_to_host_async` a device batch, called before
    `_dispatch_device` returns — so before `_complete_device` comes for the
    result — for both job classes; and the counter says of every batch
    whether its program had finished by then."""
    from seaweedfs_tpu.ops import rs_pallas

    events, kept = [], []  # kept: no id is given out twice
    real_async = rs_pallas.PackedRows.copy_to_host_async
    monkeypatch.setattr(
        rs_pallas.PackedRows, "copy_to_host_async", lambda self: (
            kept.append(self), events.append(("async", id(self))),
            real_async(self))[2])
    svc = _device_service(devices)
    real_dispatch, real_complete = svc._dispatch_device, svc._complete_device
    monkeypatch.setattr(svc, "_dispatch_device", lambda batch, tags: (
        events.append(("dispatch", None)), real_dispatch(batch, tags),
        events.append(("dispatched", None)))[1])
    monkeypatch.setattr(svc, "_complete_device", lambda batch, sent, tags: (
        events.append(("complete", id(sent[0]))),
        real_complete(batch, sent, tags))[1])
    before = _readbacks(cls)
    batches0 = EC_SERVICE_BATCH_JOBS.labels(cls).count
    jobs = _submit_batches(svc, cls, np.random.default_rng(38), 3)
    for fut, want in jobs:
        assert _delivered(fut.result(120), want)
    svc.close()
    started = [e for e in events if e[0] == "async"]
    assert len(started) == len({ident for _, ident in started}) == 3
    for _, ident in started:
        at = events.index(("async", ident))
        # inside its own dispatch, and before anybody asks for the result
        assert events[at - 1] == ("dispatch", None)
        assert events[at + 1] == ("dispatched", None)
        assert at < events.index(("complete", ident))
    after = _readbacks(cls)
    moved = {st: after[st] - before[st] for st in after}
    assert sum(moved.values()) == 3 == (
        EC_SERVICE_BATCH_JOBS.labels(cls).count - batches0)
    assert min(moved.values()) >= 0


def test_d2h_span_says_whether_the_result_was_ready(monkeypatch):
    from seaweedfs_tpu.telemetry import trace

    seen = []
    real_stage = trace.stage
    monkeypatch.setattr(codec_service.trace, "stage", lambda name, hist=None, **attrs: (
        seen.append((name, attrs)), real_stage(name, hist, **attrs))[1])
    svc = _one_device_service()
    before = _readbacks("pipeline")
    # a result that is long there when the scheduler comes for it
    real_complete = svc._complete_device
    monkeypatch.setattr(svc, "_complete_device", lambda batch, sent, tags: (
        sent[0].block_until_ready(), real_complete(batch, sent, tags))[1])
    for fut, _want in _submit_batches(svc, "pipeline",
                                      np.random.default_rng(39), 2):
        fut.result(120)
    svc.close()
    states = [attrs["state"] for name, attrs in seen if name == "ec.svc.d2h"]
    assert states == ["ready", "ready"]
    after = _readbacks("pipeline")
    assert (after["ready"] - before["ready"],
            after["waited"] - before["waited"]) == (2, 0)
    assert not any("state" in attrs for name, attrs in seen
                   if name != "ec.svc.d2h")


@pytest.mark.parametrize("cls", ["pipeline", "read"])
def test_failed_readback_start_fails_its_batch_and_the_next_runs(
        monkeypatch, cls):
    """`copy_to_host_async` raises for the second of three batches: every
    job of that batch fails with it, nobody is left waiting, the batch in
    flight before it and the one after it deliver, and no readback is
    counted for a batch that never came back."""
    from seaweedfs_tpu.ops import rs_pallas

    calls = []
    real_async = rs_pallas.PackedRows.copy_to_host_async

    def start(self):
        calls.append(self)
        if len(calls) == 2:
            raise RuntimeError("the transfer could not start")
        return real_async(self)

    monkeypatch.setattr(rs_pallas.PackedRows, "copy_to_host_async", start)
    svc = _one_device_service()
    before = _readbacks(cls)
    jobs = _submit_batches(svc, cls, np.random.default_rng(40), 3)
    for k, (fut, want) in enumerate(jobs):
        if k // 2 == 1:
            with pytest.raises(RuntimeError, match="could not start"):
                fut.result(120)
        else:
            assert _delivered(fut.result(120), want)
    assert not svc.closed and svc._thread_err is None
    # the service is still there for whoever comes next
    for fut, want in _submit_batches(svc, cls, np.random.default_rng(41), 1):
        assert _delivered(fut.result(120), want)
    svc.close()
    after = _readbacks(cls)
    assert sum(after.values()) - sum(before.values()) == 3


# -- what a batch may hold ----------------------------------------------------------


def _queued(svc, datas, streams=None):
    for i, d in enumerate(datas):
        svc._q.append(codec_service._Job(
            "parity", svc._parity_key, svc.parity_matrix, d, d.shape[1],
            None, None if streams is None else streams[i]))


def test_device_cap_is_derived_from_the_devices_memory():
    svc = _one_device_service()
    one_slice = 10 * encoder.DEFAULT_SLICE
    # a CPU backend reports no memory: the unreported default (one v5e's
    # 16 GiB) stands in.  Eight of the encoder's (10, 16 MiB) slices to a
    # batch on one chip, where the cap of before (64 MB) held none; the
    # job cap of sixteen on four; a head job of any size goes alone
    assert 8 <= svc._device_max_volumes(one_slice) < 16
    assert svc._device_bytes == int(
        codec_service._HBM_SHARE * codec_service._HBM_BYTES_UNREPORTED)
    assert svc._device_max_volumes(64 * one_slice) == 1
    four = CodecService(mode="device", codec_name="tpu_xor")
    four._device_bytes = 4 * svc._device_bytes
    assert four._device_max_volumes(one_slice) == four.max_batch == 16
    svc.close()


def _hold(svc, job_bytes: int, volumes: int) -> None:
    """Give the service devices that hold `volumes` jobs of `job_bytes`
    to a batch by _device_max_volumes' own count, and not one more."""
    svc._device_bytes = int(job_bytes * (
        codec_service._HBM_TEMP_PER_JOB_BYTE
        + 2 * codec_service._HBM_RESIDENT_PER_JOB_BYTE * (volumes + 0.5)))


@pytest.mark.parametrize("case", ["never_over_the_cap", "head_over_the_cap"])
def test_batch_never_exceeds_the_cap_but_the_head_always_goes(case):
    svc = _one_device_service(max_batch=16)
    _hold(svc, 10 * (16 << 10), 6)
    rng = np.random.default_rng(5)
    widths = ([16 << 10] * 12 if case == "never_over_the_cap"
              else [256 << 10] * 3)
    datas = [rng.integers(0, 256, (10, w), dtype=np.uint8) for w in widths]
    with svc._cond:
        _queued(svc, datas, streams=list(range(len(datas))))
        batch, reason = svc._collect_locked()
        left = len(svc._q)
        svc._q.clear()
    # what the cap counts: two batches' arrays and one program's temporaries
    job_bytes = 10 * CodecService._pad_width(batch[0].width, 1)
    held = job_bytes * (codec_service._HBM_TEMP_PER_JOB_BYTE + 2 * len(batch)
                        * codec_service._HBM_RESIDENT_PER_JOB_BYTE)
    if case == "never_over_the_cap":
        # the devices hold 6 (160 KiB each); a power of two of them go
        assert svc._device_max_volumes(job_bytes) == 6
        assert len(batch) == 4 and held <= svc._device_bytes and left == 8
    else:
        # the head alone is 2.5 MiB: over the cap, and it still goes, alone
        assert len(batch) == 1 and held > svc._device_bytes
        assert reason == "bytes" and left == 2
    svc.close()


def test_one_job_of_a_stream_to_a_batch_and_order_is_kept():
    svc = _one_device_service()
    rng = np.random.default_rng(6)
    datas = [rng.integers(0, 256, (10, 512), dtype=np.uint8)
             for _ in range(6)]
    streams = ["a", "a", "b", "a", "c", "b"]
    with svc._cond:
        _queued(svc, datas, streams)
        first, _ = svc._collect_locked()
        second, _ = svc._collect_locked()
        third, _ = svc._collect_locked()
        assert not svc._q
    # a, b, c are three: two go (a power of two), c's job waits in front
    assert [j.stream for j in first] == ["a", "b"]
    assert [j.stream for j in second] == ["a", "c"]
    assert [j.stream for j in third] == ["a", "b"]
    assert [id(j.data) for j in first + second + third] == [
        id(datas[i]) for i in (0, 2, 1, 4, 3, 5)]
    svc.close()


def test_a_lone_streams_slices_stay_whole_blocks():
    """What keeps the one-rpc cells as they were: two queued slices of one
    volume do not coalesce into a staged V = 2 block."""
    svc = _one_device_service()
    rng = np.random.default_rng(7)
    datas = [rng.integers(0, 256, (10, 4096), dtype=np.uint8)
             for _ in range(3)]
    before = {p: c.value for p, c in codec_service._INPUT_BYTES.items()}
    jobs0, batches0 = _jobs_per_batch()
    release = _hold_scheduler(svc)
    try:
        futs = [svc.submit_parity(d, stream="one volume") for d in datas]
    finally:
        release()
    for fut, data in zip(futs, datas):
        assert _same_as_reference(fut.result(120), data)
    svc.close()
    moved = {p: c.value - before[p]
             for p, c in codec_service._INPUT_BYTES.items()}
    assert moved == {"direct": 3 * 10 * 4096, "staged": 0}
    jobs1, batches1 = _jobs_per_batch()
    assert (jobs1 - jobs0, batches1 - batches0) == (3, 3)


def test_staging_buffers_are_reused_and_their_padding_is_zero(monkeypatch):
    from seaweedfs_tpu.parallel import mesh as mesh_mod

    calls = []
    real = mesh_mod.jobs_apply_sharded

    def capture(mesh, matrix, blocks):
        calls.append([(b.ctypes.data, b.copy()) for b in blocks])
        return real(mesh, matrix, blocks)

    monkeypatch.setattr(mesh_mod, "jobs_apply_sharded", capture)
    svc = _one_device_service()
    rng = np.random.default_rng(8)
    for _round in range(3):
        datas = [rng.integers(1, 256, (10, w), dtype=np.uint8)
                 for w in (4000, 2500)]
        release = _hold_scheduler(svc)
        try:
            futs = [svc.submit_parity(d, stream=i)
                    for i, d in enumerate(datas)]
        finally:
            release()
        for fut, data in zip(futs, datas):
            assert _same_as_reference(fut.result(120), data)
    svc.close()
    assert [len(c) for c in calls] == [2, 2, 2]
    # two staging buffers served all six jobs
    assert len({addr for c in calls for addr, _ in c}) == 2
    for c in calls:
        for (_addr, sent), width in zip(c, (4000, 2500)):
            assert sent.shape == (10, 4096)
            assert not sent[:, width:].any()  # zeroed again on every reuse


@pytest.mark.parametrize("devices", [1, 4])
def test_program_takes_views_and_jobs_get_views_of_one_readback(
        monkeypatch, devices):
    """Both bus crossings are copies of nothing on the host: the program
    is handed uint32 lane-tile views of the jobs' own bytes (of the staging
    buffer for a job off its bucket), and every job's result rows are
    C-contiguous views into the ONE array the readback made — on a mesh
    too, where the program's result is gathered and the host fetches it
    from one device: no whole-shape array is made and filled shard by
    shard on the scheduler's thread."""
    from seaweedfs_tpu.ops import rs_pallas
    from seaweedfs_tpu.parallel import mesh as mesh_mod

    handed, readbacks, on_device = [], [], []
    real_program = mesh_mod._sharded_apply_jobs

    def program(mesh, rows, n):
        fn = real_program(mesh, rows, n)
        return lambda *tiles: (handed.append(tiles), on_device.append(
            fn(*tiles)), on_device[-1])[2]

    monkeypatch.setattr(mesh_mod, "_sharded_apply_jobs", program)
    real_array = rs_pallas.PackedRows.__array__
    monkeypatch.setattr(
        rs_pallas.PackedRows, "__array__", lambda self, *a, **kw: (
            readbacks.append(real_array(self, *a, **kw)), readbacks[-1])[1])
    svc = _device_service(devices)
    rng = np.random.default_rng(31)
    datas = [rng.integers(0, 256, (10, w), dtype=np.uint8)
             for w in (65536, 65536, 65000, 65536)]
    release = _hold_scheduler(svc)
    try:
        futs = [svc.submit_parity(d, stream=i) for i, d in enumerate(datas)]
    finally:
        release()
    results = [fut.result(120) for fut in futs]
    svc.close()
    (tiles,), (readback,) = handed, readbacks  # one batch, one readback
    (dev,) = on_device
    assert len(dev.sharding.device_set) == devices
    assert dev.sharding.is_fully_replicated  # whole on every device
    for tile, data in zip(tiles, datas):
        assert tile.dtype == np.uint32 and tile.shape == (10, 128, 128)
        whole = data.shape[1] == 65536
        assert np.shares_memory(tile, data) is whole
        if whole:  # the caller's own buffer, from its first byte
            assert tile.ctypes.data == data.ctypes.data
    # the readback: the device's (V, R, T, 128) words viewed as bytes
    assert readback.shape == (4, 4, 65536) and readback.dtype == np.uint8
    assert not readback.flags["OWNDATA"]
    for vi, (result, data) in enumerate(zip(results, datas)):
        assert _same_as_reference(result, data)
        assert result.shape == (4, data.shape[1])
        assert not result.flags["OWNDATA"]
        assert all(row.flags["C_CONTIGUOUS"] for row in result)
        # where np.asarray put it, untouched since
        assert result.ctypes.data == readback[vi].ctypes.data


def test_open_streams_warm_every_program_once(monkeypatch):
    import contextlib

    from seaweedfs_tpu.parallel import mesh as mesh_mod

    programs = []
    real = mesh_mod.compile_jobs_apply

    def capture(mesh, matrix, n, shape):
        programs.append((n, shape))
        return real(mesh, matrix, n, shape)

    monkeypatch.setattr(mesh_mod, "compile_jobs_apply", capture)
    svc = _one_device_service(max_batch=16)
    _hold(svc, 10 * 16384, 4)  # and sixteen, the job cap, at 4096
    rng = np.random.default_rng(10)

    def submit(svc, width, stream):
        data = rng.integers(0, 256, (10, width), dtype=np.uint8)
        assert _same_as_reference(
            svc.submit_parity(data, stream=stream).result(120), data)

    # a stream open alone, and one that nobody opened, warm nothing
    with svc.stream("a"):
        submit(svc, 4000, "a")
        submit(svc, 4000, "nobody")
    assert programs == []
    with contextlib.ExitStack() as held:
        for name in "ab":
            held.enter_context(svc.stream(name))
        # two open: V = 1, 2 at the job's own bucket, before it is queued
        submit(svc, 4000, "a")
        assert sorted(programs) == [(1, (10, 4096)), (2, (10, 4096))]
        submit(svc, 9000, "b")
        assert sorted(programs) == sorted(
            [(v, (10, w)) for w in (4096, 16384) for v in (1, 2)])
        for k in range(18):
            held.enter_context(svc.stream(k))
        # twenty open: as many volumes as the devices hold, and no more
        submit(svc, 4096, "b")
        submit(svc, 16384, 3)
        want = sorted([(v, (10, 4096)) for v in (1, 2, 4, 8, 16)]
                      + [(v, (10, 16384)) for v in (1, 2, 4)])
        assert sorted(programs) == want
        submit(svc, 4000, 4)
        submit(svc, 9000, "a")
        assert sorted(programs) == want  # nothing compiles twice
        host = CodecService(mode="host")
        with host.stream("x"), host.stream("y"):
            submit(host, 4096, "x")
        assert sorted(programs) == want  # host mode has no programs
        # a batch of a warmed shape finds its program compiled; one of a
        # shape nobody warmed (jobs of unopened streams) compiles when it
        # forms (the listener sees that much)
        from seaweedfs_tpu.ops import device

        device.enable_compile_cache()
        for width, streams, compiles in (
                (4096, (0, 1, 2, 3), False), (8192, "wxyz", True)):
            spent = device._stats["compile_seconds"]
            datas = [rng.integers(0, 256, (10, width), dtype=np.uint8)
                     for _ in range(4)]
            release = _hold_scheduler(svc)
            try:
                futs = [svc.submit_parity(d, stream=name)
                        for name, d in zip(streams, datas)]
            finally:
                release()
            for fut, data in zip(futs, datas):
                assert _same_as_reference(fut.result(120), data)
            assert (device._stats["compile_seconds"] > spent) is compiles
    assert svc._streams == {}
    svc.close()


# -- eight encodes at once ------------------------------------------------------------


def _make_dat(path: str, size: int, seed: int) -> None:
    with open(path, "wb") as f:
        f.write(np.random.default_rng(seed).integers(
            0, 256, size, dtype=np.uint8).tobytes())


def _shard_bytes(base: str) -> list:
    out = []
    for i in range(14):
        with open(f"{base}.ec{i:02d}", "rb") as f:
            out.append(f.read())
    return out


def test_eight_concurrent_encodes_write_what_one_at_a_time_writes(
        tmp_path, monkeypatch):
    """Eight `generate_ec_files` at once through ONE device-mode service
    (what eight VolumeEcShardsGenerate rpcs are to a volume server): the
    files equal a lone encode's, batches carried more than one volume, the
    gauge counted them, and their open streams warmed the batch programs."""
    large, small, slice_size = 8192, 512, 4096
    sizes = [70_000, 70_000, 41_000, 70_000, 12_345, 70_000, 55_555, 70_000]
    for k, size in enumerate(sizes):
        _make_dat(str(tmp_path / f"one_{k}.dat"), size, seed=k)
        os.link(tmp_path / f"one_{k}.dat", tmp_path / f"many_{k}.dat")
    from seaweedfs_tpu.parallel import mesh as mesh_mod

    svc = _one_device_service()
    programs = []
    real_compile = mesh_mod.compile_jobs_apply
    monkeypatch.setattr(mesh_mod, "compile_jobs_apply", lambda m, r, n, shape: (
        programs.append((n, shape)), real_compile(m, r, n, shape))[1])
    peak = [0.0]

    def encode(base):
        encoder.generate_ec_files(
            base, large_block_size=large, small_block_size=small,
            codec_name="tpu_xor", slice_size=slice_size, service=svc)

    for k in range(len(sizes)):
        encode(str(tmp_path / f"one_{k}"))
    # a server that never holds two encodes compiles nothing ahead
    assert programs == [] and EC_ENCODES_INFLIGHT.labels().value == 0

    # a scheduler slower than its eight producers, as the chip's is at
    # the served sizes: jobs of other volumes queue behind each batch
    real_dispatch = svc._dispatch_device
    monkeypatch.setattr(svc, "_dispatch_device", lambda batch, tags: (
        time.sleep(0.05), real_dispatch(batch, tags))[1])
    jobs0, batches0 = _jobs_per_batch()
    gate = threading.Barrier(len(sizes))
    real_stream = svc.stream

    import contextlib

    @contextlib.contextmanager
    def open_together(name):
        # every encode's stream is open before any goes on: what eight
        # rpcs arriving together look like, made certain
        with real_stream(name):
            gate.wait(30)
            peak[0] = max(peak[0], EC_ENCODES_INFLIGHT.labels().value)
            yield

    monkeypatch.setattr(svc, "stream", open_together)
    errors = []

    def guarded(base):
        try:
            encode(base)
        except Exception as e:  # noqa: BLE001 — reported below
            errors.append(e)

    threads = [threading.Thread(target=guarded,
                                args=(str(tmp_path / f"many_{k}"),))
               for k in range(len(sizes))]
    for t in threads:
        t.start()
    for t in threads:
        t.join(300)
    svc.close()
    assert not errors, errors
    assert peak[0] == len(sizes) and EC_ENCODES_INFLIGHT.labels().value == 0
    assert svc._streams == {}
    # every program a batch of eight streams can take, each compiled once
    assert len(set(programs)) == len(programs)
    assert {n for n, _shape in programs} == {1, 2, 4, 8}
    for k in range(len(sizes)):
        assert _shard_bytes(str(tmp_path / f"many_{k}")) == _shard_bytes(
            str(tmp_path / f"one_{k}")), k
    jobs1, batches1 = _jobs_per_batch()
    assert jobs1 - jobs0 > batches1 - batches0  # some batch held V > 1


def test_encode_recycles_six_slice_buffers_and_never_one_in_use(
        tmp_path, monkeypatch):
    """The pipelined encode keeps _POOL_SLICES slice buffers however many
    slices the volume has, and refills one only after its parity is back
    and its rows are written: shards equal the host codec's."""
    large, small, slice_size = 8192, 512, 2048
    _make_dat(str(tmp_path / "pooled.dat"), 600_000, seed=99)
    os.link(tmp_path / "pooled.dat", tmp_path / "plain.dat")
    svc = _one_device_service()
    owned = []  # (the slice submitted, its future)
    real_submit = svc.submit_parity
    monkeypatch.setattr(svc, "submit_parity", lambda data, out=None, stream=None: (
        owned.append((data, real_submit(data, out, stream))), owned[-1][1])[1])
    filled_early, buffers = [], set()
    real_fill = encoder.fill_stripe_rows

    def fill(f, batch, dest):
        buffers.add((dest if dest.base is None else dest.base).ctypes.data)
        filled_early.extend(
            1 for data, fut in list(owned)
            if not fut.done() and np.shares_memory(dest, data))
        real_fill(f, batch, dest)

    monkeypatch.setattr(encoder, "fill_stripe_rows", fill)
    encoder.generate_ec_files(
        str(tmp_path / "pooled"), large_block_size=large,
        small_block_size=small, codec_name="tpu_xor", slice_size=slice_size,
        service=svc)
    svc.close()
    monkeypatch.setattr(encoder, "fill_stripe_rows", real_fill)
    encoder.generate_ec_files(
        str(tmp_path / "plain"), large_block_size=large,
        small_block_size=small, codec_name="cpu", slice_size=slice_size)
    assert len(owned) > 2 * encoder._POOL_SLICES  # many more slices than buffers
    assert 1 <= len(buffers) <= encoder._POOL_SLICES
    assert not filled_early
    assert _shard_bytes(str(tmp_path / "pooled")) == _shard_bytes(
        str(tmp_path / "plain"))


# -- the process's slice pool (ISSUE 34) ------------------------------------------------

GEOM = dict(large_block_size=8192, small_block_size=512)
SLICE = 2048  # four 512-byte rows to a slice


@pytest.fixture
def slice_pool(monkeypatch):
    """A pool of this test's own.  The process's is emptied first and this
    one afterwards, so the gauge reads this one alone."""
    empty_slice_pool(monkeypatch)
    pool = encoder._SlicePool()
    monkeypatch.setattr(encoder, "_SLICE_POOL", pool)
    yield pool
    empty_slice_pool(monkeypatch, pool)


def _slices(dat_size: int) -> int:
    return len(list(encoder._slice_tasks(
        dat_size, GEOM["large_block_size"], GEOM["small_block_size"], SLICE)))


def _free_ids(pool, slice_size=SLICE) -> set:
    return {id(buf) for _at, buf in pool._free.get(slice_size, ())}


def _taken(pipeline: str) -> dict:
    return {source: EC_SLICE_BUFFERS.labels(pipeline, source).value
            for source in ("pooled", "fresh")}


def _pool_bytes() -> float:
    return EC_SLICE_POOL_BYTES.labels().value


def _filled_buffers(monkeypatch) -> list:
    """-> the list every fill_stripe_rows call appends its slice buffer to."""
    filled = []
    real_fill = encoder.fill_stripe_rows
    monkeypatch.setattr(encoder, "fill_stripe_rows", lambda f, batch, dest: (
        filled.append(dest if dest.base is None else dest.base),
        real_fill(f, batch, dest))[1])
    return filled


def _host_shards(tmp_path, name: str) -> list:
    """What the host codec (the mmap path: no pool) writes for <name>.dat."""
    os.link(tmp_path / f"{name}.dat", tmp_path / f"{name}_host.dat")
    encoder.generate_ec_files(str(tmp_path / f"{name}_host"),
                              codec_name="cpu", slice_size=SLICE, **GEOM)
    return _shard_bytes(str(tmp_path / f"{name}_host"))


def test_second_encode_reads_into_the_firsts_buffers(
        tmp_path, monkeypatch, slice_pool):
    """Two encodes in a row through the service, the pool holding 0xFF
    buffers before the first and the first volume's bytes before the
    second, which is shorter and ends past EOF: every byte used is written
    first, and no buffer is made."""
    for _ in range(encoder._POOL_SLICES):
        slice_pool.give_back(np.full((10, SLICE), 0xFF, dtype=np.uint8))
    ours = _free_ids(slice_pool)
    _make_dat(str(tmp_path / "first.dat"), 600_000, seed=1)
    _make_dat(str(tmp_path / "second.dat"), 123_457, seed=2)  # a 1-row tail
    want = {name: _host_shards(tmp_path, name) for name in ("first", "second")}
    filled = _filled_buffers(monkeypatch)
    svc = _one_device_service()
    before = _taken("encode")
    used = {}
    for name in ("first", "second"):
        del filled[:]
        encoder.generate_ec_files(str(tmp_path / name), codec_name="tpu_xor",
                                  slice_size=SLICE, service=svc, **GEOM)
        used[name] = {id(buf) for buf in filled}
        assert _shard_bytes(str(tmp_path / name)) == want[name], name
    svc.close()
    after = _taken("encode")
    assert after["fresh"] == before["fresh"]
    assert after["pooled"] - before["pooled"] == sum(  # every slice
        _slices(size) for size in (600_000, 123_457))
    assert used["second"] <= used["first"] <= ours
    assert _free_ids(slice_pool) == ours
    assert _pool_bytes() == len(ours) * 10 * SLICE


def test_rebuild_takes_the_encodes_buffers(tmp_path, slice_pool):
    _make_dat(str(tmp_path / "vol.dat"), 300_000, seed=3)
    base = str(tmp_path / "vol")
    svc = _one_device_service()
    encoder.generate_ec_files(base, codec_name="tpu_xor", slice_size=SLICE,
                              service=svc, **GEOM)
    want, left = _shard_bytes(base), _free_ids(slice_pool)
    assert 1 <= len(left) <= encoder._POOL_SLICES
    for sid in (0, 1, 2, 3):
        os.remove(f"{base}.ec{sid:02d}")
    before = _taken("rebuild")
    assert encoder.rebuild_ec_files(
        base, codec_name="tpu_xor", slice_size=SLICE, service=svc) == [
            0, 1, 2, 3]
    svc.close()
    after = _taken("rebuild")
    assert after["fresh"] == before["fresh"]
    assert after["pooled"] > before["pooled"]
    assert _free_ids(slice_pool) == left
    assert _shard_bytes(base) == want


@pytest.mark.parametrize("failing", [0, 3])
def test_failed_encode_gives_back_nothing_it_still_held(
        tmp_path, monkeypatch, slice_pool, failing):
    """The service raises for slice `failing`: only slices whose rows the
    writer had put in the shard files gave their buffers back; what the
    pipeline held when it failed is dropped; the next encode is right."""
    from concurrent.futures import Future

    _make_dat(str(tmp_path / "vol.dat"), 300_000, seed=4)
    want = _host_shards(tmp_path, "vol")
    filled = _filled_buffers(monkeypatch)
    svc = _one_device_service()
    real_submit, calls = svc.submit_parity, []

    def submit(data, out=None, stream=None):
        calls.append(data)
        if len(calls) - 1 == failing:
            fut = Future()
            fut.set_exception(RuntimeError("the device is gone"))
            return fut
        return real_submit(data, out, stream)

    monkeypatch.setattr(svc, "submit_parity", submit)
    written = []
    with pytest.raises(RuntimeError, match="the device is gone"):
        encoder.generate_ec_files(
            str(tmp_path / "vol"), codec_name="tpu_xor", slice_size=SLICE,
            service=svc, progress=written.append, **GEOM)
    assert len(written) <= failing  # slices are written in order
    held = {id(buf) for buf in filled[len(written):]}
    assert held and not (_free_ids(slice_pool) & held)
    assert len(_free_ids(slice_pool)) <= len(written)
    assert _pool_bytes() == len(_free_ids(slice_pool)) * 10 * SLICE
    monkeypatch.setattr(svc, "submit_parity", real_submit)
    encoder.generate_ec_files(str(tmp_path / "vol"), codec_name="tpu_xor",
                              slice_size=SLICE, service=svc, **GEOM)
    svc.close()
    assert _shard_bytes(str(tmp_path / "vol")) == want


def test_eight_encodes_at_once_make_at_most_four_buffers_each(
        tmp_path, slice_pool):
    """Between the pool and eight pipelines there are never more than
    8 x _POOL_SLICES buffers: no more than that are ever made, and a
    second round of eight makes none."""
    n = 8
    for k in range(n):
        _make_dat(str(tmp_path / f"v{k}.dat"), 200_000 + 4321 * k, seed=k)
    want = [_host_shards(tmp_path, f"v{k}") for k in range(n)]
    errors = []

    def encode(k):
        try:  # a device codec with no service: the pipelined path, direct
            encoder.generate_ec_files(
                str(tmp_path / f"v{k}"), codec_name="tpu_xor",
                slice_size=SLICE, **GEOM)
        except Exception as e:  # noqa: BLE001 — reported below
            errors.append(e)

    before = _taken("encode")
    for round_ in range(2):
        threads = [threading.Thread(target=encode, args=(k,))
                   for k in range(n)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(300)
        assert not errors, errors
        made = _taken("encode")["fresh"] - before["fresh"]
        free = len(_free_ids(slice_pool))
        assert n <= free == made <= n * encoder._POOL_SLICES, round_
        assert _pool_bytes() == free * 10 * SLICE
        if round_ == 0:
            made_first = made
    assert made == made_first
    for k in range(n):
        assert _shard_bytes(str(tmp_path / f"v{k}")) == want[k], k


def test_idle_pool_is_released_after_the_fixed_period(
        tmp_path, monkeypatch, slice_pool):
    monkeypatch.setattr(encoder, "_POOL_IDLE_S", 0.3)
    _make_dat(str(tmp_path / "vol.dat"), 100_000, seed=5)

    def encode():
        encoder.generate_ec_files(str(tmp_path / "vol"), codec_name="tpu_xor",
                                  slice_size=SLICE, **GEOM)

    encode()
    assert _pool_bytes() > 0 and _free_ids(slice_pool)
    deadline = time.monotonic() + 10
    while _pool_bytes() and time.monotonic() < deadline:
        time.sleep(0.05)
    assert _pool_bytes() == 0 and not _free_ids(slice_pool)
    assert slice_pool._timer is None  # nothing free: nothing pending
    before = _taken("encode")
    encode()  # an encode an hour later pays what it paid before the pool
    after = _taken("encode")
    assert after["fresh"] > before["fresh"]
    assert sum(after.values()) - sum(before.values()) == _slices(100_000)


def test_batch_spans_say_volumes_padding_and_mesh(monkeypatch):
    from seaweedfs_tpu.telemetry import trace

    seen = []
    real_stage = trace.stage
    monkeypatch.setattr(codec_service.trace, "stage", lambda name, hist=None, **attrs: (
        seen.append((name, attrs)), real_stage(name, hist, **attrs))[1])
    svc = _one_device_service()
    rng = np.random.default_rng(9)
    datas = [rng.integers(0, 256, (10, w), dtype=np.uint8)
             for w in (4096, 4000)]
    release = _hold_scheduler(svc)
    try:
        futs = [svc.submit_parity(d, stream=i) for i, d in enumerate(datas)]
    finally:
        release()
    for fut in futs:
        fut.result(120)
    svc.close()
    by_name = dict(seen)
    for name in ("ec.svc.build", "ec.svc.enqueue"):
        attrs = by_name[name]
        assert (attrs["volumes"], attrs["v_pad"], attrs["mesh"]) == (2, 2, "1x1")
        assert attrs["jobs"] == 2 and attrs["bytes"] == 10 * 8096
    assert by_name["ec.svc.build"]["path"] == "mixed"  # one whole, one not
    # the form a batch crossed the bus in, both ways
    for name in ("ec.svc.enqueue", "ec.svc.d2h"):
        assert by_name[name]["layout"] == "u32x128"
