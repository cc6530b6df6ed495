"""Codec service + device choice: byte identity (host/device/batched vs
single), fairness under a saturating producer, clean shutdown with jobs
in flight, and NO fallback to the host codec when a device codec's
backend is unusable (it raises)."""

from __future__ import annotations

import os
import threading
import time

import numpy as np
import pytest

from seaweedfs_tpu.ops import codec as codec_mod
from seaweedfs_tpu.ops import codec_service, device, gf256
from seaweedfs_tpu.ops.codec import get_codec
from seaweedfs_tpu.ops.codec_service import CodecService
from seaweedfs_tpu.ops.rs_cpu import ReedSolomon


@pytest.fixture(autouse=True)
def _clean_service_state():
    yield
    codec_service.shutdown_all(timeout=10)
    codec_mod._AUTO_CHOICE.clear()


def _rand_block(rng, width):
    return rng.integers(0, 256, (10, width), dtype=np.uint8)


def _as2d(result):
    return np.stack([np.asarray(r) for r in result])


# -- device choice: in process, never degraded ------------------------------


def _no_backend(*_a, **_k):
    raise RuntimeError("Unable to initialize backend 'tpu': test says no")


def test_held_device_reports_this_process_backend(monkeypatch):
    import subprocess

    def boom(*a, **k):
        raise AssertionError("the device question must not start a child")

    monkeypatch.setattr(subprocess, "run", boom)
    monkeypatch.setattr(subprocess, "Popen", boom)
    dev = device.held_device()
    assert set(dev) == {"platform", "kind", "count"}
    assert dev["platform"] == "cpu"  # conftest pins the cpu backend
    assert dev["count"] == 8


def test_held_device_raises_when_backend_cannot_initialise(monkeypatch):
    import jax

    monkeypatch.setattr(jax, "devices", _no_backend)
    with pytest.raises(RuntimeError, match="Unable to initialize"):
        device.held_device()


def test_device_codec_never_degrades_to_host_codec(monkeypatch):
    """A device codec whose backend is unusable RAISES — from the codec's
    first device call, from the service routing the rpcs take, and from
    the volume server's start — instead of serving from cpu_simd."""
    import jax
    import jax.numpy as jnp

    codec = get_codec("tpu")
    assert codec._impl == "pallas"  # InstrumentedCodec label: not "cpu"
    monkeypatch.setattr(jax, "devices", _no_backend)
    monkeypatch.setattr(jnp, "asarray", _no_backend)
    with pytest.raises(RuntimeError, match="Unable to initialize"):
        codec.parity_of(np.zeros((10, 512), np.uint8))
    with pytest.raises(RuntimeError, match="Unable to initialize"):
        codec_service.service_for_codec("tpu")
    with pytest.raises(RuntimeError, match="Unable to initialize"):
        get_codec("auto")

    import tempfile

    from seaweedfs_tpu.volume.server import VolumeServer

    with tempfile.TemporaryDirectory() as td:
        with pytest.raises(RuntimeError, match="Unable to initialize"):
            VolumeServer([td], ["127.0.0.1:1"], codec_name="tpu_xor")


def test_auto_codec_decided_in_process(monkeypatch):
    assert codec_mod.resolve_codec_name("tpu_xor") == "tpu_xor"
    assert codec_mod.resolve_codec_name("auto") == "cpu"  # cpu backend
    codec_mod._AUTO_CHOICE.clear()
    monkeypatch.setattr(
        device, "held_device",
        lambda: {"platform": "tpu", "kind": "TPU v5 lite", "count": 1})
    assert codec_mod.resolve_codec_name("auto") == "tpu"


# -- host-mode byte identity ------------------------------------------------


def test_host_parity_identity_mixed_widths():
    rs = ReedSolomon()
    svc = CodecService(mode="host")
    rng = np.random.default_rng(1)
    widths = (0, 1, 7, 100, 4096, 17 << 10, 300_000)  # spans the slab cutoff
    futs, expect = [], []
    for w in widths:
        block = _rand_block(rng, w)
        futs.append(svc.submit_parity(block))
        expect.append(rs.parity_of(block))
    for fut, exp in zip(futs, expect):
        assert np.array_equal(_as2d(fut.result(30)), exp)
    svc.close()


def test_host_apply_identity_decode_plan():
    rs = ReedSolomon()
    svc = CodecService(mode="host")
    rng = np.random.default_rng(2)
    plan = gf256.decode_plan_for(
        rs.matrix, 10, list(range(4, 14)), (0, 1, 2, 3))
    block = _rand_block(rng, 5000)
    got = _as2d(svc.submit_apply(plan, block).result(30))
    assert np.array_equal(got, np.stack(rs.apply_rows(plan, list(block))))
    svc.close()


def test_vectored_submit_preserves_order_and_identity():
    rs = ReedSolomon()
    svc = CodecService(mode="host")
    rng = np.random.default_rng(3)
    datas = [_rand_block(rng, w) for w in (64, 0, 2048, 9000, 3)]
    futs = svc.submit_parity_many(datas)
    for fut, data in zip(futs, datas):
        assert np.array_equal(_as2d(fut.result(30)), rs.parity_of(data))
    svc.close()


def test_out_buffers_filled_in_place():
    rs = ReedSolomon()
    svc = CodecService(mode="host")
    rng = np.random.default_rng(4)
    block = _rand_block(rng, 12345)
    out = np.zeros((4, 12345), dtype=np.uint8)
    svc.parity_into(block, out)
    assert np.array_equal(out, rs.parity_of(block))
    svc.close()


def test_list_of_rows_input():
    """mmap-view-style input: a list of 1-D rows, not a 2-D array."""
    rs = ReedSolomon()
    svc = CodecService(mode="host")
    rng = np.random.default_rng(5)
    rows = [rng.integers(0, 256, 777, dtype=np.uint8) for _ in range(10)]
    got = _as2d(svc.submit_parity(rows).result(30))
    assert np.array_equal(got, rs.parity_of(np.stack(rows)))
    svc.close()


def test_strided_row_views_are_decoded_correctly():
    """Non-contiguous row views must be copied before the raw-pointer
    kernel path, or it would silently read stride-1 garbage — for widths
    on BOTH sides of the slab-coalescing cutoff."""
    rs = ReedSolomon()
    svc = CodecService(mode="host", coalesce_kb=16)
    rng = np.random.default_rng(15)
    for w in (1024, 64 << 10):  # slab path and per-job native path
        rows = [rng.integers(0, 256, 2 * w, dtype=np.uint8)[::2]
                for _ in range(10)]
        got = _as2d(svc.submit_parity(rows).result(30))
        exp = rs.parity_of(np.stack([np.ascontiguousarray(r_)
                                     for r_ in rows]))
        assert np.array_equal(got, exp)
    svc.close()


# -- device-mode (mesh dry-run on the virtual 8-device CPU mesh) ------------


def test_device_mode_identity_parity_and_apply():
    rs = ReedSolomon()
    svc = CodecService(mode="device", codec_name="tpu_xor")
    rng = np.random.default_rng(6)
    futs, expect = [], []
    for w in (64, 200, 32768, 40000):  # spans two width buckets
        block = _rand_block(rng, w)
        futs.append(svc.submit_parity(block))
        expect.append(rs.parity_of(block))
    plan = gf256.decode_plan_for(
        rs.matrix, 10, list(range(4, 14)), (2,))
    block = _rand_block(rng, 513)
    afut = svc.submit_apply(plan, block)
    for fut, exp in zip(futs, expect):
        assert np.array_equal(_as2d(fut.result(120)), exp)
    assert np.array_equal(
        _as2d(afut.result(120)),
        np.stack(rs.apply_rows(plan, list(block))))
    svc.close()


# what reaches the jit, per shape of batch: (mesh devices, dp, job widths,
# how a job is handed in) -> (path of every job, width of every block)
_BLOCK_CASES = {
    "one_job_at_bucket_width": (1, 1, (16384,), "2d", "direct", 16384),
    "width_off_the_bucket": (1, 1, (16000,), "2d", "staged", 16384),
    "list_of_rows": (1, 1, (16384,), "rows", "staged", 16384),
    "strided_column_slice": (1, 1, (16384,), "strided", "staged", 16384),
    "two_coalesced_jobs": (1, 1, (16384, 16384), "2d", "direct", 16384),
    "two_jobs_off_the_bucket": (1, 1, (16000, 9000), "2d", "staged", 16384),
    # columns go over every device of any mesh: no padding volume
    "dp2_mesh": (2, 2, (16384,), "2d", "direct", 16384),
    "four_devices": (4, 1, (16384, 16384), "2d", "direct", 16384),
}
_HAND_IN = {
    "2d": lambda d: d,
    "rows": list,
    # the rebuild's tail: the first columns of a wider pooled buffer
    "strided": lambda d: np.concatenate([d, d], axis=1)[:, :d.shape[1]],
}


@pytest.mark.parametrize("case", sorted(_BLOCK_CASES))
def test_device_block_is_the_job_or_a_staged_copy(case, monkeypatch):
    """A job that is a whole block already reaches jobs_apply_sharded as
    its own array; every other job as a padded copy in a staging buffer.
    Same bytes out either way."""
    import jax

    from seaweedfs_tpu.parallel import mesh as mesh_mod
    from seaweedfs_tpu.stats.metrics import EC_SERVICE_STAGE

    n_dev, dp, widths, hand_in, want_path, want_width = _BLOCK_CASES[case]
    calls = []
    real = mesh_mod.jobs_apply_sharded

    def capture(mesh, matrix, blocks):
        calls.append([(b, b.copy()) for b in blocks])
        return real(mesh, matrix, blocks)

    monkeypatch.setattr(mesh_mod, "jobs_apply_sharded", capture)
    rs = ReedSolomon()
    rng = np.random.default_rng(28)
    datas = [_rand_block(rng, w) for w in widths]
    build = EC_SERVICE_STAGE.labels("build", "pipeline")
    counted = codec_service._INPUT_BYTES
    before = {p: c.value for p, c in counted.items()}
    build_before = (build.total, build.count)
    svc = CodecService(
        mode="device", codec_name="tpu_xor",
        mesh=mesh_mod.make_mesh(jax.devices()[:n_dev], dp=dp))
    futs = svc.submit_parity_many([_HAND_IN[hand_in](d) for d in datas])
    for fut, data in zip(futs, datas):
        assert np.array_equal(_as2d(fut.result(120)), rs.parity_of(data))
    svc.close()

    (blocks,) = calls  # one batch, whatever it held
    assert len(blocks) == len(datas)
    for (block, sent), d in zip(blocks, datas):
        assert sent.shape == (10, want_width) and sent.dtype == np.uint8
        if want_path == "direct":
            assert block is d  # the job's own array: nothing was copied
        else:
            assert not np.shares_memory(block, d)
            assert np.array_equal(sent[:, :d.shape[1]], d)
            assert not sent[:, d.shape[1]:].any()
    moved = {p: c.value - before[p] for p, c in counted.items()}
    other = "staged" if want_path == "direct" else "direct"
    assert moved == {want_path: 10 * sum(widths), other: 0}
    # the stage is observed on every batch, the direct ones too: a sum of
    # 0 would read as "no value" in the benchmark's per-GB reader
    assert build.count - build_before[1] == 1
    assert build.total - build_before[0] > 0


def test_auto_mode_with_device_codec_is_device_mode():
    # a device codec name means the jax mesh program, whatever backend
    # this process holds — there is no host-mode fallback to name
    svc = CodecService(mode="auto", codec_name="tpu")
    assert svc.mode == "device"
    assert not hasattr(svc, "fallback_reason")
    assert CodecService(mode="auto", codec_name="cpu").mode == "host"
    rng = np.random.default_rng(7)
    block = _rand_block(rng, 1024)
    assert np.array_equal(
        _as2d(svc.submit_parity(block).result(30)),
        ReedSolomon().parity_of(block))
    svc.close()


# -- scheduler behavior -----------------------------------------------------


def test_batches_coalesce_under_load():
    from seaweedfs_tpu.stats.metrics import EC_SERVICE_BATCH_JOBS

    child = EC_SERVICE_BATCH_JOBS.labels("pipeline")
    before_total, before_count = child.total, child.count
    svc = CodecService(mode="host", max_batch=16, coalesce_kb=16)
    rng = np.random.default_rng(8)
    big = _rand_block(rng, 32 << 20)  # occupies the worker for a while
    small = [_rand_block(rng, 2048) for _ in range(12)]
    first = svc.submit_parity(big)
    futs = svc.submit_parity_many(small)
    first.result(60)
    for f in futs:
        f.result(60)
    svc.close()
    jobs = child.total - before_total
    batches = child.count - before_count
    assert jobs == 13
    # the 12 small jobs queued while the big one computed must have
    # coalesced into (far) fewer than 12 batches
    assert batches < 13


def test_fairness_saturating_producer_does_not_starve():
    svc = CodecService(mode="host", max_batch=8)
    rng = np.random.default_rng(9)
    flood_block = _rand_block(rng, 64 << 10)
    stop = threading.Event()

    def flood():
        pend = []
        while not stop.is_set():
            pend.append(svc.submit_parity(flood_block))
            if len(pend) > 8:
                pend.pop(0).result()
        for f in pend:
            f.result()

    threads = [threading.Thread(target=flood) for _ in range(3)]
    for t in threads:
        t.start()
    try:
        time.sleep(0.1)  # let the flood saturate the queue
        plan = gf256.decode_plan_for(
            ReedSolomon().matrix, 10, list(range(4, 14)), (1,))
        block = _rand_block(rng, 2048)
        t0 = time.perf_counter()
        got = svc.submit_apply(plan, block).result(10)
        latency = time.perf_counter() - t0
        assert np.array_equal(
            _as2d(got),
            np.stack(ReedSolomon().apply_rows(plan, list(block))))
        # head-of-queue batching bounds the odd job's wait to a couple of
        # batch service times, not the flood's duration
        assert latency < 2.0
    finally:
        stop.set()
        for t in threads:
            t.join()
        svc.close()


def test_clean_shutdown_delivers_inflight_jobs():
    rs = ReedSolomon()
    svc = CodecService(mode="host")
    rng = np.random.default_rng(10)
    datas = [_rand_block(rng, 100_000) for _ in range(24)]
    futs = svc.submit_parity_many(datas)
    svc.close()  # drain: every already-accepted job still completes
    for fut, data in zip(futs, datas):
        assert np.array_equal(_as2d(fut.result(30)), rs.parity_of(data))
    with pytest.raises(RuntimeError):
        svc.submit_parity(datas[0])


def test_compute_failure_fails_jobs_not_hangs(monkeypatch):
    svc = CodecService(mode="host")

    def boom(batch, tags):
        raise RuntimeError("injected compute failure")

    monkeypatch.setattr(svc, "_compute_host", boom)
    fut = svc.submit_parity(_rand_block(np.random.default_rng(11), 1024))
    with pytest.raises(RuntimeError, match="injected"):
        fut.result(30)
    svc.close()


def test_validation_errors_raise_in_caller():
    svc = CodecService(mode="host")
    rng = np.random.default_rng(12)
    with pytest.raises(ValueError):
        svc.submit_parity(rng.integers(0, 256, (9, 64), dtype=np.uint8))
    with pytest.raises(ValueError):
        svc.submit_parity(
            [rng.integers(0, 256, w, dtype=np.uint8)
             for w in (64,) * 9 + (65,)])
    with pytest.raises(ValueError):
        svc.submit_parity(_rand_block(rng, 64),
                          out=np.zeros((4, 63), np.uint8))
    svc.close()


# -- singletons + env gating ------------------------------------------------


def test_get_service_disabled_by_env(monkeypatch):
    monkeypatch.setenv("SEAWEEDFS_TPU_EC_SERVICE", "0")
    assert codec_service.get_service("cpu") is None
    assert codec_service.service_for_codec("tpu") is None


def test_get_service_shared_and_recreated_after_shutdown(monkeypatch):
    monkeypatch.delenv("SEAWEEDFS_TPU_EC_SERVICE", raising=False)
    a = codec_service.get_service("cpu")
    assert a is codec_service.get_service("cpu")
    codec_service.shutdown_all()
    b = codec_service.get_service("cpu")
    assert b is not a and not b.closed


def test_service_for_codec_routes_by_held_device(monkeypatch):
    # cpu backend: bulk pipelines keep their direct (tested) dispatch
    monkeypatch.delenv("SEAWEEDFS_TPU_EC_SERVICE", raising=False)
    assert codec_service.service_for_codec("tpu") is None
    assert codec_service.service_for_codec("cpu") is None
    assert codec_service.service_for_codec("auto") is None
    # an accelerator held by this process: the device-mode service
    monkeypatch.setattr(
        device, "held_device",
        lambda: {"platform": "tpu", "kind": "TPU v5 lite", "count": 1})
    svc = codec_service.service_for_codec("tpu")
    assert svc is not None and svc.mode == "device"
    assert codec_service.service_for_codec("cpu") is None


# -- pipeline integration ---------------------------------------------------


def _write_dat(path, nbytes, seed=13):
    rng = np.random.default_rng(seed)
    with open(path, "wb") as f:
        f.write(rng.integers(0, 256, nbytes, dtype=np.uint8).tobytes())


def test_generate_and_rebuild_via_service_byte_identical(tmp_path):
    from seaweedfs_tpu.storage.ec.constants import to_ext
    from seaweedfs_tpu.storage.ec.encoder import (
        generate_ec_files,
        rebuild_ec_files,
    )

    base = str(tmp_path / "v")
    large, small = 1 << 20, 64 << 10
    _write_dat(base + ".dat", 11 * (1 << 20) + 4321)
    generate_ec_files(base, large_block_size=large, small_block_size=small,
                      codec_name="cpu", slice_size=256 << 10)
    ref = {i: open(base + to_ext(i), "rb").read() for i in range(14)}

    svc = CodecService(mode="host")
    # mixed slice sizes through the service: batched segments coalesce
    for slice_size in (64 << 10, 192 << 10):
        generate_ec_files(base, large_block_size=large,
                          small_block_size=small, codec_name="cpu",
                          slice_size=slice_size, service=svc)
        for i in range(14):
            assert open(base + to_ext(i), "rb").read() == ref[i], \
                f"shard {i} differs at slice_size={slice_size}"
    # rebuild through the service: worst-case data loss + one parity
    for sid in (0, 1, 2, 13):
        os.remove(base + to_ext(sid))
    rebuilt = rebuild_ec_files(base, codec_name="cpu",
                               slice_size=128 << 10, service=svc)
    assert sorted(rebuilt) == [0, 1, 2, 13]
    for i in range(14):
        assert open(base + to_ext(i), "rb").read() == ref[i]
    svc.close()


def test_generate_device_codec_via_device_service(tmp_path):
    """The pipelined encode path with an explicit device-mode service —
    the mesh dry-run for the serving path's batched dispatch."""
    from seaweedfs_tpu.storage.ec.constants import to_ext
    from seaweedfs_tpu.storage.ec.encoder import generate_ec_files

    base = str(tmp_path / "v")
    large, small = 1 << 20, 64 << 10
    _write_dat(base + ".dat", 3 * (1 << 20) + 999)
    generate_ec_files(base, large_block_size=large, small_block_size=small,
                      codec_name="cpu", slice_size=256 << 10)
    ref = {i: open(base + to_ext(i), "rb").read() for i in range(14)}
    svc = CodecService(mode="device", codec_name="tpu_xor")
    generate_ec_files(base, large_block_size=large, small_block_size=small,
                      codec_name="tpu_xor", slice_size=256 << 10,
                      service=svc)
    for i in range(14):
        assert open(base + to_ext(i), "rb").read() == ref[i]
    svc.close()


@pytest.mark.parametrize("mode,codec", [("host", "cpu"),
                                        ("device", "tpu_xor")])
def test_degraded_read_via_service(tmp_path, monkeypatch, mode, codec):
    """A lost interval goes through the service the volume is handed (the
    routing a process that holds an accelerator takes by itself), as one
    `apply` job of class `read`; without one, a host codec decodes
    inline."""
    from seaweedfs_tpu.stats.metrics import EC_SERVICE_JOBS
    from seaweedfs_tpu.storage.ec.constants import to_ext
    from seaweedfs_tpu.storage.ec.encoder import (
        generate_ec_files,
        write_sorted_file_from_idx,
    )
    from seaweedfs_tpu.storage.ec.volume import EcVolume
    from seaweedfs_tpu.storage.needle import FLAG_HAS_NAME, Needle
    from seaweedfs_tpu.storage.super_block import SuperBlock
    from seaweedfs_tpu.storage.volume import Volume

    rng = np.random.default_rng(14)
    vol = Volume(str(tmp_path), "", 1, super_block=SuperBlock())
    payloads = {}
    for i in range(1, 21):
        n = Needle(cookie=int(rng.integers(0, 2**32)), id=i,
                   data=rng.integers(0, 256, 4096, dtype=np.uint8).tobytes())
        n.set(FLAG_HAS_NAME)
        n.name = f"svc-{i}.bin".encode()
        payloads[i] = n.data
        vol.append_needle(n)
    base = vol.file_name()
    vol.close()
    generate_ec_files(base, codec_name="cpu")
    write_sorted_file_from_idx(base)
    for sid in (0, 1, 2, 3):
        os.remove(base + to_ext(sid))

    monkeypatch.setenv("SEAWEEDFS_TPU_EC_INTERVAL_CACHE_MB", "0")
    ev = EcVolume(base, volume_id=1)
    assert ev._decode_service() is None      # a host codec: inline
    jobs = EC_SERVICE_JOBS.labels("apply", "ok")
    svc = CodecService(mode=mode, codec_name=codec)
    try:
        assert ev.read_needle(1).data == payloads[1]
        before = jobs.value
        ev.decode_service = svc
        for i in (1, 5, 9, 20):
            needle = ev.read_needle(i)
            assert needle.data == payloads[i]
        assert jobs.value - before >= 4
    finally:
        ev.close()
        svc.close()


# -- compile cache placement -------------------------------------------------


def test_compile_cache_dir_is_env_or_fixed_checkout_path(monkeypatch):
    """JAX_COMPILATION_CACHE_DIR set -> the code sets NO directory;
    unset -> one fixed, git-ignored path inside the checkout."""
    import jax

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    seen = []
    monkeypatch.setattr(jax.config, "update",
                        lambda k, v: seen.append((k, v)))
    monkeypatch.setattr(device, "_cache_dir", None)
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/somewhere/else")
    assert device.enable_compile_cache() == "/somewhere/else"
    assert seen == []
    monkeypatch.setattr(device, "_cache_dir", None)
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    assert device.enable_compile_cache() == os.path.join(
        repo, ".jax_compile_cache")
    assert ("jax_compilation_cache_dir",
            os.path.join(repo, ".jax_compile_cache")) in seen
    assert device.enable_compile_cache() == device._cache_dir  # idempotent
    with open(os.path.join(repo, ".gitignore")) as f:
        assert ".jax_compile_cache/" in f.read().split()
