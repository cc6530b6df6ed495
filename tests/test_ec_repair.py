"""EC repair data plane: the pipelined rebuild (byte identity across
loss patterns, remote-source hook, clean-error contract), the shared
decode-plan cache, and the degraded-read single-flight + interval LRU.

Companion to test_ec_pipeline.py (encode conformance) — this file covers
the REPAIR half of the north star (BASELINE configs 3 and 5).
"""

import os
import threading
import time
from concurrent.futures import ThreadPoolExecutor

import numpy as np
import pytest

from seaweedfs_tpu.ops import gf256
from seaweedfs_tpu.ops.codec import get_codec
from seaweedfs_tpu.stats.metrics import (
    EC_DECODE_PLAN,
    EC_SINGLEFLIGHT,
)
from seaweedfs_tpu.storage.ec import constants as ecc
from seaweedfs_tpu.storage.ec.encoder import (
    generate_ec_files,
    rebuild_ec_files,
    write_sorted_file_from_idx,
)
from seaweedfs_tpu.storage.ec.volume import EcVolume
from seaweedfs_tpu.storage.super_block import VERSION3

from helpers import make_volume

LARGE = 10000  # scaled-down block sizes, as in test_ec_pipeline.py
SMALL = 100


def _encoded(tmp_path, n_needles):
    vol = make_volume(str(tmp_path), n_needles=n_needles, seed=21,
                      max_size=3000)
    base = vol.file_name()
    vol.close()
    generate_ec_files(base, large_block_size=LARGE, small_block_size=SMALL,
                      codec_name="cpu", slice_size=1 << 20)
    write_sorted_file_from_idx(base)
    return base


@pytest.fixture()
def encoded_base(tmp_path):
    return _encoded(tmp_path, 60)


@pytest.fixture()
def encoded_base_wide(tmp_path):
    """Shards of several 4 KiB slices (a device's least lane tile) and a
    tail."""
    return _encoded(tmp_path, 200)


def _shard_bytes(base):
    return {i: open(base + ecc.to_ext(i), "rb").read()
            for i in range(ecc.TOTAL_SHARDS)}


# -- rebuild byte identity across loss patterns ---------------------------

# 1-4 lost shards: data-only, parity-only, and mixed patterns
LOSS_PATTERNS = [
    (0,),
    (13,),
    (0, 13),
    (0, 1, 2, 3),          # worst case: 4 data shards
    (10, 11, 12, 13),      # all parity
    (2, 7, 11, 13),        # mixed
]


@pytest.mark.parametrize("lost", LOSS_PATTERNS)
def test_rebuild_byte_identity_cpu(encoded_base, lost):
    originals = _shard_bytes(encoded_base)
    for sid in lost:
        os.remove(encoded_base + ecc.to_ext(sid))
    rebuilt = rebuild_ec_files(encoded_base, codec_name="cpu",
                               slice_size=1000)
    assert sorted(rebuilt) == sorted(lost)
    for sid in lost:
        got = open(encoded_base + ecc.to_ext(sid), "rb").read()
        assert got == originals[sid], f"shard {sid} not byte-identical"


@pytest.mark.parametrize("lost", [(0, 1, 2, 3), (3, 9, 12, 13)])
def test_rebuild_byte_identity_device_codec(encoded_base, lost):
    """The async-dispatch device path (apply_rows_device, one slice in
    flight) must produce the same bytes as the host codec."""
    originals = _shard_bytes(encoded_base)
    for sid in lost:
        os.remove(encoded_base + ecc.to_ext(sid))
    rebuilt = rebuild_ec_files(encoded_base, codec_name="tpu",
                               slice_size=4096)
    assert sorted(rebuilt) == sorted(lost)
    for sid in lost:
        got = open(encoded_base + ecc.to_ext(sid), "rb").read()
        assert got == originals[sid], f"shard {sid} differs on device codec"


def test_rebuild_via_device_service_direct_slices_and_buffer_ownership(
        encoded_base_wide, monkeypatch):
    """Through a device-mode service on a one-device mesh: a full slice
    is the pooled buffer itself and reaches the device uncopied, the
    tail is staged, the shards come out byte-identical — and no pooled
    buffer is written to while a job that reads it is unresolved (the
    submit_* contract, which the uncopied path depends on)."""
    import jax

    from seaweedfs_tpu.ops import codec_service
    from seaweedfs_tpu.parallel.mesh import make_mesh
    from seaweedfs_tpu.storage.ec import encoder

    lost, slice_size = (0, 1, 2, 3), 4096
    originals = _shard_bytes(encoded_base_wide)
    shard_size = len(originals[0])
    n_full, tail = divmod(shard_size, slice_size)
    assert n_full >= 4 and tail, "fixture must give full slices and a tail"
    for sid in lost:
        os.remove(encoded_base_wide + ecc.to_ext(sid))

    # one job a batch, as the byte cap makes it at the served 160 MiB
    # slices (two queued toy slices would otherwise coalesce, and stage)
    svc = codec_service.CodecService(
        mode="device", codec_name="tpu_xor", max_batch=1,
        mesh=make_mesh(jax.devices()[:1]))
    owned = []  # (input rows, future) of every job the rebuild submitted
    real_submit = svc.submit_apply

    def submit(rows, inputs, out=None, stream=None):
        fut = real_submit(rows, inputs, out, stream)
        owned.append((inputs if isinstance(inputs, list) else [inputs], fut))
        return fut

    written_early = []
    real_pread = encoder._pread_into

    def pread(fd, dest, offset):
        written_early.extend(
            offset for rows, fut in list(owned) if not fut.done()
            and any(np.may_share_memory(dest, r) for r in rows))
        real_pread(fd, dest, offset)

    monkeypatch.setattr(svc, "submit_apply", submit)
    monkeypatch.setattr(encoder, "_pread_into", pread)
    counted = codec_service._INPUT_BYTES
    before = {p: c.value for p, c in counted.items()}
    try:
        rebuilt = rebuild_ec_files(encoded_base_wide, codec_name="tpu_xor",
                                   slice_size=slice_size, service=svc)
    finally:
        svc.close()
    assert sorted(rebuilt) == sorted(lost)
    for sid in lost:
        got = open(encoded_base_wide + ecc.to_ext(sid), "rb").read()
        assert got == originals[sid], f"shard {sid} not byte-identical"
    assert len(owned) == n_full + 1 and all(f.done() for _, f in owned)
    assert written_early == []
    moved = {p: c.value - before[p] for p, c in counted.items()}
    assert moved == {"direct": n_full * ecc.DATA_SHARDS * slice_size,
                     "staged": ecc.DATA_SHARDS * tail}


def test_rebuild_progress_monotonic(encoded_base):
    for sid in (0, 11):
        os.remove(encoded_base + ecc.to_ext(sid))
    seen = []
    rebuild_ec_files(encoded_base, codec_name="cpu", slice_size=1000,
                     progress=seen.append)
    assert seen == sorted(seen) and seen, "progress must be monotonic"
    assert seen[-1] == os.path.getsize(encoded_base + ecc.to_ext(0))


# -- remote-source hook ---------------------------------------------------

def test_rebuild_remote_source_hook(encoded_base):
    """A node with fewer than DATA_SHARDS local shards streams the
    missing source intervals from peers instead of failing — and only
    rebuilds the GLOBALLY missing shards (peer-held ones need a copy
    rpc, not a decode)."""
    originals = _shard_bytes(encoded_base)
    gone = [0, 1, 2, 3, 4, 5]  # 8 local left — not enough to decode
    peer_holds = {4, 5}        # the rest are lost cluster-wide
    for sid in gone:
        os.remove(encoded_base + ecc.to_ext(sid))

    # without the hook: clean refusal, nothing rebuilt
    with pytest.raises(ValueError):
        rebuild_ec_files(encoded_base, codec_name="cpu", slice_size=1000)
    for sid in gone:
        assert not os.path.exists(encoded_base + ecc.to_ext(sid))

    calls = []

    def fetch(sid, off, length):
        if sid not in peer_holds:
            return None
        calls.append(sid)
        return originals[sid][off:off + length]

    rebuilt = rebuild_ec_files(encoded_base, codec_name="cpu",
                               slice_size=1000, remote_fetch=fetch)
    assert sorted(rebuilt) == [0, 1, 2, 3]
    assert calls, "remote sources must have been streamed"
    for sid in (0, 1, 2, 3):
        got = open(encoded_base + ecc.to_ext(sid), "rb").read()
        assert got == originals[sid], f"shard {sid} differs via remote hook"
    for sid in peer_holds:  # healthy on a peer: not regenerated locally
        assert not os.path.exists(encoded_base + ecc.to_ext(sid))


def test_rebuild_remote_source_dies_cleanly(encoded_base):
    """A peer dying mid-rebuild surfaces a clean IOError and leaves NO
    partial .ecNN outputs for a later mount to trust; a retry against a
    healthy peer then succeeds byte-identically."""
    originals = _shard_bytes(encoded_base)
    gone = [0, 1, 2, 3, 4]  # 9 local left; the peer holds only shard 4
    for sid in gone:
        os.remove(encoded_base + ecc.to_ext(sid))
    fail_after = {"n": 4}  # probe + a few slices, then the peer dies

    def dying_fetch(sid, off, length):
        if sid != 4:
            return None
        if fail_after["n"] <= 0:
            return None  # the peer went away mid-stream
        fail_after["n"] -= 1
        return originals[sid][off:off + length]

    with pytest.raises(IOError):
        rebuild_ec_files(encoded_base, codec_name="cpu", slice_size=1000,
                         remote_fetch=dying_fetch)
    for sid in gone:
        assert not os.path.exists(encoded_base + ecc.to_ext(sid)), \
            f"partial shard {sid} must be removed on error"

    def good_fetch(sid, off, length):
        return originals[sid][off:off + length] if sid == 4 else None

    rebuilt = rebuild_ec_files(encoded_base, codec_name="cpu",
                               slice_size=1000, remote_fetch=good_fetch)
    assert sorted(rebuilt) == [0, 1, 2, 3]
    for sid in (0, 1, 2, 3):
        assert open(encoded_base + ecc.to_ext(sid), "rb").read() \
            == originals[sid]


def test_rebuild_writer_error_does_not_deadlock(encoded_base):
    """A writer-stage failure (here: the progress callback raising, the
    same path a full disk takes) must surface promptly — the prefetch
    thread's buffer-pool wait is stop-aware, so the error path cannot
    strand the join — and must remove partial outputs."""
    for sid in (0, 1):
        os.remove(encoded_base + ecc.to_ext(sid))

    def bad_progress(done):
        raise RuntimeError("writer boom")

    result = {}

    def run():
        try:
            rebuild_ec_files(encoded_base, codec_name="cpu", slice_size=500,
                             progress=bad_progress)
            result["r"] = "no error"
        except Exception as e:  # noqa: BLE001
            result["r"] = e

    t = threading.Thread(target=run, daemon=True)
    t.start()
    t.join(20)
    assert not t.is_alive(), "rebuild deadlocked on writer error"
    assert isinstance(result["r"], RuntimeError)
    for sid in (0, 1):
        assert not os.path.exists(encoded_base + ecc.to_ext(sid))


# -- decode-plan cache ----------------------------------------------------

def test_decode_plan_matches_direct_computation():
    m = gf256.rs_matrix(10, 14)
    present = [2, 3, 4, 5, 6, 7, 8, 9, 10, 12]
    wanted = (0, 1, 11, 13)
    plan = gf256.decode_plan_for(m, 10, present, wanted)
    dec = gf256.mat_inv(m[np.asarray(present[:10], dtype=np.int64)])
    for i, w in enumerate(wanted):
        if w < 10:
            assert np.array_equal(plan[i], dec[w])
        else:
            assert np.array_equal(
                plan[i], gf256.mat_mul(m[w:w + 1, :10], dec)[0])


def test_decode_plan_cache_hits():
    m = gf256.rs_matrix(10, 14)
    present = [0, 1, 2, 3, 4, 5, 6, 7, 8, 13]  # a set other tests don't use
    wanted = (9, 10)
    hit = EC_DECODE_PLAN.labels("hit")
    first = gf256.decode_plan_for(m, 10, present, wanted)
    before = hit.value
    again = gf256.decode_plan_for(m, 10, present, wanted)
    assert again is first, "second lookup must come from the cache"
    assert hit.value == before + 1


def test_decode_plan_cached_vs_uncached_decode(encoded_base):
    """Needle bytes decoded through the cached plan equal a from-scratch
    numpy decode with no cache involved."""
    ev = EcVolume(encoded_base, volume_id=1, version=VERSION3,
                  large_block_size=LARGE, small_block_size=SMALL)
    want = ev.read_needle(7)
    for sid in (0, 1, 2, 3):
        ev.delete_shard(sid)
    got = ev.read_needle(7)  # degraded: through decode_plan_for
    assert got.data == want.data
    ev.close()

    # from-scratch check of one reconstructed interval, bypassing every
    # cache: invert with a fresh Gauss-Jordan per call
    shard_size = os.path.getsize(encoded_base + ecc.to_ext(4))
    m = gf256.rs_matrix(10, 14)
    present = list(range(4, 14))
    dec = gf256.mat_inv(m[np.asarray(present, dtype=np.int64)])
    srcs = [np.frombuffer(
        open(encoded_base + ecc.to_ext(i), "rb").read(), dtype=np.uint8)
        for i in present]
    t = gf256.mul_table()
    acc = np.zeros(shard_size, dtype=np.uint8)
    for j, c in enumerate(dec[0]):
        if c:
            acc ^= srcs[j] if c == 1 else t[c][srcs[j]]
    cached = get_codec("cpu").reconstruct_one(
        [None, None, None, None] + srcs, 0)
    assert np.array_equal(np.asarray(cached), acc)


# -- degraded-read single-flight + interval cache -------------------------

def _degraded_volume(base):
    """EcVolume with the first 4 data shards gone."""
    for sid in range(4):
        os.remove(base + ecc.to_ext(sid))
    return EcVolume(base, volume_id=1, version=VERSION3,
                    large_block_size=LARGE, small_block_size=SMALL)


def _count_gathers(ev, delay=0.0):
    """Wrap _gather_and_decode with an invocation counter."""
    counter = {"n": 0}
    inner = ev._gather_and_decode

    def counting(shard_id, offset, length):
        counter["n"] += 1
        if delay:
            time.sleep(delay)
        return inner(shard_id, offset, length)

    ev._gather_and_decode = counting
    return counter


def test_single_flight_coalesces_concurrent_readers(encoded_base):
    ev = _degraded_volume(encoded_base)
    counter = _count_gathers(ev, delay=0.05)
    coalesced = EC_SINGLEFLIGHT.labels("coalesced")
    before = coalesced.value
    length = 256

    results = []
    with ThreadPoolExecutor(max_workers=16) as pool:
        futs = [pool.submit(ev._reconstruct_interval, 0, 0, length)
                for _ in range(16)]
        results = [f.result() for f in futs]
    ev.close()
    assert all(r == results[0] for r in results)
    assert len(results[0]) == length
    # 16 concurrent readers of the same lost interval: one gather+decode
    # (a tiny window exists where a follower arrives after the leader
    # popped the key — allow 2, never 16)
    assert counter["n"] <= 2, f"{counter['n']} gathers for one interval"
    assert coalesced.value >= before + 14


def test_interval_cache_serves_repeat_reads(encoded_base):
    ev = _degraded_volume(encoded_base)
    counter = _count_gathers(ev)
    first = ev._reconstruct_interval(1, 0, 512)
    again = ev._reconstruct_interval(1, 0, 512)
    assert first == again
    assert counter["n"] == 1, "second read must come from the interval LRU"
    ev.close()


def test_interval_cache_invalidated_on_unmount_and_delete(encoded_base):
    # lose only 3 shards so 11 stay mounted: the test can unmount one
    # more and the interval is still decodable from the remaining 10
    for sid in range(3):
        os.remove(encoded_base + ecc.to_ext(sid))
    ev = EcVolume(encoded_base, volume_id=1, version=VERSION3,
                  large_block_size=LARGE, small_block_size=SMALL)
    counter = _count_gathers(ev)
    ev._reconstruct_interval(2, 0, 512)
    assert counter["n"] == 1

    # shard unmount: the layout changed wholesale — re-gather
    ev.delete_shard(13)
    ev._reconstruct_interval(2, 0, 512)
    assert counter["n"] == 2
    ev.add_shard(13)
    ev._reconstruct_interval(2, 0, 512)
    assert counter["n"] == 3

    # needle delete bumps delete_seq: cached intervals become unservable
    nid = 9
    ev.delete_needle(nid)
    ev._reconstruct_interval(2, 0, 512)
    assert counter["n"] == 4
    ev.close()


def test_interval_cache_compare_before_publish(encoded_base):
    """A delete racing the gather must prevent the stale publish: the
    token captured before the reads no longer matches at put time."""
    ev = _degraded_volume(encoded_base)
    inner = ev._gather_and_decode

    def racing(shard_id, offset, length):
        data, token = inner(shard_id, offset, length)
        ev.delete_needle(11)  # bump delete_seq after the capture
        return data, token

    ev._gather_and_decode = racing
    ev._reconstruct_interval(3, 0, 256)
    assert len(ev._interval_cache) == 0, \
        "stale interval must not be published"
    ev.close()


def test_degraded_reads_spawn_no_new_threads(encoded_base):
    """The per-call ThreadPoolExecutor is gone: after warmup, a storm of
    degraded reads (incl. remote fetches through the shared bounded
    executor) must not grow the process thread count."""
    originals = {i: open(encoded_base + ecc.to_ext(i), "rb").read()
                 for i in range(ecc.TOTAL_SHARDS)}
    for sid in range(6):  # force the remote fan-out path (8 local < 10)
        os.remove(encoded_base + ecc.to_ext(sid))
    ev = EcVolume(encoded_base, volume_id=1, version=VERSION3,
                  large_block_size=LARGE, small_block_size=SMALL)
    ev.remote_fetch = lambda sid, off, ln: originals[sid][off:off + ln]

    from seaweedfs_tpu.storage.ec import volume as ec_volume

    def census():
        """-> (shared-pool workers, every other thread).  A stdlib pool
        grows lazily up to max_workers, and how far four warm-up calls
        get depends on core count — so the pool is held to its configured
        bound and only threads OUTSIDE it are held to the baseline."""
        pool = sum(t.name.startswith("ec-fetch")
                   for t in threading.enumerate())
        return pool, threading.active_count() - pool

    for i in range(4):  # warm the shared pool + caches
        ev._gather_and_decode(0, i * 7, 64)
    _, baseline = census()
    for i in range(40):
        ev._gather_and_decode(0, i * 11, 64)  # distinct intervals: no LRU
    pool_threads, others = census()
    assert pool_threads <= ec_volume._fetch_pool()._max_workers
    assert others <= baseline, \
        "degraded reads must not spawn threads per call"
    ev.close()


def test_ec_shard_deltas_reach_the_master_without_waiting_for_a_tick(
        encoded_base, tmp_path):
    """An un-mount and a mount wake the volume server's heartbeat: the
    master's view follows within a beat, although the generator's sleep
    (made 60 s here, where a server's is up to 1 s) never runs out."""
    import shutil

    from helpers import free_port, start_master_cluster
    from seaweedfs_tpu.volume.server import VolumeServer

    class NeverTicks(threading.Event):
        def wait(self, timeout=None):
            return super().wait(60.0)

    def holders(vid):
        return {sid for sid, nodes in
                master.topo.lookup_ec_shards(vid).items() if nodes}

    def wait_for(want):
        deadline = time.monotonic() + 20
        while holders(7) != want and time.monotonic() < deadline:
            time.sleep(0.01)
        assert holders(7) == want

    master, masters = start_master_cluster(str(tmp_path))
    vdir = tmp_path / "vs"
    vdir.mkdir()
    vs = VolumeServer(directories=[str(vdir)],
                      master_addresses=[f"127.0.0.1:{master.grpc_port}"],
                      ip="127.0.0.1", port=free_port())
    vs._wake = NeverTicks()
    vs.store.on_ec_delta = vs._wake.set
    vs.start()
    try:
        tbase = vs.store.locations[0].base_name(7, "")
        shutil.copy(encoded_base + ".ecx", tbase + ".ecx")
        for sid in range(ecc.TOTAL_SHARDS):
            shutil.copy(encoded_base + ecc.to_ext(sid), tbase + ecc.to_ext(sid))
        every = set(range(ecc.TOTAL_SHARDS))
        vs.store.mount_ec_shards(7, "", sorted(every))
        wait_for(every)
        vs.store.unmount_ec_shards(7, [0, 1, 2, 3])
        wait_for(every - {0, 1, 2, 3})
        vs.store.mount_ec_shards(7, "", [0, 1, 2, 3])
        wait_for(every)
        # a disk fault still forces the full beat, through the same sleep
        vs.store.on_disk_event()
        assert vs._beat_now.is_set() and vs._wake.is_set()
    finally:
        vs.stop()
        for m in masters:
            m.stop()
