"""Degraded reads of an `EcVolume` against the plain reference: a lost
interval decoded through a device-mode codec service handed to the volume
(the route a `-ec.codec tpu` server takes by itself; on a CPU backend
`service_for_codec` gives none) and by the codec's direct dispatch (the
route of a device codec on a CPU backend), byte for byte."""

import os
import sys
import threading
import time

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from benchmark import reference_gf as ref  # noqa: E402
from seaweedfs_tpu.ops import device  # noqa: E402
from seaweedfs_tpu.ops.codec_service import CodecService  # noqa: E402
from seaweedfs_tpu.stats.metrics import (  # noqa: E402
    EC_DEGRADED_INTERVALS,
    EC_SERVICE_BATCH_JOBS,
)
from seaweedfs_tpu.storage.ec.constants import to_ext  # noqa: E402
from seaweedfs_tpu.storage.ec.encoder import (  # noqa: E402
    generate_ec_files,
    write_sorted_file_from_idx,
)
from seaweedfs_tpu.storage.ec.volume import EcVolume  # noqa: E402
from seaweedfs_tpu.storage.needle import CorruptNeedleError, Needle  # noqa: E402
from seaweedfs_tpu.storage.super_block import SuperBlock  # noqa: E402
from seaweedfs_tpu.storage.volume import Volume  # noqa: E402

BLOCK = 1 << 20
# (offset in the shard file, length): 1 B, 1 KiB, 4 KiB + 1, a whole 1 MiB
# block, and both sides of a block boundary
INTERVALS = [(5, 1), (1000, 1024), (70000, 4097), (0, BLOCK),
             (BLOCK - 4096, 4096), (BLOCK, 1024)]
LOSSES = [(0,), (1,), (2,), (3,), (0, 1, 2, 3)]


@pytest.fixture(scope="module")
def encoded(tmp_path_factory):
    """-> (base, the 14 shard files' bytes) of 25 MiB of seeded bytes:
    three stripe rows of 1 MiB blocks, the last one padded."""
    base = str(tmp_path_factory.mktemp("ec") / "7")
    np.random.default_rng(37).integers(
        0, 256, 25 << 20, dtype=np.uint8).tofile(base + ".dat")
    generate_ec_files(base, codec_name="cpu")
    shards = [open(base + to_ext(i), "rb").read() for i in range(14)]
    return base, shards


def degraded_volume(tmp_path, base, lost, codec="tpu_xor") -> EcVolume:
    """An EcVolume over hard links of `base`'s shard files without `lost`."""
    mine = str(tmp_path / "7")
    for i in range(14):
        if i not in lost:
            os.link(base + to_ext(i), mine + to_ext(i))
    open(mine + ".ecx", "wb").close()
    return EcVolume(mine, volume_id=7, codec_name=codec)


@pytest.fixture
def service():
    svc = CodecService(mode="device", codec_name="tpu_xor")
    yield svc
    svc.close()


@pytest.mark.parametrize("route", ["service", "direct"])
@pytest.mark.parametrize("lost", LOSSES, ids=lambda t: "lost" + "".join(
    map(str, t)))
def test_lost_intervals_equal_the_original_and_the_reference(
        tmp_path, encoded, service, lost, route):
    base, shards = encoded
    ev = degraded_volume(tmp_path, base, lost)
    if route == "service":
        ev.decode_service = service
    else:
        assert ev._decode_service() is None
    decoded = EC_DEGRADED_INTERVALS.labels("decoded")
    read_batches = EC_SERVICE_BATCH_JOBS.labels("read")
    before, batches = decoded.value, read_batches.count
    try:
        for n, (off, length) in enumerate(INTERVALS):
            # every width for the first lost shard, two for the others
            for sid in lost if n in (1, 3) else lost[:1]:
                got = ev.read_shard_interval(sid, off, length)
                assert got == shards[sid][off:off + length], (sid, off)
                survivors = [i for i in range(14) if i not in lost][:10]
                plain = ref.reconstruct(
                    {i: np.frombuffer(shards[i][off:off + length], np.uint8)
                     for i in survivors}, length)
                assert got == plain[sid].tobytes(), (sid, off)
        assert decoded.value - before == len(INTERVALS) + 2 * (len(lost) - 1)
        # through the service every decode is a job of class `read`
        assert (read_batches.count > batches) is (route == "service")
    finally:
        ev.close()


def test_sixteen_readers_and_a_parity_stream_share_one_service(
        tmp_path, encoded, service, monkeypatch):
    """Every result exact, nobody starves, and once the volume has warmed
    its decode programs nothing compiles, whatever lengths meet in a
    batch."""
    monkeypatch.setenv("SEAWEEDFS_TPU_EC_INTERVAL_CACHE_MB", "0")
    base, shards = encoded
    ev = degraded_volume(tmp_path, base, (0, 1, 2, 3))
    ev.decode_service = service
    rng = np.random.default_rng(41)
    slices = [rng.integers(0, 256, (10, 65536), dtype=np.uint8)
              for _ in range(4)]
    parity = [ref.parity_of(s) for s in slices]
    device.enable_compile_cache()
    ev.warm_decode()
    assert np.array_equal(np.asarray(
        service.submit_parity(slices[0], stream="enc").result(120)),
        parity[0])
    warmed = dict(device.compile_cache_stats())
    stop, errors, done = threading.Event(), [], [0] * 17

    def reader(k: int) -> None:
        r = np.random.default_rng([41, k])
        try:
            # at least twelve reads each, and on for as long as the parity
            # stream runs: the queue is never without a read
            while done[k] < 12 or not stop.is_set():
                sid = int(r.integers(4))
                length = int(np.exp(r.uniform(0, np.log(BLOCK))))
                off = int(r.integers(0, 3 * BLOCK - length))
                off -= max(0, off % BLOCK + length - BLOCK)  # in one block
                got = ev.read_shard_interval(sid, off, length)
                assert got == shards[sid][off:off + length]
                done[k] += 1
        except Exception as e:  # noqa: BLE001 — reported below
            errors.append(e)
            stop.set()

    def encoder() -> None:
        try:
            with service.stream("enc"):
                for n in range(8):
                    out = service.submit_parity(
                        slices[n % 4], stream="enc").result(120)
                    assert np.array_equal(np.asarray(out), parity[n % 4])
                    done[16] += 1
        except Exception as e:  # noqa: BLE001
            errors.append(e)
        finally:
            stop.set()

    enc = threading.Thread(target=encoder)
    readers = [threading.Thread(target=reader, args=(k,)) for k in range(16)]
    for th in readers:
        th.start()
    time.sleep(0.05)
    enc.start()
    enc.join(300)
    for th in readers:
        th.join(300)
    ev.close()
    assert not errors, errors[:3]
    # the stream's slices got their turns among the reads, and every
    # reader its reads beside the stream
    assert done[16] == 8 and min(done[:16]) >= 12
    after = device.compile_cache_stats()
    assert (after["misses"], after["compile_seconds"]) == (
        warmed["misses"], warmed["compile_seconds"])


@pytest.mark.parametrize("route", ["service", "direct"])
def test_a_corrupted_survivor_byte_is_a_crc_failure_not_an_answer(
        tmp_path, service, route):
    rng = np.random.default_rng(43)
    vol = Volume(str(tmp_path), "", 3, super_block=SuperBlock())
    payloads = {}
    for i in range(1, 13):
        payloads[i] = rng.integers(0, 256, 4096, dtype=np.uint8).tobytes()
        vol.append_needle(Needle(cookie=7, id=i, data=payloads[i]))
    base = vol.file_name()
    vol.close()
    generate_ec_files(base, codec_name="cpu")
    write_sorted_file_from_idx(base)
    for sid in (0, 1, 2, 3):
        os.remove(base + to_ext(sid))
    ev = EcVolume(base, volume_id=3, codec_name="tpu_xor")
    if route == "service":
        ev.decode_service = service
    try:
        _off, _size, intervals = ev.locate(5)
        sid, off = intervals[0].to_shard_id_and_offset(
            ev.large_block_size, ev.small_block_size)
        assert sid == 0     # a lost shard: needle 5 is decoded
        assert ev.read_needle(5).data == payloads[5]
        with open(base + to_ext(9), "r+b") as f:
            f.seek(off + 100)
            b = f.read(1)
            f.seek(off + 100)
            f.write(bytes([b[0] ^ 0x10]))
        ev._invalidate_intervals()   # the cached decode predates the rot
        with pytest.raises(CorruptNeedleError):
            ev.read_needle(5)
        assert ev.read_needle(9).data == payloads[9]
    finally:
        ev.close()
