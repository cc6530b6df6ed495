"""EC pipeline conformance — the tier-2 harness from SURVEY.md §4.

Mirrors the reference's ec_test.go: encode a real volume with scaled-down
block sizes (10000/100), then for every live needle assert the `.dat` bytes
equal the striped shard bytes via the interval math, do random 10-of-14
reconstruction per interval, rebuild missing shard files, and round-trip
decode back to a byte-identical `.dat`.  Runs against both the reference's
checked-in fixture (when present) and a synthetic volume, with CPU and TPU
codecs producing identical shards.
"""

import os
import shutil

import numpy as np
import pytest

from seaweedfs_tpu.storage import NeedleMap
from seaweedfs_tpu.storage.ec import constants as ecc
from seaweedfs_tpu.storage.ec.decoder import (
    find_dat_file_size,
    write_dat_file,
    write_idx_file_from_ec_index,
)
from seaweedfs_tpu.storage.ec.encoder import (
    generate_ec_files,
    rebuild_ec_files,
    write_sorted_file_from_idx,
)
from seaweedfs_tpu.storage.ec.locate import locate_data, shard_file_size
from seaweedfs_tpu.storage.ec.volume import EcVolume, NotFoundError
from seaweedfs_tpu.storage.needle import actual_size
from seaweedfs_tpu.storage.super_block import VERSION3
from seaweedfs_tpu.ops.codec import get_codec

from helpers import make_volume

LARGE = 10000  # scaled-down block sizes, as in the reference ec_test.go:16-19
SMALL = 100
REF_EC_DIR = "/root/reference/weed/storage/erasure_coding"


def _encode_dir(base, codec="cpu"):
    generate_ec_files(base, large_block_size=LARGE, small_block_size=SMALL,
                      codec_name=codec, slice_size=50)
    write_sorted_file_from_idx(base)


def _read_ec_interval(base, dat_size, offset, size):
    out = b""
    for iv in locate_data(LARGE, SMALL, dat_size, offset, size):
        sid, soff = iv.to_shard_id_and_offset(LARGE, SMALL)
        with open(base + ecc.to_ext(sid), "rb") as f:
            f.seek(soff)
            out += f.read(iv.size)
    return out


def _validate_all_needles(base):
    """dat bytes == striped shard bytes for every live needle."""
    nm = NeedleMap.load_from_idx(base + ".idx")
    dat_size = os.path.getsize(base + ".dat")
    with open(base + ".dat", "rb") as dat:
        for v in nm.items_ascending():
            if v.size <= 0:
                continue
            dat.seek(v.offset)
            direct = dat.read(v.size)
            striped = _read_ec_interval(base, dat_size, v.offset, v.size)
            assert striped == direct, f"needle {v.key} mismatch"


@pytest.fixture()
def synthetic_base(tmp_path):
    vol = make_volume(str(tmp_path), n_needles=80, seed=3, max_size=3000)
    base = vol.file_name()
    vol.close()
    return base


def test_encode_validate_synthetic(synthetic_base):
    _encode_dir(synthetic_base)
    _validate_all_needles(synthetic_base)
    # shard sizes match the predicted geometry
    dat_size = os.path.getsize(synthetic_base + ".dat")
    expect = shard_file_size(dat_size, LARGE, SMALL)
    for i in range(ecc.TOTAL_SHARDS):
        assert os.path.getsize(synthetic_base + ecc.to_ext(i)) == expect


def test_batched_slices_byte_identical(synthetic_base):
    """Multi-row codec batches (slice >> small block) must produce the
    exact bytes of the one-segment-at-a-time path — parity is columnwise,
    so batching is pure data layout."""
    _encode_dir(synthetic_base)  # slice_size=50: every call one segment
    small_slices = {}
    for i in range(ecc.TOTAL_SHARDS):
        p = synthetic_base + ecc.to_ext(i)
        small_slices[i] = open(p, "rb").read()
        os.remove(p)
    generate_ec_files(synthetic_base, large_block_size=LARGE,
                      small_block_size=SMALL, codec_name="cpu",
                      slice_size=1 << 20)  # whole volume in one batch
    for i in range(ecc.TOTAL_SHARDS):
        batched = open(synthetic_base + ecc.to_ext(i), "rb").read()
        assert batched == small_slices[i], f"shard {i} differs when batched"


def test_auto_codec_resolves():
    codec = get_codec("auto")
    assert codec._impl == "cpu"  # decided in process: this backend is cpu
    data = np.arange(10 * 64, dtype=np.uint8).reshape(10, 64)
    ref = get_codec("cpu").parity_of(data)
    assert np.array_equal(np.asarray(codec.parity_of(data)), np.asarray(ref))


def test_tpu_and_cpu_shards_identical(synthetic_base):
    _encode_dir(synthetic_base, codec="cpu")
    cpu_shards = {}
    for i in range(ecc.TOTAL_SHARDS):
        p = synthetic_base + ecc.to_ext(i)
        cpu_shards[i] = open(p, "rb").read()
        os.remove(p)
    generate_ec_files(synthetic_base, large_block_size=LARGE,
                      small_block_size=SMALL, codec_name="tpu",
                      slice_size=4096)
    for i in range(ecc.TOTAL_SHARDS):
        tpu = open(synthetic_base + ecc.to_ext(i), "rb").read()
        assert tpu == cpu_shards[i], f"shard {i} differs between codecs"


def test_random_10_of_14_reconstruction(synthetic_base):
    _encode_dir(synthetic_base)
    rng = np.random.default_rng(4)
    nm = NeedleMap.load_from_idx(synthetic_base + ".idx")
    dat_size = os.path.getsize(synthetic_base + ".dat")
    codec = get_codec("cpu")
    for v in list(nm.items_ascending())[:20]:
        for iv in locate_data(LARGE, SMALL, dat_size, v.offset, max(v.size, 1)):
            sid, soff = iv.to_shard_id_and_offset(LARGE, SMALL)
            with open(synthetic_base + ecc.to_ext(sid), "rb") as f:
                f.seek(soff)
                want = f.read(iv.size)
            # pick 10 random other shards, reconstruct this interval
            others = [i for i in range(ecc.TOTAL_SHARDS) if i != sid]
            chosen = rng.choice(others, 10, replace=False)
            shards = [None] * ecc.TOTAL_SHARDS
            for i in chosen:
                with open(synthetic_base + ecc.to_ext(int(i)), "rb") as f:
                    f.seek(soff)
                    shards[int(i)] = np.frombuffer(f.read(iv.size), dtype=np.uint8)
            rebuilt = codec.reconstruct_data(shards)
            got = np.asarray(rebuilt[sid]).tobytes() if sid < 10 else None
            if sid < 10:
                assert got == want
            break  # one interval per needle keeps runtime sane


def test_rebuild_missing_shards(synthetic_base, tmp_path):
    _encode_dir(synthetic_base)
    originals = {}
    for i in (0, 4, 11, 13):  # kill 2 data + 2 parity shards
        p = synthetic_base + ecc.to_ext(i)
        originals[i] = open(p, "rb").read()
        os.remove(p)
    rebuilt = rebuild_ec_files(synthetic_base, slice_size=1000)
    assert sorted(rebuilt) == [0, 4, 11, 13]
    for i, want in originals.items():
        got = open(synthetic_base + ecc.to_ext(i), "rb").read()
        assert got == want, f"rebuilt shard {i} not byte-identical"


def test_decode_roundtrip(synthetic_base, tmp_path):
    _encode_dir(synthetic_base)
    orig_dat = open(synthetic_base + ".dat", "rb").read()
    orig_idx = open(synthetic_base + ".idx", "rb").read()
    # move shards to a fresh dir, decode there
    dec_base = str(tmp_path / "decoded" / "1")
    os.makedirs(os.path.dirname(dec_base))
    for i in range(ecc.TOTAL_SHARDS):
        shutil.copy(synthetic_base + ecc.to_ext(i), dec_base + ecc.to_ext(i))
    shutil.copy(synthetic_base + ".ecx", dec_base + ".ecx")

    # find_dat_file_size recovers the logical size from the index (the tail
    # padding beyond the last needle is not recoverable, nor needed)
    import seaweedfs_tpu.storage.ec.decoder as dec

    orig_large = dec.LARGE_BLOCK_SIZE, dec.SMALL_BLOCK_SIZE
    dec.LARGE_BLOCK_SIZE, dec.SMALL_BLOCK_SIZE = LARGE, SMALL
    try:
        dat_size = find_dat_file_size(dec_base, dec_base)
        write_dat_file(dec_base, dat_size)
        write_idx_file_from_ec_index(dec_base)
    finally:
        dec.LARGE_BLOCK_SIZE, dec.SMALL_BLOCK_SIZE = orig_large

    got = open(dec_base + ".dat", "rb").read()
    assert got == orig_dat[: len(got)]
    assert len(got) >= dat_size
    assert open(dec_base + ".idx", "rb").read() == orig_idx


def test_ec_volume_runtime(synthetic_base):
    _encode_dir(synthetic_base)
    ev = EcVolume(synthetic_base, volume_id=1, version=VERSION3,
                  large_block_size=LARGE, small_block_size=SMALL)
    n = ev.read_needle(5)
    assert n.id == 5
    # degraded read: drop 4 shard files from the volume's view
    for sid in (0, 1, 2, 3):
        ev.delete_shard(sid)
    n2 = ev.read_needle(5)
    assert n2.data == n.data
    # delete: tombstone + journal, then read fails
    ev.delete_needle(5)
    with pytest.raises((NotFoundError, KeyError)):
        ev.read_needle(5)
    assert os.path.exists(synthetic_base + ".ecj")
    ev.close()


def test_ec_volume_remote_only_reads(synthetic_base):
    """A server holding only the .ecx (every shard remote) must still locate
    and read needles: shard size is derived from the index, intervals are
    served through the remote-fetch hook."""
    _encode_dir(synthetic_base)
    ref = EcVolume(synthetic_base, volume_id=1, version=VERSION3,
                   large_block_size=LARGE, small_block_size=SMALL)
    want = ref.read_needle(5)
    real_shard_size = ref.shard_size
    ref.close()

    ev = EcVolume(synthetic_base, volume_id=1, version=VERSION3,
                  large_block_size=LARGE, small_block_size=SMALL)
    for sid in list(ev.shards):
        ev.delete_shard(sid)

    def fetch(shard_id, offset, length):
        with open(synthetic_base + ecc.to_ext(shard_id), "rb") as f:
            f.seek(offset)
            return f.read(length)

    ev.remote_fetch = fetch
    assert ev.shard_size == real_shard_size
    got = ev.read_needle(5)
    assert got.data == want.data
    ev.close()


@pytest.mark.skipif(not os.path.isdir(REF_EC_DIR), reason="reference fixture absent")
def test_reference_fixture_conformance(tmp_path):
    """Encode the reference's real 1.dat volume (written by the original
    implementation) with the scaled block sizes from its own test harness and
    validate every needle through the stripe — our equivalent of the
    reference's TestEncodingDecoding over the same bytes."""
    base = str(tmp_path / "1")
    shutil.copy(os.path.join(REF_EC_DIR, "1.dat"), base + ".dat")
    shutil.copy(os.path.join(REF_EC_DIR, "1.idx"), base + ".idx")
    _encode_dir(base)
    _validate_all_needles(base)


def test_locate_data_reference_vectors():
    """The exact interval pinned by the reference's TestLocateData."""
    ivs = locate_data(LARGE, SMALL, 10 * LARGE + 1, 10 * LARGE, 1)
    assert len(ivs) == 1
    iv = ivs[0]
    assert (iv.block_index, iv.inner_block_offset, iv.size, iv.is_large_block) == (
        0, 0, 1, False,
    )
    assert iv.large_block_rows_count == 1
    # spanning interval: from mid-large-area to the end of the volume
    total = 10 * LARGE + 1
    start = 10 * LARGE // 2 + 100
    ivs = locate_data(LARGE, SMALL, total, start, total - start)
    assert sum(i.size for i in ivs) == total - start
    # contiguity: intervals chain across block boundaries
    pos = start
    dat = np.arange(total) % 251
    for iv in ivs:
        pos += iv.size
    assert pos == total


def test_shard_file_size_edges():
    ten = ecc.DATA_SHARDS
    assert shard_file_size(0, LARGE, SMALL) == 0
    assert shard_file_size(1, LARGE, SMALL) == SMALL
    assert shard_file_size(ten * SMALL, LARGE, SMALL) == SMALL
    assert shard_file_size(ten * SMALL + 1, LARGE, SMALL) == 2 * SMALL
    assert shard_file_size(ten * LARGE, LARGE, SMALL) == LARGE  # all small rows
    assert shard_file_size(ten * LARGE + 1, LARGE, SMALL) == LARGE + SMALL
