"""The plain reference, the byte counts and the comparisons, pinned."""

import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark import check, dataset, gf_work  # noqa: E402
from benchmark import reference_gf as ref  # noqa: E402
from benchmark.faults import Fault  # noqa: E402

# klauspost/Backblaze RS(10,4) parity rows (tests/test_rs_known_answers.py
# derives them independently of ops/gf256.py as well)
PARITY_MATRIX_10_4 = [
    [129, 150, 175, 184, 210, 196, 254, 232, 3, 2],
    [150, 129, 184, 175, 196, 210, 232, 254, 2, 3],
    [191, 214, 98, 10, 6, 111, 223, 183, 5, 4],
    [214, 191, 10, 98, 111, 6, 183, 223, 4, 5],
]
# parity of the stripe d[i, j] = (i*31 + j*7 + 1) % 256, shape (10, 16)
KAT_AFFINE_PARITY = [
    [11, 23, 69, 36, 227, 42, 14, 188, 160, 242, 125, 202, 70, 17, 10, 59],
    [140, 180, 100, 206, 194, 113, 239, 142, 65, 191, 28, 93, 103, 130, 100, 228],
    [140, 59, 131, 42, 246, 142, 87, 112, 34, 134, 166, 221, 96, 38, 165, 136],
    [140, 75, 162, 160, 215, 199, 54, 186, 67, 166, 199, 153, 65, 110, 122, 12],
]


def test_generator_matrix_known_answer():
    assert ref.PARITY_ROWS == PARITY_MATRIX_10_4
    assert ref.MATRIX[:10] == [[int(i == j) for j in range(10)]
                               for i in range(10)]


def test_parity_known_answer():
    d = np.array([[(i * 31 + j * 7 + 1) % 256 for j in range(16)]
                  for i in range(10)], dtype=np.uint8)
    assert ref.parity_of(d).tolist() == KAT_AFFINE_PARITY


def test_field_tables():
    assert ref.gf_mul(2, 128) == 0x1D          # x * x^7 = x^8 = poly - x^8
    for a in (1, 2, 3, 87, 255):
        assert ref.gf_mul(a, ref.gf_inv(a)) == 1
        assert ref.MUL[a][ref.gf_inv(a)] == 1
    assert ref.gf_pow(0, 0) == 1 and ref.gf_pow(0, 3) == 0


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_reference_agrees_with_the_host_codec(seed):
    from seaweedfs_tpu.ops.codec import get_codec

    cpu = get_codec("cpu")
    data = np.random.default_rng(seed).integers(
        0, 256, (10, 4099), dtype=np.uint8)
    assert np.array_equal(ref.parity_of(data), np.asarray(cpu.parity_of(data)))


@pytest.mark.parametrize("lost", [[3], [0, 1, 2, 3], [0, 2, 5, 9],
                                  [10, 11, 12, 13], [9, 13]])
def test_reconstruct_any_four(lost):
    data = np.random.default_rng(7).integers(0, 256, (10, 513),
                                             dtype=np.uint8)
    full = np.concatenate([data, ref.parity_of(data)])
    got = ref.reconstruct(
        {i: full[i] for i in range(14) if i not in lost}, 513)
    assert np.array_equal(got, full)


def test_reconstruct_refuses_five_losses():
    with pytest.raises(ValueError):
        ref.reconstruct({i: np.zeros(4, np.uint8) for i in range(9)}, 4)


@pytest.mark.parametrize("dat,large,small,want", [
    (1, 100, 10, (0, 1, 10)),
    (100, 100, 10, (0, 1, 10)),
    (101, 100, 10, (0, 2, 20)),
    (1000, 100, 10, (0, 10, 100)),       # strictly greater, as upstream
    (1001, 100, 10, (1, 1, 110)),
    (1 << 30, 1 << 30, 1 << 20, (0, 103, 103 << 20)),
])
def test_shard_layout(dat, large, small, want):
    assert ref.shard_layout(dat, large, small) == want


@pytest.mark.parametrize("fn,args,want", [
    (gf_work.parity_bytes, (10e9,), 14e9),
    (gf_work.rebuild_bytes, (10e9, 1), 11e9),
    (gf_work.rebuild_bytes, (10e9, 4), 14e9),
    (gf_work.needed_bytes, (160 << 20, 10, 4), 224 << 20),
])
def test_gf_work_byte_counts(fn, args, want):
    assert fn(*args) == pytest.approx(want)


def test_roofline_share():
    # 8.19 GB in 0.1 s on an 819 GB/s chip is a tenth of the roofline
    assert gf_work.hbm_roofline_pct(8.19e9, 0.1, 819e9) == pytest.approx(10.0)
    with pytest.raises(ValueError):
        gf_work.hbm_roofline_pct(1.0, 0.0, 819e9)
    with pytest.raises(ValueError):
        gf_work.needed_bytes(1.0, 0, 4)


def test_needles_and_small_files_come_from_the_seed_alone():
    a, b = dataset.Needles(5, 1 << 20, 1024, 1 << 16), \
        dataset.Needles(5, 1 << 20, 1024, 1 << 16)
    assert a.sizes == b.sizes and a.data(3) == b.data(3)
    assert a.data(3)[:8] == (3).to_bytes(8, "little")
    assert dataset.Needles(6, 1 << 20, 1024, 1 << 16).sizes != a.sizes
    assert a.total >= 1 << 20 and min(a.sizes) >= 1024
    f = dataset.SmallFiles(5, 1024)
    assert len(f.data(0)) == 1024 and f.data(70000) != f.data(70001)
    assert f.data(9) == dataset.SmallFiles(5, 1024).data(9)
    assert set(a.sample(np.random.default_rng(1), 4)) >= set(
        sorted(range(len(a)), key=lambda i: -a.sizes[i])[:4])


@pytest.fixture(scope="module")
def encoded_volume(tmp_path_factory):
    """A 25 MiB volume built as the benchmark builds it and encoded by the
    program's host codec at upstream block sizes."""
    from seaweedfs_tpu.storage.ec.encoder import (
        write_ec_files, write_sorted_file_from_idx)

    d = str(tmp_path_factory.mktemp("vol"))
    needles = dataset.Needles(11, 25 << 20, 1024, 1 << 20)
    base = dataset.build_volume(d, "warm", 3, (
        needles.data(i) for i in range(len(needles))))
    clone = dataset.clone_volume(base, d, "warm", 4)
    assert os.path.samefile(base + ".dat", clone + ".dat")
    write_ec_files(base, codec_name="cpu")
    write_sorted_file_from_idx(base)
    return base


def test_comparisons_pass_on_a_sound_encode(encoded_volume):
    base = encoded_volume
    rows = check.pick_rows(np.random.default_rng(0), 3, 2)
    assert rows[-1] == 2
    assert check.shard_bytes_differing(
        base + ".dat", [base], list(range(14)), [0, 1, 2]) == 0
    assert check.ecx_bytes_differing(base + ".idx", [base]) == 0


def test_comparisons_fail_the_control_and_the_fault(encoded_volume, tmp_path):
    base = encoded_volume
    every = list(range(14))
    # the control: RS(10,3) passed off as RS(10,4)
    three = [r[:] for r in ref.PARITY_ROWS[:3]] + [[0] * 10]
    assert check.shard_bytes_differing(
        base + ".dat", [base], every, [0, 2], parity_rows=three) > 1 << 20
    work = str(tmp_path / "w")
    for i in every:
        with open(check.shard_path(base, i), "rb") as f, \
                open(check.shard_path(work, i), "wb") as g:
            g.write(f.read())
    fault = Fault("flip-shard-byte", armed=True)
    fault.ec_files(work, every)
    assert fault.fired == 1
    assert check.shard_bytes_differing(
        base + ".dat", [work], every, [2]) == 1
    Fault("rs-10-3", armed=True).ec_files(work, every)
    assert check.shard_bytes_differing(
        base + ".dat", [work], [13], [0]) > 1 << 19
    os.remove(check.shard_path(work, 5))
    assert check.shard_bytes_differing(
        base + ".dat", [work], [5], [0]) == 3 << 20
    with open(work + ".ecx", "wb") as g:
        g.write(b"\0" * 16)
    assert check.ecx_bytes_differing(base + ".idx", [work]) > 0
    assert check.ecx_bytes_differing(base + ".idx", [work + "-none"]) > 0


def test_unknown_control_is_refused():
    with pytest.raises(ValueError):
        Fault("no-such-fault")
    assert Fault(None).put_body(63, b"x" * 200) == b"x" * 200
    assert Fault("alter-put", armed=True).put_body(63, b"x" * 200) != b"x" * 200
    # before the window opens (warm-up) a planted fault does nothing
    assert Fault("alter-put").put_body(63, b"x" * 200) == b"x" * 200
