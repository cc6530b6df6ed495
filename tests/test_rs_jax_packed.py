"""The XOR network on packed words (ISSUE 31): the one dtype-generic
network of ops/rs_jax.py on uint32 lane tiles — what the codec service's
device program runs — equals the plain reference byte for byte, as it
does on uint8; no carry crosses a byte lane; and the service delivers the
same bytes at every width, on one device and on the mesh a four-device
process builds for itself."""

from __future__ import annotations

import itertools
import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from benchmark import reference_gf as ref  # noqa: E402
from helpers import run_four_device_child  # noqa: E402

from seaweedfs_tpu.ops import codec_service, gf256  # noqa: E402
from seaweedfs_tpu.ops.codec_service import CodecService  # noqa: E402
from seaweedfs_tpu.ops.rs_jax import _rows_of, make_apply_xor  # noqa: E402
from seaweedfs_tpu.ops.rs_pallas import pack_lane_tiles  # noqa: E402


def _decode_plan(lost):
    present = [i for i in range(14) if i not in lost]
    return gf256.decode_plan_for(
        gf256.rs_matrix(10, 14), 10, present, tuple(lost))


# every constant's bit pattern the doubling chain can meet at its edges,
# and a row of zeros (an output no input reaches)
_EDGE_CONSTANTS = np.array(
    [[0, 1, 0x80, 0xFF, 0, 1, 0x80, 0xFF, 0, 1],
     [0xFF] * 10,
     [0] * 10,
     [0x80, 0x80, 1, 1, 0xFF, 0, 0x80, 1, 0xFF, 0]], dtype=np.uint8)

MATRICES = {
    "parity": lambda: gf256.rs_parity_matrix(10, 4),
    "decode_lost_0123": lambda: _decode_plan([0, 1, 2, 3]),
    "decode_lost_02": lambda: _decode_plan([0, 2]),
    "one_row_plan": lambda: _decode_plan([3]),
    "edge_constants": lambda: _EDGE_CONSTANTS,
}


def _edge_bytes(width: int) -> np.ndarray:
    """(10, width): the bytes 0x80 / 0xFF / 0x00 / 0x01 as neighbours in
    every order, so each stands in every byte lane of a word beside each
    of the others; every row starts one byte further on."""
    orders = np.array(list(itertools.permutations([0x80, 0xFF, 0x00, 0x01])),
                      dtype=np.uint8).reshape(-1)  # 24 words, 96 bytes
    row = np.resize(orders, width + 10)
    return np.stack([row[i:i + width] for i in range(10)])


INPUTS = {
    "random": lambda w: np.random.default_rng(31).integers(
        0, 256, (10, w), dtype=np.uint8),
    "edge_bytes_in_every_lane": _edge_bytes,
}


@pytest.mark.parametrize("data", sorted(INPUTS))
@pytest.mark.parametrize("matrix", sorted(MATRICES))
def test_packed_network_equals_the_reference(matrix, data):
    rows = MATRICES[matrix]()
    block = np.ascontiguousarray(INPUTS[data](8192))
    want = ref.apply_rows(rows.tolist(), block)
    apply = make_apply_xor(_rows_of(rows))  # ONE network, either dtype
    on_bytes = np.asarray(apply(block))
    tiles = pack_lane_tiles(block)
    assert tiles.dtype == np.uint32 and np.shares_memory(tiles, block)
    on_words = np.asarray(apply(tiles))
    assert on_bytes.dtype == np.uint8 and on_words.dtype == np.uint32
    assert np.array_equal(on_bytes, want)
    assert np.array_equal(
        on_words.view(np.uint8).reshape(len(rows), -1), want)


# -- through the service: both buckets, off-bucket (staged), tiny -----------

_WIDTHS = {"bucket_4k": 4096, "bucket_8k": 8192, "staged_5000": 5000,
           "staged_100": 100}


@pytest.fixture(autouse=True)
def _clean_service_state():
    yield
    codec_service.shutdown_all(timeout=10)


@pytest.mark.parametrize("width", sorted(_WIDTHS))
@pytest.mark.parametrize("matrix", ["decode_lost_0123", "one_row_plan"])
def test_service_applies_decode_plans_at_every_width(matrix, width):
    import jax

    from seaweedfs_tpu.parallel.mesh import make_mesh

    rows = MATRICES[matrix]()
    w = _WIDTHS[width]
    svc = CodecService(mode="device", codec_name="tpu_xor",
                       mesh=make_mesh(jax.devices()[:1]))
    blocks = [np.ascontiguousarray(INPUTS[k](w)) for k in sorted(INPUTS)]
    futs = svc.submit_apply_many(rows, blocks)
    for fut, block in zip(futs, blocks):
        got = np.stack([np.asarray(r) for r in fut.result(120)])
        assert np.array_equal(got, ref.apply_rows(rows.tolist(), block))
    svc.close()


_FOUR_DEVICE_CHILD = """
import json, sys
import numpy as np
sys.path.insert(0, %(root)r)
import jax
from benchmark import reference_gf as ref
from seaweedfs_tpu.ops.codec_service import CodecService
sys.path.insert(0, %(tests)r)
import test_rs_jax_packed as cases
out = {"devices": len(jax.devices())}
svc = CodecService(mode="device", codec_name="tpu_xor")  # its own mesh
out["mesh"] = svc.mesh_shape()
for name in sorted(cases.MATRICES):
    rows = cases.MATRICES[name]()
    same = []
    # a whole bucket on four devices, a wider one, and two staged widths
    for w in (16384, 32768, 20000, 100):
        for k in sorted(cases.INPUTS):
            block = np.ascontiguousarray(cases.INPUTS[k](w))
            got = np.stack([np.asarray(r) for r in
                            svc.submit_apply(rows, block).result(120)])
            same.append(bool(np.array_equal(
                got, ref.apply_rows(rows.tolist(), block))))
    out[name] = same
svc.close()
print(json.dumps(out))
"""


@pytest.fixture(scope="module")
def four_device_run():
    """One child whose CPU backend has four devices: the service builds
    its 1x4 mesh from `jax.devices()`, as a server on four chips does."""
    return run_four_device_child(_FOUR_DEVICE_CHILD % {
        "root": ROOT, "tests": os.path.join(ROOT, "tests")})


@pytest.mark.parametrize("matrix", sorted(MATRICES))
def test_four_device_mesh_equals_the_reference(four_device_run, matrix):
    assert four_device_run["devices"] == 4
    assert four_device_run["mesh"] == "1x4"
    assert four_device_run[matrix] == [True] * 8
