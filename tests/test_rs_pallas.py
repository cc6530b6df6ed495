"""Pallas kernel conformance (interpret mode on the CPU test mesh)."""

import numpy as np
import jax.numpy as jnp

from seaweedfs_tpu.ops import gf256
from seaweedfs_tpu.ops.rs_cpu import ReedSolomon
from seaweedfs_tpu.ops.rs_pallas import apply_matrix_pallas, parity_fn


def test_pallas_parity_matches_cpu():
    fn = parity_fn()  # interpret=None -> rs_pallas.INTERPRET (conftest)
    rng = np.random.default_rng(0)
    data = rng.integers(0, 256, (10, 4096), dtype=np.uint8)
    got = np.asarray(fn(jnp.asarray(data)))
    shards = list(data) + [np.zeros(4096, np.uint8) for _ in range(4)]
    ReedSolomon().encode(shards)
    for i in range(4):
        assert np.array_equal(got[i], shards[10 + i])


def test_pallas_unaligned_width():
    fn = parity_fn()
    rng = np.random.default_rng(1)
    for b in (1, 100, 511, 513, 1000):
        data = rng.integers(0, 256, (10, b), dtype=np.uint8)
        got = np.asarray(fn(jnp.asarray(data)))
        shards = list(data) + [np.zeros(b, np.uint8) for _ in range(4)]
        ReedSolomon().encode(shards)
        for i in range(4):
            assert np.array_equal(got[i], shards[10 + i]), (b, i)


def test_pallas_lane_tile_entry():
    """The bare kernel entry over host-packed uint32 lane tiles, incl. a
    width that pads to whole SUBLANES blocks on the host."""
    from seaweedfs_tpu.ops.rs_pallas import LANES, SUBLANES, pack_lane_tiles

    fn = parity_fn()
    rng = np.random.default_rng(2)
    for b in (2048, SUBLANES * LANES * 4 + 7):
        data = rng.integers(0, 256, (10, b), dtype=np.uint8)
        d3 = pack_lane_tiles(data)
        assert d3.dtype == np.uint32 and d3.shape[2] == LANES
        assert d3.shape[1] % min(SUBLANES, d3.shape[1]) == 0
        got = np.asarray(fn.as_u32_3d(jnp.asarray(d3)))
        got8 = got.view(np.uint8).reshape(4, -1)[:, :b]
        assert np.array_equal(got8, ReedSolomon().parity_of(data)), b


def test_pallas_decode_matrix():
    rng = np.random.default_rng(3)
    rs = ReedSolomon()
    shards = [rng.integers(0, 256, 1024).astype(np.uint8) for _ in range(10)]
    shards += [np.zeros(1024, np.uint8) for _ in range(4)]
    rs.encode(shards)
    present = [0, 1, 4, 5, 6, 7, 8, 9, 10, 13]  # lost 2,3,11,12
    dec = gf256.decode_matrix_for(gf256.rs_matrix(10, 14), 10, present)
    inputs = jnp.asarray(np.stack([shards[i] for i in present]))
    rebuilt = np.asarray(apply_matrix_pallas(dec, inputs))
    for i in range(10):
        assert np.array_equal(rebuilt[i], shards[i])


def test_codec_registry_pallas():
    from seaweedfs_tpu.ops.codec import get_codec

    c = get_codec("tpu")
    assert c.impl == "pallas"
    rng = np.random.default_rng(4)
    shards = [rng.integers(0, 256, 512).astype(np.uint8) for _ in range(10)]
    shards += [np.zeros(512, np.uint8) for _ in range(4)]
    ref = [s.copy() for s in shards]
    ReedSolomon().encode(ref)
    c.encode(shards)
    for i in range(14):
        assert np.array_equal(shards[i], ref[i])
