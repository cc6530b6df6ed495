"""Multi-chip EC: sharded batch encode and collective decode over a Mesh.

This is the ICI story for the codec (SURVEY.md §2.9, BASELINE config 4:
batch ec.encode of 64 volumes across a v5e-8 slice):

* ``batch_encode_sharded`` — (V, 10, B) volumes with V sharded over the
  ``dp`` mesh axis and the block/column dimension over ``sp``.  Parity is
  columnwise so encode partitions with ZERO collectives; XLA just runs the
  fused GF kernel per device.

* ``distributed_reconstruct`` — the decode matmul with the *shard* axis
  split across ``dp``.  GF addition is XOR, which integer matmuls can't
  accumulate across devices — but in the bit-plane formulation XOR is
  addition mod 2, so each device computes the partial int32 bit-matmul over
  its local shards, a ``psum`` over ``dp`` rides the ICI, and the mod-2 is
  taken after the collective.  This is the TPU-native analogue of the
  reference's parallel 10-of-14 recovery fan-in (store_ec.go:324-378).

Tested on a virtual 8-device CPU mesh; the same code drives real slices.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..ops import gf256
from ..ops.rs_jax import _rows_of, make_apply_xor
from ..ops.rs_pallas import (
    BYTES_PER_LANE,
    LANES,
    PackedRows,
    pack_lane_tiles,
)

# A device's least share of a job's row: eight sublanes of 128 uint32 lanes,
# the tile the TPU stores 32-bit arrays in — laid out on the device as on
# the host.  The codec service's width buckets are multiples of it.
_ROW_BYTES = LANES * BYTES_PER_LANE
_TILE_ROWS = 8
TILE_BYTES = _TILE_ROWS * _ROW_BYTES


def make_mesh(
    devices=None,
    axis_names=("dp", "sp"),
    dp: int | None = None,
    shard_axis: int = 10,
) -> Mesh:
    """2-D mesh: dp (volumes / shard-splitting) x sp (block columns).

    ``dp`` must divide both the device count and the GF shard axis
    (``distributed_reconstruct`` splits S=10 shards over dp).  When not
    given, pick the largest valid dp ≤ sqrt(n) so the mesh stays balanced:
    n=8 -> (2, 4); n=4 -> (2, 2); n=16 -> (2, 8); odd n -> (1, n).
    """
    devices = list(devices if devices is not None else jax.devices())
    n = len(devices)
    if dp is None:
        dp = 1
        for cand in range(2, int(n**0.5) + 1):
            if n % cand == 0 and shard_axis % cand == 0:
                dp = cand
    elif n % dp or shard_axis % dp:
        raise ValueError(
            f"dp={dp} must divide both device count {n} and "
            f"shard axis {shard_axis}"
        )
    sp = n // dp
    arr = np.asarray(devices[: dp * sp]).reshape(dp, sp)
    return Mesh(arr, axis_names)


# ---------------------------------------------------------------------------
# Batch encode: pure data/sequence parallel, no collectives.
# ---------------------------------------------------------------------------


@functools.lru_cache(maxsize=None)
def _batch_encoder(rows: tuple[tuple[int, ...], ...]):
    apply_one = make_apply_xor(rows)

    def encode(batch: jax.Array) -> jax.Array:  # (V, S, B) -> (V, R, B)
        return jax.vmap(apply_one)(batch)

    return encode


@functools.lru_cache(maxsize=None)
def _sharded_encoder(mesh: Mesh, data_shards: int, parity_shards: int):
    """One jitted sharded encoder per (mesh, geometry) — rebuilding the
    jit wrapper per call would recompile on EVERY invocation, turning a
    multi-step batch encode into a compile storm."""
    rows = _rows_of(gf256.rs_parity_matrix(data_shards, parity_shards))
    encode = _batch_encoder(rows)
    in_sharding = NamedSharding(mesh, P("dp", None, "sp"))
    out_sharding = NamedSharding(mesh, P("dp", None, "sp"))
    return jax.jit(encode, in_shardings=in_sharding,
                   out_shardings=out_sharding)


def batch_encode_sharded(
    mesh: Mesh,
    volumes: jax.Array | np.ndarray,
    data_shards: int = 10,
    parity_shards: int = 4,
) -> jax.Array:
    """Encode (V, data_shards, B) -> (V, parity_shards, B) over the mesh.

    V shards over ``dp``, B over ``sp``; the stripe axis stays local.
    """
    # host arrays go in as they are: the jit's in_shardings then move each
    # device's slice straight to it (jnp.asarray first would land the
    # whole block on device 0 and reshard from there)
    return _sharded_encoder(mesh, data_shards, parity_shards)(volumes)


@functools.lru_cache(maxsize=None)
def _sharded_apply_jobs(mesh: Mesh, rows: tuple[tuple[int, ...], ...],
                        n: int):
    """One jitted program per (mesh, matrix, n): n jobs' own (S, T, 128)
    uint32 lane tiles in (ops.rs_pallas.pack_lane_tiles: a job's bytes,
    four to a word in host order), the (n, R, T, 128) stack of their
    results out, every array's tile rows — so its columns — spread over
    ALL devices of the mesh.  The codec service's device batch, for encode
    (parity rows) and decode (plan rows) alike: each job's host array goes
    to the devices as it is (no (n, S, B) block is ever built on the
    host), both ways in the layout the device keeps (an 8-bit array would
    be re-laid byte by byte on the host, in and out), and the XOR network
    runs job after job on the packed words inside the one program, so its
    temporaries are one job's whatever n is (a vmapped block program's
    grow with V: at V = 8 of the encoder's slices the compiler refuses it
    on one v5e).  On more than one device the result is gathered: every
    device ends with the whole stack (0.4 bytes over ICI per job byte), so
    the host fetches ONE array from one device, as it does on one chip —
    fetching a column-sharded result is an `np.empty` of the whole shape
    and a slice assignment per shard on the thread that asks for it."""
    apply_one = make_apply_xor(rows)

    def gf_apply(*tiles: jax.Array) -> jax.Array:
        return jnp.stack([apply_one(t) for t in tiles])

    # the program's name on the device plane of a trace
    # (`jit_gf_apply_r4_s10`): stable across a change of kernel, and it
    # tells the matrix shapes in mixed traffic apart
    gf_apply.__name__ = f"gf_apply_r{len(rows)}_s{len(rows[0])}"
    cols = mesh.axis_names
    whole = P() if mesh.size > 1 else P(None, None, cols, None)
    return jax.jit(
        gf_apply,
        in_shardings=(NamedSharding(mesh, P(None, cols, None)),) * n,
        out_shardings=NamedSharding(mesh, whole))


def _lane_tile_shape(mesh: Mesh, shape: tuple) -> tuple:
    """The (S, T, 128) of an (S, B) uint8 job on this mesh."""
    s, b = shape
    if b % (TILE_BYTES * mesh.size):
        raise ValueError(
            f"job width {b} is no multiple of {TILE_BYTES} bytes x "
            f"{mesh.size} devices")
    return s, b // _ROW_BYTES, LANES


def jobs_apply_sharded(mesh: Mesh, matrix: np.ndarray, blocks) -> PackedRows:
    """Apply one (R, S) GF matrix to each of ``blocks`` — equal-shape
    C-contiguous (S, B) uint8 host arrays, B a multiple of 4096 x the
    device count — in one device program over views of them; ->
    ``PackedRows`` of (len(blocks), R, B).  Dispatch is async."""
    _lane_tile_shape(mesh, blocks[0].shape)
    tiles = [pack_lane_tiles(b, _TILE_ROWS * mesh.size) for b in blocks]
    return PackedRows(
        _sharded_apply_jobs(mesh, _rows_of(np.asarray(matrix)), len(tiles))(
            *tiles), blocks[0].shape[1])


def compile_jobs_apply(mesh: Mesh, matrix: np.ndarray, n: int,
                       shape: tuple) -> None:
    """Compile, and run nothing, the program ``jobs_apply_sharded`` takes
    for n jobs of this (S, B) shape: the first real batch of that many
    then finds it compiled."""
    tiles = jax.ShapeDtypeStruct(_lane_tile_shape(mesh, shape), jnp.uint32)
    _sharded_apply_jobs(mesh, _rows_of(np.asarray(matrix)), n).lower(
        *[tiles] * n).compile()


# ---------------------------------------------------------------------------
# Distributed decode: shard axis split over dp, psum-mod-2 over ICI.
# ---------------------------------------------------------------------------


def _bit_unpack(data: jax.Array) -> jax.Array:
    """(S, B) uint8 -> (8S, B) int8 bit-planes."""
    s, b = data.shape
    shifts = jnp.arange(8, dtype=jnp.uint8)
    bits = ((data[:, None, :] >> shifts[None, :, None]) & 1).astype(jnp.int8)
    return bits.reshape(s * 8, b)


def _bit_pack(pbits: jax.Array) -> jax.Array:
    """(8R, B) -> (R, B) uint8."""
    r8, b = pbits.shape
    p = pbits.reshape(r8 // 8, 8, b).astype(jnp.uint8)
    out = p[:, 0, :]
    for k in range(1, 8):
        out = out | (p[:, k, :] << k)
    return out


@functools.lru_cache(maxsize=None)
def _reconstruct_program(mesh: Mesh):
    """One jitted psum-decode per mesh.  The bit matrix is an ARGUMENT, so
    every decode plan of a shape shares the compiled program — a jit
    wrapper rebuilt per call would recompile on every rebuild slice."""

    def local_fn(a_local: jax.Array, x_local: jax.Array) -> jax.Array:
        # a_local: (S/dp, 8R, 8), x_local: (S/dp, B/sp)
        s_loc, r8, _ = a_local.shape
        bits = _bit_unpack(x_local)  # (8*S/dp, B/sp)
        a_flat = a_local.transpose(1, 0, 2).reshape(r8, 8 * s_loc)
        partial = jax.lax.dot_general(
            a_flat, bits, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.int32,
        )
        total = jax.lax.psum(partial, axis_name="dp")  # ICI collective
        return _bit_pack(total & 1)

    in_specs = (P("dp", None, None), P("dp", "sp"))
    return jax.jit(
        shard_map(local_fn, mesh=mesh, in_specs=in_specs,
                  out_specs=P(None, "sp")),
        # explicit, so host inputs are split on their way to the devices
        in_shardings=tuple(NamedSharding(mesh, spec) for spec in in_specs),
    )


def distributed_reconstruct(
    mesh: Mesh,
    matrix: np.ndarray,
    inputs: jax.Array | np.ndarray,
) -> jax.Array:
    """Apply a (R, S) GF matrix to (S, B) inputs with S split over ``dp``
    and B over ``sp``; partial bit-matmuls psum over ``dp``.

    S must be divisible by the dp axis size (10 and 2 in practice).
    """
    r, s = matrix.shape
    dp = mesh.shape["dp"]
    if s % dp:
        raise ValueError(f"shard axis {s} not divisible by dp={dp}")
    a = gf256.bit_matrix(np.asarray(matrix, dtype=np.uint8)).astype(np.int8)
    a = a.reshape(8 * r, s, 8).transpose(1, 0, 2)  # (S, 8R, 8) per-shard slices
    return _reconstruct_program(mesh)(a, inputs)


# ---------------------------------------------------------------------------
# The "full training step" analogue: encode a sharded batch of volumes AND
# run a distributed decode — exercises dp, sp shardings and a dp-psum.
# ---------------------------------------------------------------------------


def train_step(
    mesh: Mesh,
    volumes: jax.Array | np.ndarray,
    decode_inputs: jax.Array | np.ndarray,
    decode_matrix: np.ndarray,
) -> tuple[jax.Array, jax.Array]:
    parity = batch_encode_sharded(mesh, volumes)
    rebuilt = distributed_reconstruct(mesh, decode_matrix, decode_inputs)
    return parity, rebuilt
