"""Pallas TPU kernel for the GF(2^8) RS matmul — the hot encode/decode op.

Why a hand kernel: the jnp XOR-network formulation (rs_jax.py) is correct
but XLA materialises the eight doubling-chain multiples as full HBM temps
(each consumed by several parity outputs, so fusion CSEs them into kLoop
fusion outputs) — ~8x extra HBM traffic and OOM at large blocks.  Here the
whole multiply-accumulate network runs per VMEM tile: grid over column
blocks, each step DMAs a (S, R, 128) tile in, computes the doubling chain
and the constant-selected XOR accumulation on the VPU, and writes the
(R_out, R, 128) parity tile — HBM traffic is exactly input+output.

SWAR trick: Mosaic has no u8 vector shifts, so bytes are packed four-to-a-
lane as uint32 and the doubling step works on all four at once:

    x*2 (per byte) = ((x << 1) & 0xFEFEFEFE) ^ (((x >> 7) & 0x01010101) * 0x1D)

The high-bit extraction keeps bytes independent (0x1D < 0x100, no carries),
so one u32 op stream processes 4 GF bytes per lane — 512 bytes per VPU op
at full lane width.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from . import gf256

LANES = 128
BYTES_PER_LANE = 4  # uint32 SWAR packing
_REDUCE = 0x1D1D1D1D
_HI_MASK = 0x80808080
_LO7_MASK = 0x7F7F7F7F
_ONE_MASK = 0x01010101

# sublane rows per grid step: each input tile is (S, SUBLANES, 128) u32
# = SUBLANES*512 bytes per shard per step
SUBLANES = 256  # 128KB/shard/step; 14 shards ~ 1.8MB VMEM live per stage


def _kernel_body(rows: tuple[tuple[int, ...], ...], data_ref, out_ref):
    """data_ref: (S, R, 128) u32; out_ref: (R_out, R, 128) u32."""
    n_out = len(rows)
    s = len(rows[0])
    max_bit = [0] * s
    for row in rows:
        for j, c in enumerate(row):
            for k in range(8):
                if (c >> k) & 1:
                    max_bit[j] = max(max_bit[j], k)
    accs: list = [None] * n_out
    for j in range(s):
        x = data_ref[j]
        for k in range(max_bit[j] + 1):
            if k > 0:
                hi = (x >> 7) & jnp.uint32(_ONE_MASK)
                x = ((x << 1) & jnp.uint32(0xFEFEFEFE)) ^ (
                    hi * jnp.uint32(0x1D)
                )
            for i in range(n_out):
                if (rows[i][j] >> k) & 1:
                    accs[i] = x if accs[i] is None else accs[i] ^ x
    for i in range(n_out):
        out_ref[i] = (
            accs[i] if accs[i] is not None else jnp.zeros_like(data_ref[0])
        )


# Mosaic compiles the kernel; only tests/conftest.py flips this (the CPU
# backend cannot compile a TPU kernel, so the suite runs the interpreter).
# No env var or CLI flag reaches it: on a non-TPU backend a server fails
# loudly instead of interpreting at 1/1000th speed without a word.
INTERPRET = False


def make_apply_pallas(
    rows: tuple[tuple[int, ...], ...], interpret: bool | None = None
):
    """Host (S, B) uint8 bytes -> async ``PackedRows`` GF matmul via the
    Pallas kernel.  interpret=None takes the module default ``INTERPRET``.
    """
    return _make_apply_pallas(
        rows, INTERPRET if interpret is None else bool(interpret))


class PackedRows:
    """Async result of a packed GF apply: ``(..., R, rows, 128)`` uint32
    lane tiles still on (or on their way from) the device.  ``np.asarray``
    of it blocks, reads back and returns the ``(..., R, B)`` uint8 bytes —
    the device holds such tiles in the host's own order, so the readback
    is a copy, and the u32->u8 view and the trim of the host-side pad are
    free ndarray views.
    """

    __slots__ = ("dev", "width")

    def __init__(self, dev: jax.Array, width: int):
        self.dev = dev
        self.width = width

    def block_until_ready(self) -> "PackedRows":
        self.dev.block_until_ready()
        return self

    def copy_to_host_async(self) -> None:
        """Start the readback now, on the runtime's threads: ``np.asarray``
        later picks up a host copy that is under way or done."""
        self.dev.copy_to_host_async()

    def is_ready(self) -> bool:
        return self.dev.is_ready()

    def __array__(self, dtype=None, copy=None):
        out = np.asarray(self.dev).view(np.uint8).reshape(
            *self.dev.shape[:-2], -1)[..., :self.width]
        return out if dtype is None else out.astype(dtype, copy=False)


def pack_lane_tiles(data: np.ndarray, block_rows: int = SUBLANES) -> np.ndarray:
    """(S, B) uint8 host bytes -> (S, R, 128) uint32 lane tiles, zero-
    padded ON THE HOST so R splits into whole blocks of ``block_rows``
    (the kernel's grid step; parallel.mesh: eight sublanes on each device).

    The host already holds the bytes, so the u8->u32 repack is a free
    ndarray view here; done on the device it cost a 64x temp and an 80 s
    compile for the 1x10 decode matrix.  Only odd widths pay a copy."""
    s, b = data.shape
    tile = LANES * BYTES_PER_LANE  # 512 bytes per lane-tile row
    rows_total = -(-b // tile)
    if rows_total > block_rows:
        rows_total = -(-rows_total // block_rows) * block_rows
    padded = rows_total * tile
    if padded != b:
        buf = np.zeros((s, padded), dtype=np.uint8)
        buf[:, :b] = data
        data = buf
    elif not data.flags["C_CONTIGUOUS"]:
        data = np.ascontiguousarray(data)
    return data.view(np.uint32).reshape(s, rows_total, LANES)


@functools.lru_cache(maxsize=None)
def _make_apply_pallas(rows: tuple[tuple[int, ...], ...], interpret: bool):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    n_out = len(rows)
    s = len(rows[0])
    kernel = functools.partial(_kernel_body, rows)

    @jax.jit
    def apply32_3d(d3: jax.Array) -> jax.Array:
        """(S, R, 128) u32 with R % min(SUBLANES, R) == 0 -> (n_out, R, 128).

        The host views bytes as uint32 and reshapes to lane tiles itself
        (``pack_lane_tiles``), so the jitted program is EXACTLY the
        pallas_call — no reshape/pad/bitcast ops whose layout assignment
        could materialise a transposed or widened copy in HBM.
        """
        assert d3.dtype == jnp.uint32 and d3.ndim == 3
        assert d3.shape[0] == s and d3.shape[2] == LANES
        rows_total = d3.shape[1]
        tile_rows = min(SUBLANES, rows_total)
        assert rows_total % tile_rows == 0, (rows_total, tile_rows)
        return pl.pallas_call(
            kernel,
            out_shape=jax.ShapeDtypeStruct(
                (n_out, rows_total, LANES), jnp.uint32),
            grid=(rows_total // tile_rows,),
            in_specs=[
                pl.BlockSpec(
                    (s, tile_rows, LANES),
                    lambda g: (0, g, 0),
                    memory_space=pltpu.VMEM,
                )
            ],
            out_specs=pl.BlockSpec(
                (n_out, tile_rows, LANES),
                lambda g: (0, g, 0),
                memory_space=pltpu.VMEM,
            ),
            interpret=interpret,
        )(d3)

    def apply(data: np.ndarray) -> PackedRows:
        """(S, B) uint8 HOST bytes -> async (n_out, B) result."""
        data = np.asarray(data)
        assert data.dtype == np.uint8 and data.shape[0] == s, (
            data.dtype, data.shape, s)
        return PackedRows(
            apply32_3d(jnp.asarray(pack_lane_tiles(data))), data.shape[1])

    apply.as_u32_3d = apply32_3d  # type: ignore[attr-defined]
    return apply


def apply_matrix_pallas(
    matrix: np.ndarray, data: np.ndarray, interpret: bool | None = None
) -> PackedRows:
    rows = tuple(tuple(int(c) for c in r) for r in np.asarray(matrix))
    return make_apply_pallas(rows, interpret)(data)


def parity_fn(data_shards: int = 10, parity_shards: int = 4,
              interpret: bool | None = None):
    """The flagship fused kernel: (10, B) stripe -> (4, B) parity."""
    m = gf256.rs_parity_matrix(data_shards, parity_shards)
    rows = tuple(tuple(int(c) for c in r) for r in m)
    return make_apply_pallas(rows, interpret)
