"""TPU-native Reed-Solomon codec: GF(2^8) matmul as JAX/XLA programs.

This replaces the reference's SIMD-assembly GF kernel (klauspost/reedsolomon,
the hot loop at weed/storage/erasure_coding/ec_encoder.go:179
`enc.Encode(buffers)`) with two TPU formulations:

1. ``xor`` (VPU): GF multiply distributes over the bit decomposition of the
   constant:  c*x = XOR_{k: bit k of c} (2^k * x).  We compute the eight
   doubling multiples 2^k*x once per input shard (a fused chain of shifts and
   conditional reductions by 0x1D) and XOR together the multiples selected by
   the generator matrix.  With the matrix baked in at trace time XLA constant-
   folds the selection into a static XOR network and fuses the whole encode
   into one elementwise kernel: 10 streams in, 4 streams out, no
   intermediates in HBM.
   The network is the same on bytes and on words of them: the codec
   service's program (parallel.mesh) runs it on uint32 lane tiles, four
   bytes to a lane, the layout the host and the device share.

2. ``mxu`` (systolic array): over GF(2) the codec is linear in *bits*, so
   unpack bytes to bit-planes, multiply by the 8Rx8C 0/1 matrix of
   ``gf256.bit_matrix`` as an int8 matmul (int32 accumulation), take parity
   (&1), and repack.  256 MACs/byte keeps the MXU busy and the op
   HBM-bandwidth-bound.

Both are shape-polymorphic in the block length B and are reused by the
multi-volume sharded encoder in seaweedfs_tpu.parallel.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from . import gf256
from ..telemetry import trace

_REDUCE = 0x1D  # low byte of the field polynomial 0x11D


def _byte_lanes(dtype, byte: int):
    """``byte`` in every byte lane of one ``dtype`` word."""
    dtype = np.dtype(dtype)
    return dtype.type(int.from_bytes(bytes([byte]) * dtype.itemsize, "little"))


def _multiples(data: jax.Array) -> list[jax.Array]:
    """[data * 2^k for k in 0..7] — the doubling chain in GF(2^8).

    data: uint8 bytes, or words of them in host order (uint32 lane tiles:
    rs_pallas.pack_lane_tiles), leading axis the shards.  Each step, per
    byte lane: x*2 = ((x & 0x7F) << 1) ^ (0x1D if x & 0x80).  Nothing
    crosses a byte lane — the mask drops the bit the shift would carry
    over, and 0x1D < 0x100 — so a word is its bytes side by side in any
    byte order.
    """
    lo7, one = _byte_lanes(data.dtype, 0x7F), _byte_lanes(data.dtype, 0x01)
    reduce = data.dtype.type(_REDUCE)  # times 0 or 1 in each byte lane
    ms = [data]
    x = data
    for _ in range(7):
        x = ((x & lo7) << 1) ^ (((x >> 7) & one) * reduce)
        ms.append(x)
    return ms


def _xor_network(rows: tuple[tuple[int, ...], ...], data: jax.Array) -> jax.Array:
    """Apply a constant GF matrix to (S, ...) data, bytes or words of
    them (``_multiples``), via the XOR network -> (R, ...)."""
    ms = _multiples(data)
    outs = []
    for row in rows:
        acc = None
        for j, c in enumerate(row):
            for k in range(8):
                if (c >> k) & 1:
                    term = ms[k][j]
                    acc = term if acc is None else acc ^ term
        outs.append(acc if acc is not None else jnp.zeros_like(data[0]))
    return jnp.stack(outs)


@functools.lru_cache(maxsize=None)
def make_apply_xor(rows: tuple[tuple[int, ...], ...]):
    """Jitted (S, ...) -> (R, ...) GF matmul with baked constants, on
    uint8 bytes or on uint32 words of them (``_multiples``)."""

    @jax.jit
    def apply(data: jax.Array) -> jax.Array:
        return _xor_network(rows, data)

    return apply


@functools.lru_cache(maxsize=None)
def make_apply_mxu(rows: tuple[tuple[int, ...], ...]):
    """Jitted GF matmul on the MXU via the bit-plane int8 matmul."""
    m = np.array(rows, dtype=np.uint8)
    a = gf256.bit_matrix(m).astype(np.int8)  # (8R, 8S)

    @jax.jit
    def apply(data: jax.Array) -> jax.Array:
        s, b = data.shape
        shifts = jnp.arange(8, dtype=jnp.uint8)
        bits = ((data[:, None, :] >> shifts[None, :, None]) & 1).astype(jnp.int8)
        bits = bits.reshape(s * 8, b)
        acc = jax.lax.dot_general(
            jnp.asarray(a),
            bits,
            (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.int32,
        )  # (8R, B)
        pbits = (acc & 1).astype(jnp.uint8).reshape(-1, 8, b)
        out = pbits[:, 0, :]
        for k in range(1, 8):
            out = out | (pbits[:, k, :] << k)
        return out

    return apply


def _rows_of(matrix: np.ndarray) -> tuple[tuple[int, ...], ...]:
    return tuple(tuple(int(c) for c in row) for row in np.asarray(matrix))


def _impl_fn(rows: tuple[tuple[int, ...], ...], impl: str):
    if impl == "xor":
        return make_apply_xor(rows)
    if impl == "mxu":
        return make_apply_mxu(rows)
    if impl == "pallas":
        from .rs_pallas import make_apply_pallas

        return make_apply_pallas(rows)
    raise ValueError(f"unknown jax codec impl {impl!r}")


class ReedSolomonTPU:
    """RS(data, parity) codec running the GF matmul on the accelerator.

    API mirrors ops.rs_cpu.ReedSolomon (encode / reconstruct /
    reconstruct_data over lists of equal-length uint8 numpy arrays), plus
    async dispatch entries (encode_device / apply_rows_device) used by the
    streaming file encoder and rebuild pipelines.
    """

    def __init__(
        self,
        data_shards: int = 10,
        parity_shards: int = 4,
        impl: str = "xor",
    ):
        self.data_shards = data_shards
        self.parity_shards = parity_shards
        self.total_shards = data_shards + parity_shards
        self.impl = impl
        self.matrix = gf256.rs_matrix(data_shards, self.total_shards)

    # -- async device entries ---------------------------------------------

    def apply_rows_device(self, rows: np.ndarray, inputs: np.ndarray):
        """Arbitrary (R, S) GF matrix x (S, B) uint8 HOST bytes, dispatched
        asynchronously; ``np.asarray`` of the result blocks and yields the
        (R, B) uint8 rows.  Every caller holds its bytes on the host, so
        the Pallas impl packs them into uint32 lane tiles there (a free
        view, rs_pallas.pack_lane_tiles) and the device program is exactly
        the kernel; the XLA impls are plain jitted functions, which move
        a numpy argument to the device themselves."""
        return _impl_fn(_rows_of(rows), self.impl)(inputs)

    def encode_device(self, data: np.ndarray):
        """(data_shards, B) uint8 host bytes -> async (parity_shards, B)."""
        return self.apply_rows_device(self.matrix[self.data_shards:], data)

    def _apply_blocking(self, rows: np.ndarray, data: np.ndarray) -> np.ndarray:
        """apply_rows_device + readback, spanned separately so a slow
        rebuild is attributable to transfer-in + compute vs transfer-out."""
        with trace.stage("ec.device_compute", impl=self.impl,
                         bytes=int(data.nbytes)):
            # dispatch is async: block here so transfer-in + compute land
            # in THIS span, not misattributed to the device_get transfer
            dev = self.apply_rows_device(rows, data).block_until_ready()
        with trace.stage("ec.device_get", impl=self.impl):
            return np.asarray(dev)

    def parity_of(self, data: np.ndarray) -> np.ndarray:
        """(data_shards, B) -> (parity_shards, B), the bulk-pipeline entry."""
        assert data.shape[0] == self.data_shards
        return self._apply_blocking(self.matrix[self.data_shards:], data)

    # -- numpy convenience (same shapes as rs_cpu) ------------------------

    def encode(self, shards: list[np.ndarray]) -> None:
        data = np.stack(shards[: self.data_shards])
        parity = self.parity_of(data)
        for i in range(self.parity_shards):
            shards[self.data_shards + i][:] = parity[i]

    def _reconstruct(self, shards, data_only: bool):
        if len(shards) != self.total_shards:
            raise ValueError(f"expected {self.total_shards} shard slots")
        present = [i for i, s in enumerate(shards) if s is not None]
        if len(present) == self.total_shards:
            return list(shards)
        if len(present) < self.data_shards:
            raise ValueError("too few shards to reconstruct")
        sub = present[: self.data_shards]
        stacked = np.stack([shards[i] for i in sub])
        out = list(shards)
        missing_data = [i for i in range(self.data_shards) if shards[i] is None]
        if missing_data:
            rows = gf256.decode_plan_for(
                self.matrix, self.data_shards, present, tuple(missing_data))
            for i, r in zip(missing_data, self._apply_blocking(rows, stacked)):
                out[i] = r
        if not data_only:
            missing_parity = [
                i for i in range(self.data_shards, self.total_shards)
                if shards[i] is None
            ]
            if missing_parity:
                data = np.stack(
                    [np.asarray(out[i]) for i in range(self.data_shards)])
                rows = self.matrix[np.asarray(missing_parity)]
                for i, p in zip(missing_parity,
                                self._apply_blocking(rows, data)):
                    out[i] = p
        return out

    def reconstruct(self, shards):
        return self._reconstruct(shards, data_only=False)

    def reconstruct_one(self, shards, shard_id: int) -> np.ndarray:
        """Decode ONLY shard_id from >= data_shards present shards: the one
        plan row a degraded read wants, dispatched from the caller's
        thread (a process that holds an accelerator sends the same row to
        its codec service instead: storage/ec/volume.py)."""
        if shards[shard_id] is not None:
            return np.asarray(shards[shard_id], dtype=np.uint8)
        present = [i for i, s in enumerate(shards) if s is not None]
        row = gf256.decode_plan_for(
            self.matrix, self.data_shards, present, (shard_id,))
        return self._apply_blocking(row, np.stack(
            [shards[i] for i in present[: self.data_shards]]))[0]

    def reconstruct_data(self, shards):
        return self._reconstruct(shards, data_only=True)

    def verify(self, shards: list[np.ndarray]) -> bool:
        parity = self.parity_of(np.stack(shards[: self.data_shards]))
        return all(
            np.array_equal(parity[i], shards[self.data_shards + i])
            for i in range(self.parity_shards)
        )
