"""Bytes the RS algorithm has to move for a job, whatever kernel serves it.

The codec service counts its input as width x rows (unpadded); a job reads
its input rows once and writes its output rows once, and nothing else is
needed: not the padded shapes, not the temporaries of an implementation.
"""

from __future__ import annotations


def needed_bytes(input_bytes: float, rows_in: int, rows_out: int) -> float:
    """HBM bytes for input of `input_bytes` (= rows_in x real columns)
    producing rows_out rows of the same columns."""
    if rows_in <= 0 or rows_out <= 0 or input_bytes < 0:
        raise ValueError("rows and bytes must be positive")
    return input_bytes * (rows_in + rows_out) / rows_in


def parity_bytes(input_bytes: float) -> float:
    """RS(10,4) encode: 10 rows in, 4 parity rows out."""
    return needed_bytes(input_bytes, 10, 4)


def rebuild_bytes(input_bytes: float, shards_rebuilt: int) -> float:
    """Rebuild: 10 survivor rows in, the lost rows out."""
    return needed_bytes(input_bytes, 10, shards_rebuilt)


def hbm_roofline_pct(bytes_needed: float, device_seconds: float,
                     hbm_bytes_per_s: float) -> float:
    """Least time the chip could take over the time it took, in percent.
    The bound is HBM bytes: v5e publishes no integer-VPU peak."""
    if device_seconds <= 0 or hbm_bytes_per_s <= 0:
        raise ValueError("time and peak must be positive")
    return 100.0 * (bytes_needed / hbm_bytes_per_s) / device_seconds
