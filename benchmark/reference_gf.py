"""Plain reference: table-driven GF(2^8) Reed-Solomon RS(10,4).

Independent of seaweedfs_tpu/ops: its own field tables (polynomial 0x11D),
its own generator matrix (the klauspost/Backblaze construction: Vandermonde
rows r^c, normalised so the top square is the identity) and a Gauss-Jordan
inverse for reconstruction.  numpy only; runs outside the window.
"""

from __future__ import annotations

import numpy as np

POLY = 0x11D
DATA_SHARDS = 10
PARITY_SHARDS = 4
TOTAL_SHARDS = DATA_SHARDS + PARITY_SHARDS


def _tables():
    exp = np.zeros(512, dtype=np.int32)
    log = np.zeros(256, dtype=np.int32)
    x = 1
    for i in range(255):
        exp[i] = x
        log[x] = i
        x <<= 1
        if x & 0x100:
            x ^= POLY
    exp[255:510] = exp[0:255]
    return exp, log


EXP, LOG = _tables()


def gf_mul(a: int, b: int) -> int:
    if a == 0 or b == 0:
        return 0
    return int(EXP[LOG[a] + LOG[b]])


def gf_inv(a: int) -> int:
    if a == 0:
        raise ZeroDivisionError("0 has no inverse in GF(2^8)")
    return int(EXP[255 - LOG[a]])


def gf_pow(a: int, n: int) -> int:
    if n == 0:
        return 1
    if a == 0:
        return 0
    return int(EXP[(LOG[a] * n) % 255])


def _mul_table() -> np.ndarray:
    """MUL[c] is the 256-entry lookup row of multiplication by c."""
    t = np.zeros((256, 256), dtype=np.uint8)
    for c in range(1, 256):
        idx = np.arange(1, 256)
        t[c, 1:] = EXP[LOG[c] + LOG[idx]]
    return t


MUL = _mul_table()


def mat_mul(a: list, b: list) -> list:
    return [[_dot(row, [b[k][j] for k in range(len(b))])
             for j in range(len(b[0]))] for row in a]


def _dot(u: list, v: list) -> int:
    acc = 0
    for x, y in zip(u, v):
        acc ^= gf_mul(x, y)
    return acc


def mat_inv(m: list) -> list:
    n = len(m)
    a = [list(row) + [int(i == j) for j in range(n)]
         for i, row in enumerate(m)]
    for col in range(n):
        piv = next((r for r in range(col, n) if a[r][col]), None)
        if piv is None:
            raise ValueError("singular matrix")
        a[col], a[piv] = a[piv], a[col]
        inv = gf_inv(a[col][col])
        a[col] = [gf_mul(x, inv) for x in a[col]]
        for r in range(n):
            if r != col and a[r][col]:
                f = a[r][col]
                a[r] = [x ^ gf_mul(f, y) for x, y in zip(a[r], a[col])]
    return [row[n:] for row in a]


def rs_matrix(data: int = DATA_SHARDS, total: int = TOTAL_SHARDS) -> list:
    """total x data generator matrix; its top `data` rows are the identity."""
    vm = [[gf_pow(r, c) for c in range(data)] for r in range(total)]
    return mat_mul(vm, mat_inv(vm[:data]))


MATRIX = rs_matrix()
PARITY_ROWS = MATRIX[DATA_SHARDS:]


def apply_rows(rows: list, shards: np.ndarray) -> np.ndarray:
    """rows (r x k coefficients) applied to shards (k, width) uint8."""
    out = np.zeros((len(rows), shards.shape[1]), dtype=np.uint8)
    for i, row in enumerate(rows):
        for k, c in enumerate(row):
            if c == 1:
                out[i] ^= shards[k]
            elif c:
                out[i] ^= MUL[c][shards[k]]
    return out


def parity_of(data: np.ndarray, parity_rows: "list | None" = None
              ) -> np.ndarray:
    """(10, width) data rows -> (4, width) parity rows."""
    return apply_rows(PARITY_ROWS if parity_rows is None else parity_rows,
                      data)


def reconstruct(shards: dict, width: int) -> np.ndarray:
    """{shard id: (width,) bytes} with >= 10 entries -> all 14, (14, width)."""
    have = sorted(shards)[:DATA_SHARDS]
    if len(have) < DATA_SHARDS:
        raise ValueError("fewer than 10 shards: unrecoverable")
    sub_inv = mat_inv([MATRIX[i] for i in have])
    stacked = np.stack([np.frombuffer(shards[i], dtype=np.uint8)
                        if not isinstance(shards[i], np.ndarray)
                        else shards[i] for i in have])
    assert stacked.shape[1] == width
    data = apply_rows(sub_inv, stacked)
    return np.concatenate([data, parity_of(data)])


# -- the volume's striping (ec_encoder.go: large rows, then small rows) ---------


def shard_layout(dat_size: int, large: int, small: int):
    """-> (large rows, small rows, bytes per shard file)."""
    n_large, remaining = 0, dat_size
    while remaining > large * DATA_SHARDS:
        n_large += 1
        remaining -= large * DATA_SHARDS
    n_small = -(-remaining // (small * DATA_SHARDS)) if remaining > 0 else 0
    return n_large, n_small, n_large * large + n_small * small


def stripe_row(dat, dat_size: int, row_start: int, block: int) -> np.ndarray:
    """The (10, block) data rows of one stripe row, zero padded past the
    end of the .dat.  `dat` is a binary file object."""
    out = np.zeros((DATA_SHARDS, block), dtype=np.uint8)
    for i in range(DATA_SHARDS):
        start = row_start + i * block
        if start >= dat_size:
            break
        dat.seek(start)
        chunk = dat.read(min(block, dat_size - start))
        out[i, :len(chunk)] = np.frombuffer(chunk, dtype=np.uint8)
    return out
