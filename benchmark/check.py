"""The comparisons that decide `correct`, against the plain reference.

Every function returns counts of what differs; the limit of each is 0
(an exact comparison).  They read what the timed window itself produced:
shard and index files on disk, and needles over HTTP from the EC volume.
"""

from __future__ import annotations

import os

import numpy as np

from . import reference_gf as ref
from .dataset import fid, sorted_index_bytes
from .harness import say

LARGE_BLOCK = 1 << 30   # upstream erasure_coding: 1 GB large block rows
SMALL_BLOCK = 1 << 20   # and 1 MB small block rows


def shard_path(base: str, i: int) -> str:
    return f"{base}.ec{i:02d}"


def pick_rows(rng: np.random.Generator, n_rows: int, n: int) -> list:
    """`n` stripe rows drawn from `rng`, the last (zero-padded) one always
    among them."""
    rows = set(int(r) for r in rng.choice(
        n_rows, size=min(n, n_rows), replace=False))
    rows.add(n_rows - 1)
    return sorted(rows)


def shard_bytes_differing(dat_path: str, bases: list, shard_ids: list,
                          rows: list, parity_rows=None) -> int:
    """Bytes by which the shard files `base.ecNN` (NN in shard_ids) of
    every base differ from the reference's encoding of `dat_path`, over
    the sampled stripe `rows`, plus the bytes by which a file's length is
    off.  Every base holds the same `.dat` (hard links), so the reference
    encodes each sampled row once."""
    dat_size = os.path.getsize(dat_path)
    n_large, n_small, shard_size = ref.shard_layout(
        dat_size, LARGE_BLOCK, SMALL_BLOCK)
    if n_large:
        raise ValueError("large-block rows are not sampled here (> 10 GiB)")
    diff = 0
    files = {}
    for base in bases:
        for i in shard_ids:
            p = shard_path(base, i)
            if not os.path.exists(p):
                diff += shard_size
                continue
            diff += abs(os.path.getsize(p) - shard_size)
            files[(base, i)] = open(p, "rb")
    try:
        with open(dat_path, "rb") as dat:
            for r in rows:
                data = ref.stripe_row(
                    dat, dat_size, r * SMALL_BLOCK * ref.DATA_SHARDS,
                    SMALL_BLOCK)
                want = None
                if any(i >= ref.DATA_SHARDS for i in shard_ids):
                    want = ref.parity_of(data, parity_rows)
                for (base, i), f in files.items():
                    expect = (data[i] if i < ref.DATA_SHARDS
                              else want[i - ref.DATA_SHARDS])
                    f.seek(r * SMALL_BLOCK)
                    got = np.frombuffer(f.read(SMALL_BLOCK), dtype=np.uint8)
                    if len(got) != SMALL_BLOCK:
                        diff += SMALL_BLOCK - len(got)
                        got = np.concatenate(
                            [got, expect[len(got):]])
                    diff += int(np.count_nonzero(got != expect))
    finally:
        for f in files.values():
            f.close()
    return diff


def ecx_bytes_differing(idx_path: str, bases: list) -> int:
    want = sorted_index_bytes(idx_path)
    diff = 0
    for base in bases:
        try:
            with open(base + ".ecx", "rb") as f:
                got = f.read()
        except OSError:
            diff += len(want)
            continue
        a = np.frombuffer(got[:len(want)], dtype=np.uint8)
        b = np.frombuffer(want[:len(got)], dtype=np.uint8)
        diff += int(np.count_nonzero(a != b)) + abs(len(got) - len(want))
    return diff


def needles_differing(conn, vid: int, needles, ids: list) -> int:
    """Needles of volume `vid` whose GET does not return the seed's bytes."""
    bad = 0
    for i in ids:
        try:
            status, body = conn.request("GET", "/" + fid(vid, i + 1))
        except (OSError, EOFError):
            status, body = 0, b""
        if status != 200 or body != needles.data(i):
            if not bad:
                say(f"needle {fid(vid, i + 1)}: HTTP {status}, "
                    f"{len(body)} bytes for {len(needles.data(i))}: {body[:80]!r}")
            bad += 1
    return bad
