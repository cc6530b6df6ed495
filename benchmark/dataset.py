"""Seeded data, built offline as real needle volumes the server loads
through its normal start-up path.

The bytes of every needle are a function of the seed alone (`Needles`,
`SmallFiles`), so a read can be checked without trusting the store.  One
volume's `.dat`/`.idx` is written with the storage engine's own `Volume`
(neither the super block nor a `.vif` binds a volume to its id: the id is
in the file name), and further volumes of the same content are hard links
to it: no byte is written twice, and `VolumeDelete` after an encode only
drops one name.
"""

from __future__ import annotations

import os

import numpy as np

COOKIE = 0x5EED0C0D


def fid(vid: int, key: int, cookie: int = COOKIE) -> str:
    return f"{vid},{key:x}{cookie:08x}"


class Needles:
    """Needle i is a window of one seeded random pool with its index
    stamped in front; sizes log-uniform min..max (copied from
    chip_smoke.py, PR 23).  Keys are 1..n."""

    def __init__(self, seed: int, total_bytes: int,
                 min_size: int = 1 << 10, max_size: int = 4 << 20):
        rng = np.random.default_rng(seed)
        self.pool = rng.integers(
            0, 256, max_size + (8 << 20), dtype=np.uint8).tobytes()
        sizes, offs, acc = [], [], 0
        lo, hi = np.log(min_size), np.log(max_size)
        while acc < total_bytes:
            size = int(np.exp(rng.uniform(lo, hi)))
            sizes.append(size)
            offs.append(int(rng.integers(0, len(self.pool) - size)))
            acc += size
        self.sizes, self.offs, self.total = sizes, offs, acc

    def __len__(self) -> int:
        return len(self.sizes)

    def data(self, i: int) -> bytes:
        body = self.pool[self.offs[i]:self.offs[i] + self.sizes[i]]
        stamp = i.to_bytes(8, "little")
        return stamp[:len(body)] + body[8:]

    def sample(self, rng: np.random.Generator, n: int) -> list:
        """n needles drawn from `rng`, with the four largest among them."""
        ids = set(int(i) for i in rng.choice(
            len(self), size=min(n, len(self)), replace=False))
        ids.update(sorted(range(len(self)), key=lambda i: -self.sizes[i])[:4])
        return sorted(ids)


class SmallFiles:
    """Fixed-size files with a per-file payload: file n (any n >= 0) is a
    window of a seeded pool with n stamped in front."""

    def __init__(self, seed: int, size: int):
        self.size = size
        rng = np.random.default_rng(seed)
        self.pool = rng.integers(0, 256, (1 << 20) + size,
                                 dtype=np.uint8).tobytes()

    def data(self, n: int) -> bytes:
        off = (n * 1021) % (1 << 20)
        return n.to_bytes(8, "little") + self.pool[off + 8:off + self.size]


def _engine():
    from seaweedfs_tpu.storage.needle import Needle
    from seaweedfs_tpu.storage.volume import Volume

    return Volume, Needle


def build_volume(directory: str, collection: str, vid: int, payloads,
                 first_key: int = 1) -> str:
    """Write one volume through the storage engine; `payloads` yields the
    needles' bytes, keys count up from `first_key`.  -> its base path."""
    Volume, Needle = _engine()
    v = Volume(directory, collection, vid)
    try:
        for k, data in enumerate(payloads, start=first_key):
            v.append_needle(Needle(cookie=COOKIE, id=k, data=data))
    finally:
        v.close()
    return v.file_name()


def build_small_files_volume(job: tuple) -> str:
    """One preload volume, in a worker process: files lo..hi-1 of
    `SmallFiles(seed, size)` under keys lo+1..hi."""
    directory, collection, vid, seed, size, lo, hi = job
    files = SmallFiles(seed, size)
    return build_volume(directory, collection, vid,
                        (files.data(n) for n in range(lo, hi)),
                        first_key=lo + 1)


def clone_volume(base: str, directory: str, collection: str, vid: int) -> str:
    name = f"{collection}_{vid}" if collection else str(vid)
    out = os.path.join(directory, name)
    for ext in (".dat", ".idx"):
        os.link(base + ext, out + ext)
    return out


def sorted_index_bytes(idx_path: str) -> bytes:
    """What the `.ecx` of a volume must hold: the 16-byte entries of its
    `.idx` log (key 8, offset 4, size 4, big endian), the last entry of a
    key winning, in ascending key order.  Own parser, not the engine's."""
    with open(idx_path, "rb") as f:
        raw = np.frombuffer(f.read(), dtype=np.uint8)
    entries = raw[:len(raw) // 16 * 16].reshape(-1, 16)
    keys = entries[:, :8].copy().view(">u8").reshape(-1)
    last = {}
    for pos, k in enumerate(keys.tolist()):
        last[k] = pos
    order = [last[k] for k in sorted(last)]
    return entries[order].tobytes()
