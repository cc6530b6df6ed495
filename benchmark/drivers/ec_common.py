"""What the two EC loops share: sealed `warm` volumes built from the seed
and the checks of what an EC rpc wrote."""

from __future__ import annotations

import os

from .. import check
from ..dataset import Needles, build_volume, clone_volume
from ..harness import HttpConn, say

ALL_SHARDS = list(range(14))


class WarmVolumes:
    """`count` sealed volumes of one seeded content (hard links) plus one
    small warm-up volume that walks the same codec-service width buckets:
    a volume of V bytes is ceil(V / 10 MiB) stripe rows, served 16 rows to
    a batch, so 23 rows (a full batch and a 7-row tail) compile what a
    1 GiB volume's 103 rows (six full batches and a 7-row tail) use."""

    def __init__(self, run, count: int, warmup_bytes: int,
                 ec_clones: int = 0):
        cfg = run.config
        self.collection = cfg["warm_collection"]
        self.needles = Needles(run.seed, cfg["volume_bytes"],
                               cfg["needle_min_bytes"],
                               cfg["needle_max_bytes"])
        first = run.alloc_vid()
        base = build_volume(run.dirs[0], self.collection, first,
                            (self.needles.data(i)
                             for i in range(len(self.needles))))
        self.bases = {first: base}
        for k in range(1, count):
            vid = run.alloc_vid()
            self.bases[vid] = clone_volume(
                base, run.dirs[k % len(run.dirs)], self.collection, vid)
        self.vids = list(self.bases)
        # ids for `clone_ec_volume`, taken before the warm-up volume's: the
        # master hands out ids above the highest it has seen at start-up,
        # so one of ours can never meet a volume it grows for an assign
        self.spare_vids = [run.alloc_vid() for _ in range(ec_clones)]
        # the source .dat is deleted by the encode: one more name keeps the
        # bytes for the reference without copying them
        self.ref_dat = os.path.join(run.ref_dir, f"warm_{first}.dat")
        self.ref_idx = os.path.join(run.ref_dir, f"warm_{first}.idx")
        os.link(base + ".dat", self.ref_dat)
        os.link(base + ".idx", self.ref_idx)
        self.dat_size = os.path.getsize(self.ref_dat)
        self.shard_size = check.ref.shard_layout(
            self.dat_size, check.LARGE_BLOCK, check.SMALL_BLOCK)[2]
        self.warm_vid = run.alloc_vid()
        small = Needles(run.seed + 1, warmup_bytes, cfg["needle_min_bytes"],
                        min(cfg["needle_max_bytes"], max(warmup_bytes // 8,
                                                         cfg["needle_min_bytes"] + 1)))
        self.warm_base = build_volume(
            run.dirs[-1], self.collection, self.warm_vid,
            (small.data(i) for i in range(len(small))))
        say(f"built {count} x {self.dat_size} byte sealed volumes "
            f"({len(self.needles)} needles each, one copy and "
            f"{count - 1} hard links) and a {os.path.getsize(self.warm_base + '.dat')} "
            f"byte warm-up volume over {len(run.dirs)} dirs")

    def clone_ec_volume(self, run, cluster, src_vid: int, k: int) -> int:
        """A further EC volume of the same content: hard links to the shard
        and index files `src_vid`'s encode wrote, under a new id, mounted
        by the server's own rpc.  An EC volume is bound to its id by its
        file names alone, and a rebuild replaces names, never bytes in
        place."""
        vid = self.spare_vids.pop(0)
        src = self.bases[src_vid]
        dst = os.path.join(run.dirs[k % len(run.dirs)],
                           f"{self.collection}_{vid}")
        for ext in [f".ec{i:02d}" for i in ALL_SHARDS] + [".ecx", ".vif"]:
            if os.path.exists(src + ext):
                os.link(src + ext, dst + ext)
        cluster.mount_shards(vid, self.collection, ALL_SHARDS)
        self.bases[vid] = dst
        self.vids.append(vid)
        return vid

    def encode(self, cluster, vid: int) -> bool:
        out = cluster.shell("ec_encode", [f"-volumeId={vid}"])
        return "spread" in out

    def rows(self, run, n: int) -> list:
        n_rows = self.shard_size // check.SMALL_BLOCK
        return check.pick_rows(run.rng("rows"), n_rows, n)

    def read_sample(self, run, cluster, vids: list, per_volume: int) -> int:
        conn = HttpConn("127.0.0.1", cluster.vport, timeout=120)
        rng = run.rng("needles")
        bad = 0
        try:
            for vid in vids:
                bad += check.needles_differing(
                    conn, vid, self.needles,
                    self.needles.sample(rng, per_volume))
        finally:
            conn.close()
        return bad

