"""GETs over EC volumes that have lost shards: a closed loop of `clients`
readers, as upstream's `weed benchmark -c 16`, read phase only.

Set-up: one sealed volume is encoded through the server and `ec_volumes`
- 1 further EC volumes are hard links of its shard files (an EC volume is
bound to its id by its file names alone); every one then loses
`lost_shards` (un-mount + delete, the operator's rpcs, as
`ec_rebuild_loop` loses them) and nobody repairs them: the shards stay
lost for the window.  A few GETs over the needle sizes, checked, end the
set-up.  The window: every client draws a volume and a needle of it
uniformly and GETs it, for all of `seconds`; every body is compared with
the seed's bytes.

Beside the reads themselves the survivor FILES are checked against the
plain reference: over sampled stripe rows they equal the reference's
encoding of the kept `.dat` (the reads changed nothing), and the lost rows
the reference re-makes from them equal the `.dat`'s own (what any correct
decoder must have answered from those files).
"""

from __future__ import annotations

import threading
import time

import numpy as np

from .. import check
from ..dataset import fid
from ..harness import HttpConn, say
from .ec_common import ALL_SHARDS, WarmVolumes

DECODED = ("seaweedfs_ec_degraded_intervals_total", 'outcome="decoded"')


class Driver:
    def __init__(self, params: dict, run):
        self.p, self.run = params, run
        cfg = run.config
        self.clients = cfg["clients"]
        self.lost = list(cfg["lost_shards"])
        self.survivors = [i for i in ALL_SHARDS if i not in self.lost]
        self.n_volumes = cfg["ec_volumes"]

    def prepare(self) -> None:
        # one sealed volume: the others are clones of its EC files
        self.vols = WarmVolumes(self.run, 1, self.p["warmup_bytes"],
                                ec_clones=self.n_volumes - 1)

    def _get(self, conn, vid: int, i: int) -> bool:
        status, body = conn.request("GET", "/" + fid(vid, i + 1))
        return status == 200 and body == self.vols.needles.data(i)

    def warm(self, cluster) -> None:
        t = time.monotonic()
        first = self.vols.vids[0]
        if not self.vols.encode(cluster, first):
            raise RuntimeError(f"set-up ec.encode of {first} failed")
        for k in range(1, self.n_volumes):
            self.vols.clone_ec_volume(self.run, cluster, first, k)
        vids = self.vols.vids
        cluster.wait_shards({v: len(ALL_SHARDS) for v in vids})
        say(f"set-up: encoded volume {first}, cloned {len(vids) - 1} EC "
            f"volumes by hard link in {time.monotonic() - t:.2f}s")
        t = time.monotonic()
        for vid in vids:
            cluster.drop_shards(vid, self.vols.collection, self.lost)
        cluster.wait_shards({v: len(self.survivors) for v in vids})
        say(f"{len(vids)} volumes lost shards {self.lost} in "
            f"{time.monotonic() - t:.2f}s")
        t = time.monotonic()
        ids = self.vols.needles.sample(self.run.rng("warm-gets"),
                                       self.p.get("warmup_gets", 24))
        conn = HttpConn("127.0.0.1", cluster.vport, timeout=120)
        try:
            for n, i in enumerate(ids):
                if not self._get(conn, vids[n % len(vids)], i):
                    raise RuntimeError(f"warm-up GET of needle {i} failed")
        finally:
            conn.close()
        say(f"warm-up: {len(ids)} GETs in {time.monotonic() - t:.2f}s")

    def run_window(self, cluster, seconds: float) -> None:
        obs, vids = self.run.obs, self.vols.vids
        # a control's fault lands on the survivor files as the window opens
        self.run.fault.ec_files(self.vols.bases[vids[0]], self.survivors)
        n_needles = len(self.vols.needles)
        results = [None] * self.clients
        t_end = time.monotonic() + seconds

        def client(k: int) -> None:
            conn = HttpConn("127.0.0.1", cluster.vport)
            rng = np.random.default_rng([self.run.seed, k])
            st = {"lat": [], "failed": 0, "wrong": 0, "bytes": 0}
            try:
                while time.monotonic() < t_end:
                    vid = vids[int(rng.integers(len(vids)))]
                    i = int(rng.integers(n_needles))
                    t0 = time.perf_counter()
                    try:
                        status, body = conn.request(
                            "GET", "/" + fid(vid, i + 1))
                    except Exception:  # noqa: BLE001 — counted as failed
                        st["failed"] += 1
                        conn.close()
                        continue
                    st["lat"].append((time.perf_counter() - t0) * 1e3)
                    st["bytes"] += len(body)
                    if status != 200:
                        st["failed"] += 1
                    elif body != self.vols.needles.data(i):
                        st["wrong"] += 1
            finally:
                conn.close()
                results[k] = st

        obs.prom_begin("read")
        t = time.monotonic()
        threads = [threading.Thread(target=client, args=(k,))
                   for k in range(self.clients)]
        for th in threads:
            th.start()
        for th in threads:
            th.join()
        obs.work["read_span_s"] = time.monotonic() - t
        obs.prom_end("read")
        obs.clock["get_ms"] = [x for r in results for x in r["lat"]]
        failed = sum(r["failed"] for r in results)
        wrong = sum(r["wrong"] for r in results)
        n_get = len(obs.clock["get_ms"])
        obs.count(n_get + failed, failed + wrong)
        obs.work["gets"] = float(n_get)
        decoded = obs.delta("read", *DECODED) or 0
        self.run.compare("requests_failed", failed)
        self.run.compare("gets_wrong", wrong)
        # a window in which no lost interval was decoded read no lost shard
        self.run.compare("window_without_degraded_decodes",
                         0 if decoded > 0 else 1)
        needed = obs.delta("read", "seaweedfs_ec_degraded_gets_total") or 0
        lat = sorted(obs.clock["get_ms"]) or [0.0]
        say(f"get: {n_get} GETs of {sum(r['bytes'] for r in results)} bytes "
            f"in {obs.work['read_span_s']:.2f}s over {len(vids)} x "
            f"{n_needles} needles ({failed} failed, {wrong} wrong), median "
            f"{lat[len(lat) // 2]:.3f} ms, longest {lat[-1]:.3f} ms; "
            f"{needed:.0f} needed a lost interval, {decoded:.0f} intervals "
            f"decoded")

    def check_live(self, cluster) -> None:
        pass

    def check_files(self) -> None:
        bases = [self.vols.bases[v] for v in self.vols.vids]
        rows = self.vols.rows(self.run, self.p.get("rows_checked", 4))
        self.run.compare("survivor_bytes_differ",
            check.shard_bytes_differing(
                self.vols.ref_dat, bases, self.survivors, rows))
        self.run.compare("survivor_decode_differs",
                         self._decode_differing(bases, rows))

    def _decode_differing(self, bases: list, rows: list) -> int:
        """Bytes by which the lost rows, re-made by the plain reference
        from each base's survivor files, differ from the same rows of the
        kept `.dat`, over the sampled stripe `rows`."""
        ref, block = check.ref, check.SMALL_BLOCK
        diff = 0
        with open(self.vols.ref_dat, "rb") as dat:
            for r in rows:
                want = ref.stripe_row(dat, self.vols.dat_size,
                                      r * block * ref.DATA_SHARDS, block)
                for base in bases:
                    have = {}
                    for i in self.survivors:
                        try:
                            with open(check.shard_path(base, i), "rb") as f:
                                f.seek(r * block)
                                have[i] = np.frombuffer(
                                    f.read(block), dtype=np.uint8)
                        except OSError:
                            have[i] = np.empty(0, dtype=np.uint8)
                    if any(len(b) != block for b in have.values()):
                        diff += block * len(self.lost)
                        continue
                    made = ref.reconstruct(have, block)
                    for i in self.lost:
                        if i >= ref.DATA_SHARDS and len(want) == ref.DATA_SHARDS:
                            want = np.concatenate([want, ref.parity_of(want)])
                        diff += int(np.count_nonzero(made[i] != want[i]))
        return diff
