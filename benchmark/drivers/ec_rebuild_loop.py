"""`ec.rebuild` of four lost data shards, round-robin over the volumes.

Each round is the operator's `ec.rebuild`; the shards of the next volume
(of three or more, the one re-made longest ago) are removed (un-mount +
delete) half a second into the round, so the loss has
ridden the volume server's heartbeat to the master (a pulse of up to 1 s)
by the time the next command asks for the topology.  Were a command ever
to find nothing to do, the loop asks again: that time stays inside the
span.  The rate is bytes of the shard files re-made over the time from the
first command's start to the last one's end.
"""

from __future__ import annotations

import os
import re
import threading
import time

from .. import check
from ..harness import say
from .ec_common import WarmVolumes

_REBUILT = re.compile(r"ec\.rebuild (\d+): rebuilt \[([0-9, ]*)\]")


class Driver:
    def __init__(self, params: dict, run):
        self.p, self.run = params, run
        self.key = params.get("as", "rebuild")
        self.lost = list(params["lost_shards"])
        self.kept: list = []      # bases of rebuilt shard sets moved aside
        self.last_whole = None
        self.degraded: set = set()
        self.order: list = []     # volumes, the one re-made longest ago first
        self.keep_every = params.get("keep_every", 1)
        self.keep_at = int(run.rng("rebuild-keep").integers(self.keep_every))
        self.drops = 0
        self.remade: set = set()  # volumes whose lost shards a round re-made

    def prepare(self) -> None:
        # one sealed volume: the others are clones of its EC files (warm)
        self.vols = WarmVolumes(self.run, 1, self.p["warmup_bytes"],
                                ec_clones=self.p["volumes"] - 1)
        self.kept_dir = os.path.join(self.run.ref_dir, "kept")
        os.makedirs(self.kept_dir, exist_ok=True)

    def _drop(self, cluster, vid: int) -> None:
        """Lose the shards of `vid`.  Where a round of the window made them,
        every `keep_every`-th set is moved aside for the check first."""
        base = self.vols.bases.get(vid, self.vols.warm_base)
        self.drops += vid in self.remade
        if vid in self.remade and self.drops % self.keep_every == self.keep_at:
            aside = os.path.join(self.kept_dir,
                                 f"{len(self.kept)}_{os.path.basename(base)}")
            for i in self.lost:
                os.link(check.shard_path(base, i), check.shard_path(aside, i))
            self.kept.append(aside)
        cluster.drop_shards(vid, self.vols.collection, self.lost)
        self.degraded.add(vid)

    def warm(self, cluster) -> None:
        t = time.monotonic()
        first = self.vols.vids[0]
        for vid in (first, self.vols.warm_vid):
            if not self.vols.encode(cluster, vid):
                raise RuntimeError(f"set-up ec.encode of {vid} failed")
        for k in range(1, self.p["volumes"]):
            self.vols.clone_ec_volume(self.run, cluster, first, k)
        self.order = list(self.vols.vids)
        every = self.vols.vids + [self.vols.warm_vid]
        cluster.wait_shards({v: 14 for v in every})
        say(f"set-up: encoded 2 volumes, cloned {len(self.order) - 1} EC "
            f"volumes by hard link in {time.monotonic() - t:.2f}s")
        t = time.monotonic()
        left = 14 - len(self.lost)
        self._drop(cluster, self.vols.warm_vid)
        cluster.wait_shards({self.vols.warm_vid: left})
        out = cluster.shell("ec_rebuild", [])
        if not _REBUILT.search(out):
            raise RuntimeError(f"warm-up ec.rebuild did nothing: {out}")
        self.degraded.discard(self.vols.warm_vid)
        cluster.wait_shards({self.vols.warm_vid: 14})
        self._drop(cluster, self.vols.vids[0])
        cluster.wait_shards({self.vols.vids[0]: left})
        say(f"warm-up ec.rebuild and first loss: {time.monotonic() - t:.2f}s")

    def run_window(self, cluster, seconds: float) -> None:
        obs, work = self.run.obs, self.run.obs.work
        drop_after = self.p.get("next_loss_after_s", 0.5)
        t0 = t_end = time.monotonic()
        rounds = failed = idle = 0
        shards = 0
        while time.monotonic() - t0 < seconds:
            # the next to lose its shards is the volume re-made longest ago:
            # never the one the last round mounted, whose mount may not have
            # reached the master yet (a stale entry then names a whole
            # volume, and the command passes over it in milliseconds)
            whole = [v for v in self.order if v not in self.degraded]
            if not self.degraded:
                self._drop(cluster, whole[0])
                continue
            timer = None
            if len(self.degraded) == 1 and whole:
                timer = threading.Timer(drop_after, self._drop,
                                        (cluster, whole[0]))
                timer.start()
            obs.rpc(rounds, "start")
            try:
                out = cluster.shell("ec_rebuild", [])
            except Exception as e:  # noqa: BLE001 — a failed rpc is counted
                say(f"ec.rebuild failed: {type(e).__name__}: {e}")
                out, failed = "", failed + 1
            t_end = time.monotonic()
            made = [(int(v), [int(s) for s in ids.split(",") if s.strip()])
                    for v, ids in _REBUILT.findall(out)]
            made = [(v, ids) for v, ids in made if ids]
            obs.rpc(rounds, "end" if made else "idle")
            if timer is not None:
                timer.join()
            if not made:
                idle += 1
                time.sleep(0.02)
                continue
            rounds += 1
            for vid, ids in made:
                shards += len(ids)
                self.degraded.discard(vid)
                self.remade.add(vid)
                self.last_whole = vid
                self.order.remove(vid)
                self.order.append(vid)
                self.run.fault.ec_files(self.vols.bases[vid], ids)
        obs.count(rounds + failed, failed)
        work[self.key + "_bytes"] = float(shards * self.vols.shard_size)
        work[self.key + "_span_s"] = t_end - t0
        self.run.compare("ec_rpcs_failed", failed)
        say(f"{self.key}: {rounds} ec.rebuild rounds re-made {shards} shards "
            f"of {self.vols.shard_size} bytes in {t_end - t0:.3f}s; "
            f"{idle} commands found nothing to do, {failed} failed")

    def check_live(self, cluster) -> None:
        vids = [self.last_whole] if self.last_whole is not None else []
        bad = self.vols.read_sample(self.run, cluster, vids,
                                    self.p.get("needles_checked", 32))
        self.run.compare("ec_needles_differ",
            bad if vids else None)

    def check_files(self) -> None:
        # shards the window's rounds re-made: the sets moved aside when
        # their volume lost them again, and the last round's, still in place
        bases = list(self.kept)
        if self.last_whole is not None:
            bases.append(self.vols.bases[self.last_whole])
        rows = self.vols.rows(self.run, self.p.get("rows_checked", 24))
        self.run.compare("shard_bytes_differ",
            check.shard_bytes_differing(
                self.vols.ref_dat, bases, self.lost, rows)
            if bases else None)
