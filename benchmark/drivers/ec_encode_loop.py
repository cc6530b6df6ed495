"""`ec.encode` of prepared sealed volumes, one after another.

No new rpc is started once `seconds` have passed; the last one runs to its
end.  The rate is bytes of the `.dat` files whose encode completed over the
time from the first rpc's start to the last one's end.

The outputs of a sample of the window's rpcs are kept for the check: the
first always and every `keep_every`-th after it, from an offset drawn from
the seed (so every seed keeps as many, give or take one).  The other EC
volumes are dropped (un-mount + delete, the operator's
rpcs) by a helper thread while the next rpc runs, so that their 1.4 bytes
per byte encoded leave the page cache before the kernel writes them back:
the host of a chip machine keeps every block once written.
"""

from __future__ import annotations

import threading
import time

from .. import check
from ..harness import say
from .ec_common import ALL_SHARDS, WarmVolumes


class Driver:
    def __init__(self, params: dict, run):
        self.p, self.run = params, run
        self.key = params.get("as", "encode")
        self.encoded: list = []   # kept for the check
        self.dropped = 0

    def prepare(self) -> None:
        self.vols = WarmVolumes(self.run, self.p["volumes"],
                                self.p["warmup_bytes"])

    def warm(self, cluster) -> None:
        t = time.monotonic()
        if not self.vols.encode(cluster, self.vols.warm_vid):
            raise RuntimeError("warm-up ec.encode did not spread shards")
        say(f"warm-up ec.encode of volume {self.vols.warm_vid}: "
            f"{time.monotonic() - t:.2f}s")

    def run_window(self, cluster, seconds: float) -> None:
        obs, work = self.run.obs, self.run.obs.work
        keep_every = self.p.get("keep_every", 1)
        keep_at = int(self.run.rng(self.key + "-keep").integers(keep_every))
        dropper = None
        t0 = t_end = time.monotonic()
        done = failed = 0
        for i, vid in enumerate(self.vols.vids):
            if time.monotonic() - t0 >= seconds:
                break
            obs.rpc(i, "start")
            try:
                ok = self.vols.encode(cluster, vid)
            except Exception as e:  # noqa: BLE001 — a failed rpc is counted
                say(f"ec.encode {vid} failed: {type(e).__name__}: {e}")
                ok = False
            t_end = time.monotonic()
            obs.rpc(i, "end")
            if dropper is not None:
                dropper.join()
                dropper = None
            if not ok:
                failed += 1
                continue
            done += 1
            if done == 1 or i % keep_every == keep_at:
                self.encoded.append(vid)
                self.run.fault.ec_files(self.vols.bases[vid], ALL_SHARDS)
            else:
                self.dropped += 1
                dropper = threading.Thread(
                    target=cluster.drop_shards,
                    args=(vid, self.vols.collection, ALL_SHARDS))
                dropper.start()
        else:
            if time.monotonic() - t0 < seconds:
                say(f"{self.key}: ran out of volumes after "
                    f"{time.monotonic() - t0:.1f}s of {seconds}s; the rate "
                    "is over the time used")
        if dropper is not None:
            dropper.join()
        obs.count(done + failed, failed)
        work[self.key + "_bytes"] = float(done * self.vols.dat_size)
        work[self.key + "_span_s"] = t_end - t0
        self.run.compare("ec_rpcs_failed", failed)
        say(f"{self.key}: {done} ec.encode rpcs of {self.vols.dat_size} "
            f"bytes in {t_end - t0:.3f}s, {failed} failed; "
            f"{len(self.encoded)} kept for the check, {self.dropped} dropped")

    def check_live(self, cluster) -> None:
        bad = self.vols.read_sample(self.run, cluster, self.encoded,
                                    self.p.get("needles_checked", 16))
        self.run.compare("ec_needles_differ", bad)

    def check_files(self) -> None:
        bases = [self.vols.bases[v] for v in self.encoded]
        rows = self.vols.rows(self.run, self.p.get("rows_checked", 12))
        self.run.compare("shard_bytes_differ",
            check.shard_bytes_differing(
                self.vols.ref_dat, bases, ALL_SHARDS, rows))
        self.run.compare("ecx_bytes_differ",
            check.ecx_bytes_differing(self.vols.ref_idx, bases))
