"""`ec.encode` of a FIXED batch of prepared sealed volumes, `in_flight`
rpcs at a time.

`in_flight` caller threads drain one queue of `volumes` ids in order: a
caller takes the next id the moment its rpc has returned.  The window ends
when the queue is empty and every rpc has returned.  `seconds` opens
nothing and closes nothing: the deployment is a batch (BASELINE.json
configs[3]), the work is fixed and the time is what is measured; exactly
`volumes` rpcs are attempted whatever `seconds` says.  The rate is bytes of
the `.dat` files whose encode completed over the time from the first rpc's
start to the last rpc's end — all the work over all the time, as
`ec_encode_loop` has it, so `metrics/encode_MBps.json` reads both.

The first volume's output and that of every `keep_every`-th after it (from
an offset drawn from the seed) are kept for the check.  Every other EC
volume is dropped (un-mount + delete, the operator's rpcs) on a helper
thread of its own the moment its rpc returns, so that its 1.4 bytes per
byte encoded never age in the page cache until the kernel writes them back
— unless the queue was empty by then: nothing more will be written, and the
drop waits until the last rpc has returned.  The drops are the harness's
housekeeping, not the deployment's work, and an un-mount + delete of 14
files holds the store's lock for a second or more: made at once they would
stand in the way of the last rpcs' mount and source delete, inside the
timed span.  In a batch of one round every drop is made after the window.

The warm-up encodes `in_flight` copies of one small volume at once: a
server that compiles the program of every `(V, width)` batch as soon as it
holds V encodes has met them all before the window opens.
"""

from __future__ import annotations

import threading
import time

from .. import check
from ..dataset import clone_volume
from ..harness import say
from .ec_common import ALL_SHARDS, WarmVolumes


class Driver:
    def __init__(self, params: dict, run):
        self.p, self.run = params, run
        self.key = params.get("as", "encode")
        self.in_flight = int(params["in_flight"])
        self.encoded: list = []   # kept for the check
        self.dropped = 0

    def prepare(self) -> None:
        run = self.run
        self.vols = WarmVolumes(run, self.p["volumes"], self.p["warmup_bytes"])
        # one small volume per caller for the concurrent warm-up wave: hard
        # links of the warm-up volume
        self.warm_vids = [self.vols.warm_vid]
        for k in range(self.in_flight - 1):
            vid = run.alloc_vid()
            clone_volume(self.vols.warm_base, run.dirs[k % len(run.dirs)],
                         self.vols.collection, vid)
            self.warm_vids.append(vid)

    def _encode(self, cluster, vid: int) -> bool:
        try:
            return self.vols.encode(cluster, vid)
        except Exception as e:  # noqa: BLE001 — a failed rpc is counted
            say(f"ec.encode {vid} failed: {type(e).__name__}: {e}")
            return False

    def _drop(self, cluster, vid: int) -> None:
        try:
            cluster.drop_shards(vid, self.vols.collection, ALL_SHARDS)
        except Exception as e:  # noqa: BLE001 — the rpc it follows counted
            say(f"drop of EC volume {vid} failed: {type(e).__name__}: {e}")

    def warm(self, cluster) -> None:
        cluster.env()  # built once, before any caller thread asks for it
        t = time.monotonic()
        ok: list = []
        threads = [threading.Thread(
            target=lambda v=v: ok.append(self._encode(cluster, v)))
            for v in self.warm_vids]
        for th in threads:
            th.start()
        for th in threads:
            th.join()
        if not all(ok) or len(ok) != len(self.warm_vids):
            raise RuntimeError("a warm-up ec.encode did not spread shards")
        for vid in self.warm_vids:
            self._drop(cluster, vid)
        say(f"warm-up: {len(self.warm_vids)} ec.encode at once: "
            f"{time.monotonic() - t:.2f}s")

    def run_window(self, cluster, seconds: float) -> None:
        obs, work = self.run.obs, self.run.obs.work
        vids = self.vols.vids
        keep_every = self.p.get("keep_every", 1)
        keep_at = int(self.run.rng(self.key + "-keep").integers(keep_every))
        lock = threading.Lock()
        state = {"next": 0, "done": 0, "failed": 0, "t_end": None}
        droppers: list = []
        all_returned = threading.Event()

        def drop(vid: int, in_the_tail: bool) -> None:
            if in_the_tail:
                all_returned.wait()
            self._drop(cluster, vid)

        def caller() -> None:
            while True:
                with lock:
                    i = state["next"]
                    if i >= len(vids):
                        return
                    state["next"] = i + 1
                vid = vids[i]
                obs.rpc(i, "start")
                ok = self._encode(cluster, vid)
                t = time.monotonic()
                obs.rpc(i, "end")
                keep = i == 0 or i % keep_every == keep_at
                with lock:
                    state["t_end"] = max(state["t_end"] or t, t)
                    state["done" if ok else "failed"] += 1
                    if ok and keep:
                        self.encoded.append(vid)
                    in_the_tail = state["next"] >= len(vids)
                if not ok:
                    continue
                if keep:
                    self.run.fault.ec_files(self.vols.bases[vid], ALL_SHARDS)
                else:
                    th = threading.Thread(target=drop,
                                          args=(vid, in_the_tail))
                    th.start()
                    with lock:
                        droppers.append(th)

        callers = [threading.Thread(target=caller, name=f"caller-{k}")
                   for k in range(min(self.in_flight, len(vids)))]
        t0 = time.monotonic()
        try:
            for th in callers:
                th.start()
            for th in callers:
                th.join()
        finally:
            all_returned.set()
        for th in droppers:
            th.join()
        done, failed = state["done"], state["failed"]
        span = (state["t_end"] or t0) - t0
        self.dropped = len(droppers)
        self.encoded.sort()
        obs.count(done + failed, failed)
        work[self.key + "_bytes"] = float(done * self.vols.dat_size)
        work[self.key + "_span_s"] = span
        self.run.compare("ec_rpcs_failed", failed)
        say(f"{self.key}: a batch of {len(vids)} ec.encode rpcs of "
            f"{self.vols.dat_size} bytes, {len(callers)} in flight: {done} "
            f"done in {span:.3f}s ({seconds:g}s asked, not used), {failed} "
            f"failed; {len(self.encoded)} kept for the check, "
            f"{self.dropped} dropped")

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
