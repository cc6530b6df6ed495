"""Upstream `weed benchmark`: a closed loop of `clients` callers, a write
phase (assign + upload per file) then a random-read phase.

The shape is copied from seaweedfs_tpu/tools/benchmark.py (itself after
weed/command/benchmark.go); here it is seeded, timed by phase length and
not by file count, runs in the harness's process, gives every file its own
payload and checks every byte it reads.  The store is preloaded offline
with `preload_files` files whose ids and cookies come from the seed, so a
fid is known without an assign; only the window's own writes go through
`/dir/assign`.
"""

from __future__ import annotations

import json
import multiprocessing
import threading
import time
from concurrent.futures import ProcessPoolExecutor

import numpy as np

from ..dataset import SmallFiles, build_small_files_volume, fid
from ..harness import HttpConn, say


class Driver:
    def __init__(self, params: dict, run):
        self.p, self.run = params, run
        self.clients = run.config["clients"]
        self.size = run.config["file_bytes"]
        self.files = SmallFiles(run.seed, self.size)
        self.preload: list = []    # (fid, n)
        self.written: list = []    # (fid, n), acknowledged in the window
        self.next_n = 0
        self._n_lock = threading.Lock()

    def prepare(self) -> None:
        t = time.monotonic()
        cfg = self.run.config
        total, nvol = cfg["preload_files"], cfg["preload_volumes"]
        per = -(-total // nvol)
        jobs = []
        for j in range(nvol):
            vid = self.run.alloc_vid()
            lo, hi = j * per, min((j + 1) * per, total)
            jobs.append((self.run.dirs[j % len(self.run.dirs)],
                         cfg["files_collection"], vid, self.run.seed,
                         self.size, lo, hi))
            self.preload += [(fid(vid, n + 1), n) for n in range(lo, hi)]
        # ~100 us of Python per needle in the engine's append: one worker
        # process per volume, started fresh (no fork of a threaded parent)
        with ProcessPoolExecutor(
                max_workers=nvol,
                mp_context=multiprocessing.get_context("spawn")) as pool:
            list(pool.map(build_small_files_volume, jobs))
        self.next_n = total
        say(f"preloaded {total} files of {self.size} bytes into {nvol} "
            f"volumes offline ({nvol} processes) in "
            f"{time.monotonic() - t:.2f}s")

    def _take_n(self) -> int:
        with self._n_lock:
            n = self.next_n
            self.next_n += 1
        return n

    def _write_one(self, master, volume, out: list):
        """-> (assign seconds, whole write seconds); raises on failure."""
        n = self._take_n()
        body = self.run.fault.put_body(n, self.files.data(n))
        t0 = time.perf_counter()
        status, reply = master.request("GET", "/dir/assign")
        t1 = time.perf_counter()
        if status != 200:
            raise RuntimeError(f"assign: HTTP {status}")
        f = json.loads(reply)["fid"]
        status, reply = volume.request("POST", "/" + f, body)
        t2 = time.perf_counter()
        if status not in (200, 201):
            raise RuntimeError(f"POST {f}: HTTP {status}")
        self.run.fault.acked_put(volume, n, f)
        out.append((f, n))
        return t1 - t0, t2 - t0

    def _conns(self, cluster):
        return (HttpConn("127.0.0.1", cluster.mport),
                HttpConn("127.0.0.1", cluster.vport))

    def warm(self, cluster) -> None:
        master, volume = self._conns(cluster)
        try:
            for _ in range(self.p.get("warmup_writes", 64)):
                self._write_one(master, volume, self.preload)
            for f, n in self.preload[-8:] + self.preload[:8]:
                status, body = volume.request("GET", "/" + f)
                if status != 200 or body != self.files.data(n):
                    raise RuntimeError(f"warm-up GET {f}: HTTP {status}")
        finally:
            master.close()
            volume.close()

    def _phase(self, cluster, seconds: float, work) -> list:
        """Run `work(master, volume, rng, record)` in every client until
        `seconds` have passed; -> per-client results."""
        results = [None] * self.clients
        t_end = time.monotonic() + seconds

        def client(k: int) -> None:
            master, volume = self._conns(cluster)
            rng = np.random.default_rng([self.run.seed, k])
            state = {"lat": [], "aux": [], "failed": 0, "wrong": 0, "out": []}
            try:
                while time.monotonic() < t_end:
                    try:
                        work(master, volume, rng, state)
                    except Exception:  # noqa: BLE001 — counted as failed
                        state["failed"] += 1
                        master.close()
                        volume.close()
            finally:
                master.close()
                volume.close()
                results[k] = state

        threads = [threading.Thread(target=client, args=(k,))
                   for k in range(self.clients)]
        for th in threads:
            th.start()
        for th in threads:
            th.join()
        return results

    def run_window(self, cluster, seconds: float) -> None:
        obs = self.run.obs
        write_s = seconds * self.p.get("write_share", 0.5)

        def write(master, volume, rng, st):
            a, w = self._write_one(master, volume, st["out"])
            st["aux"].append(a * 1e3)
            st["lat"].append(w * 1e3)

        obs.prom_begin("write")
        t = time.monotonic()
        res = self._phase(cluster, write_s, write)
        obs.work["write_span_s"] = time.monotonic() - t
        obs.prom_end("write")
        obs.clock["put_ms"] = [x for r in res for x in r["lat"]]
        obs.clock["assign_ms"] = [x for r in res for x in r["aux"]]
        for r in res:
            self.written += r["out"]
        w_failed = sum(r["failed"] for r in res)

        known = self.preload + self.written

        def read(master, volume, rng, st):
            f, n = known[int(rng.integers(0, len(known)))]
            t0 = time.perf_counter()
            status, body = volume.request("GET", "/" + f)
            st["lat"].append((time.perf_counter() - t0) * 1e3)
            if status != 200 or body != self.files.data(n):
                st["wrong"] += 1

        obs.prom_begin("read")
        t = time.monotonic()
        res = self._phase(cluster, seconds - write_s, read)
        obs.work["read_span_s"] = time.monotonic() - t
        obs.prom_end("read")
        obs.clock["get_ms"] = [x for r in res for x in r["lat"]]
        r_failed = sum(r["failed"] for r in res)
        wrong = sum(r["wrong"] for r in res)
        n_put, n_get = len(obs.clock["put_ms"]), len(obs.clock["get_ms"])
        obs.count(n_put + w_failed + n_get + r_failed,
                  w_failed + r_failed + wrong)
        obs.work["puts"], obs.work["gets"] = float(n_put), float(n_get)
        self.run.compare("requests_failed", w_failed + r_failed)
        self.run.compare("gets_wrong", wrong)
        say(f"write phase: {n_put} files in {obs.work['write_span_s']:.2f}s "
            f"({w_failed} failed); read phase: {n_get} GETs in "
            f"{obs.work['read_span_s']:.2f}s ({r_failed} failed, "
            f"{wrong} wrong) over {len(known)} files")

    def check_live(self, cluster) -> None:
        """Every acknowledged PUT of the window is read back (a sample
        drawn from the seed where there are more than `readback_max`)."""
        todo = list(self.written)
        cap = self.p.get("readback_max", 16384)
        if len(todo) > cap:
            pick = self.run.rng("readback").choice(
                len(todo), size=cap, replace=False)
            todo = [todo[int(i)] for i in pick]
        chunks = [todo[k::self.clients] for k in range(self.clients)]
        bad = [0] * self.clients

        def reader(k: int) -> None:
            conn = HttpConn("127.0.0.1", cluster.vport)
            try:
                for f, n in chunks[k]:
                    try:
                        status, body = conn.request("GET", "/" + f)
                    except (OSError, EOFError):
                        status, body = 0, b""
                    if status != 200 or body != self.files.data(n):
                        bad[k] += 1
            finally:
                conn.close()

        threads = [threading.Thread(target=reader, args=(k,))
                   for k in range(self.clients)]
        for th in threads:
            th.start()
        for th in threads:
            th.join()
        self.run.compare("acked_puts_unreadable",
            sum(bad) if todo else None)
        say(f"read back {len(todo)} of {len(self.written)} acknowledged "
            f"PUTs: {sum(bad)} unreadable or wrong")

    def check_files(self) -> None:
        pass
