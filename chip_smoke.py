#!/usr/bin/env python3
"""The quickest proof that today's program still starts on the chip.

Run with no arguments (one TPU chip): a real `server -ec.codec=tpu`
process takes an uploaded volume through ec.encode, degraded reads and
ec.rebuild, then a library child drives the three device codecs; every
byte is compared with the host reference codec (ops/rs_cpu.py).  With
`--chips 4` it runs ONLY the mesh paths (sharded batch encode, psum
decode, the file-level batch flows) and their comparison.

Contract (the driver reads nothing but the LAST line of stdout):

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}

exactly those keys, one line, nothing after it; exit 0 only with
``ok: true``, and ``ok`` is true only on platform ``tpu``.  Everything
else worth reading is on earlier lines and in the logs under
``chiprun_out/chip_smoke/``.

One process per chip: THIS process never imports jax.  The device is
touched by one child at a time — a probe child that exits, then the
server, then (after the server is gone) the library child.
"""

from __future__ import annotations

import argparse
import bisect
import http.client
import itertools
import json
import os
import shutil
import socket
import subprocess
import sys
import threading
import time
import urllib.request

HERE = os.path.dirname(os.path.abspath(__file__))
LOG_DIR = os.path.join(HERE, "chiprun_out", "chip_smoke")
WORK_DIR = os.path.join(HERE, ".chip_smoke_work")
# the driver allows 1200s; leave room to reap children and print the line
DEADLINE_S = 1100.0
NO_DEVICE = {"platform": "", "kind": "", "count": 0}

_T0 = time.monotonic()
_CHILDREN: list[subprocess.Popen] = []
_FINISH_LOCK = threading.Lock()


# -- the last line ---------------------------------------------------------


def final_line(ok: bool, platform: str, kind: str, count: int) -> str:
    """The one line the driver parses: two keys, three keys, no more."""
    return json.dumps({"ok": bool(ok), "device": {
        "platform": str(platform), "kind": str(kind), "count": int(count)}})


def finish(ok: bool, device: dict) -> None:
    """Reap every child, print the last line, leave.  Nothing can follow
    the line: flush, then ``os._exit`` (no atexit, no thread teardown).
    ``ok`` survives only on a TPU — there is no switch around that."""
    with _FINISH_LOCK:  # the watchdog and the main thread cannot both print
        reap_children()
        shutil.rmtree(WORK_DIR, ignore_errors=True)
        ok = bool(ok) and device.get("platform") == "tpu"
        sys.stdout.write(final_line(
            ok, device.get("platform", ""), device.get("kind", ""),
            device.get("count", 0)) + "\n")
        sys.stdout.flush()
        os._exit(0 if ok else 1)


def say(msg: str) -> None:
    print(f"[chip_smoke +{time.monotonic() - _T0:6.1f}s] {msg}", flush=True)


class SmokeFailure(Exception):
    pass


def check(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


# -- children ----------------------------------------------------------------


def child_env() -> dict:
    env = dict(os.environ)
    env["PYTHONPATH"] = HERE + os.pathsep + env.get("PYTHONPATH", "")
    env.setdefault("TPU_LOG_DIR", "disabled")
    return env


def spawn(argv: list[str], log_name: str, stdout=None,
          env_extra: "dict | None" = None) -> subprocess.Popen:
    """Start a child whose stderr (and stdout, unless piped to us) go to a
    log file — never inherited, so no child can write after our last line."""
    os.makedirs(LOG_DIR, exist_ok=True)
    log = open(os.path.join(LOG_DIR, log_name), "ab")
    proc = subprocess.Popen(
        argv, cwd=HERE, env={**child_env(), **(env_extra or {})},
        stdin=subprocess.DEVNULL,
        stdout=log if stdout is None else stdout, stderr=log)
    log.close()
    _CHILDREN.append(proc)
    return proc


def reap_children() -> None:
    for proc in _CHILDREN:
        if proc.poll() is None:
            proc.terminate()
    for proc in _CHILDREN:
        try:
            proc.wait(timeout=20)
        except subprocess.TimeoutExpired:
            proc.kill()
            proc.wait()
    _CHILDREN.clear()


def log_tail(log_name: str, n: int = 15) -> str:
    try:
        with open(os.path.join(LOG_DIR, log_name), "rb") as f:
            lines = f.read().decode("utf-8", "replace").splitlines()
        return "\n".join("    | " + ln for ln in lines[-n:])
    except OSError:
        return "    | (no log)"


def run_child(mode: str, extra: list[str], log_name: str) -> "dict | None":
    """Run `chip_smoke.py --child MODE`, relay its stdout lines as ours,
    -> the dict after its ``RESULT `` line (None when it died)."""
    proc = spawn([sys.executable, os.path.abspath(__file__), "--child", mode,
                  *extra], log_name, stdout=subprocess.PIPE)
    result = None
    for raw in proc.stdout:
        line = raw.decode("utf-8", "replace").rstrip("\n")
        if line.startswith("RESULT "):
            result = json.loads(line[len("RESULT "):])
        else:
            say(f"  {mode}: {line}")
    rc = proc.wait()
    if rc != 0:
        say(f"{mode} child exited {rc}; its stderr ends:\n"
            + log_tail(log_name))
        return None
    return result


def watchdog(device: dict) -> None:
    """`device` is the dict `run` fills in once the probe child answered."""
    time.sleep(DEADLINE_S)
    say(f"FAIL: not done after {DEADLINE_S:.0f}s; giving up")
    finish(False, device)


# -- HTTP helpers (stdlib only) --------------------------------------------


def http_get(url: str, timeout: float = 60.0) -> bytes:
    with urllib.request.urlopen(url, timeout=timeout) as r:
        return r.read()


def http_json(url: str, timeout: float = 60.0) -> dict:
    return json.loads(http_get(url, timeout))


def free_port_pair() -> int:
    """A port p with p and p+10000 (the gRPC twin) both free."""
    for _ in range(200):
        with socket.socket() as s:
            s.bind(("127.0.0.1", 0))
            p = s.getsockname()[1]
        if p + 10000 > 65000:
            p -= 20000
        if p < 1024:
            continue
        try:
            for q in (p, p + 10000):
                with socket.socket() as s:
                    s.bind(("127.0.0.1", q))
            return p
        except OSError:
            continue
    raise SmokeFailure("no free port pair")


def parse_metrics(text: str) -> dict:
    """Prometheus text -> {'name{labels}': float}."""
    out = {}
    for line in text.splitlines():
        if not line or line[0] == "#":
            continue
        key, _, val = line.rpartition(" ")
        try:
            out[key] = float(val)
        except ValueError:
            pass
    return out


def metric_delta(before: dict, after: dict, name: str, *label_bits) -> float:
    """Sum of (after - before) over series of `name` whose label text holds
    every one of `label_bits`."""
    total = 0.0
    for key, val in after.items():
        base = key.split("{", 1)[0]
        if base != name or not all(b in key for b in label_bits):
            continue
        total += val - before.get(key, 0.0)
    return total


# -- seeded needles ----------------------------------------------------------


class Needles:
    """Seeded payload: needle i is a window of one seeded random pool with
    its index stamped in front, sizes log-uniform 1 KiB..4 MiB.  Expected
    bytes are recomputed from the seed, never read back from the store."""

    def __init__(self, seed: int, total_bytes: int,
                 min_size: int = 1 << 10, max_size: int = 4 << 20):
        import numpy as np

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


def pooled(fn, items, workers: int = 8) -> list:
    from concurrent.futures import ThreadPoolExecutor

    with ThreadPoolExecutor(max_workers=workers) as ex:
        return list(ex.map(fn, items))


# -- served phase --------------------------------------------------------------


class Cluster:
    """One `server` process (master + volume server) and the handles the
    phase needs on it."""

    def __init__(self, codec: str, tag: str):
        self.codec = codec
        self.data_dir = os.path.join(WORK_DIR, f"{tag}-data")
        os.makedirs(self.data_dir, exist_ok=True)
        self.mport = free_port_pair()
        self.vport = free_port_pair()
        self.log_name = f"{tag}-server.log"
        # the master would heal a lost shard by itself within seconds
        # (mass repair); switched off so the operator's `ec.rebuild` below
        # is the one repairer and the rpc under test is the one that runs
        self.proc = spawn(
            [sys.executable, "-m", "seaweedfs_tpu", "server",
             "-dir", self.data_dir, "-ip", "127.0.0.1",
             "-masterPort", str(self.mport), "-port", str(self.vport),
             "-ec.codec", codec], self.log_name,
            env_extra={"SEAWEEDFS_TPU_MASS_REPAIR": "0"})
        self.shell_runs = 0

    @property
    def master(self) -> str:
        return f"127.0.0.1:{self.mport}"

    @property
    def volume(self) -> str:
        return f"127.0.0.1:{self.vport}"

    def alive(self) -> None:
        check(self.proc.poll() is None,
              f"server exited {self.proc.returncode}; its log ends:\n"
              + log_tail(self.log_name, 25))

    def wait_ready(self) -> dict:
        """-> the volume server's /status once it answers and the master
        can assign.  No deadline of our own shorter than the run's: a cold
        TPU initialisation takes as long as it takes."""
        status = None
        while True:
            self.alive()
            try:
                if status is None:
                    status = http_json(f"http://{self.volume}/status", 5)
                http_json(f"http://{self.master}/dir/assign", 5)["fid"]
                return status
            except Exception:  # noqa: BLE001 — not up yet
                time.sleep(0.5)

    def status(self) -> dict:
        return http_json(f"http://{self.volume}/status")

    def metrics(self) -> dict:
        return parse_metrics(
            http_get(f"http://{self.volume}/metrics").decode())

    def shell(self, command: str) -> str:
        """`python -m seaweedfs_tpu shell -c ...`, the operator's entry.
        Run under -X importtime so its stderr log PROVES it never imported
        jax (a second backend while the server holds the chip)."""
        self.shell_runs += 1
        log_name = f"shell-{self.shell_runs}.err"
        out_path = os.path.join(LOG_DIR, f"shell-{self.shell_runs}.out")
        with open(out_path, "wb") as out:
            proc = spawn(
                [sys.executable, "-X", "importtime", "-m", "seaweedfs_tpu",
                 "shell", "-master", self.master, "-c", command],
                log_name, stdout=out)
            rc = proc.wait()
        with open(out_path, "rb") as f:
            text = f.read().decode("utf-8", "replace").strip()
        check(rc == 0, f"shell `{command}` exited {rc}: {text[-300:]}\n"
              + log_tail(log_name, 8))
        with open(os.path.join(LOG_DIR, log_name), "rb") as f:
            imports = f.read().decode("utf-8", "replace")
        jax_imports = [ln for ln in imports.splitlines()
                       if ln.startswith("import time:")
                       and ln.rsplit("|", 1)[-1].strip().split(".")[0]
                       in ("jax", "jaxlib")]
        check(not jax_imports,
              f"shell `{command}` imported jax: {jax_imports[:3]}")
        say(f"shell `{command}` -> {text.splitlines()[-1] if text else ''}"
            "  [no jax import in its -X importtime log]")
        return text

    def stop(self) -> None:
        if self.proc.poll() is None:
            self.proc.terminate()
            try:
                self.proc.wait(timeout=30)
            except subprocess.TimeoutExpired:
                self.proc.kill()
                self.proc.wait()


def device_work_report(before: dict, after: dict, rpc: str, kind: str,
                       status: dict) -> None:
    """Who did the GF work of one EC rpc, from the server's own counters."""
    def d(name: str, *bits) -> float:
        return metric_delta(before, after, name, *bits)

    host_ops = d("seaweedfs_ec_op_seconds_count", 'impl="cpu"')
    svc_ok = d("seaweedfs_ec_service_jobs_total", f'kind="{kind}"',
               'result="ok"')
    svc_err = d("seaweedfs_ec_service_jobs_total", 'result="error"')
    readback = d("seaweedfs_ec_service_stage_seconds_count",
                 'stage="readback"')
    compute_s = d("seaweedfs_ec_service_stage_seconds_sum",
                  'stage="compute"')
    readback_s = d("seaweedfs_ec_service_stage_seconds_sum",
                   'stage="readback"')
    svc_bytes = d("seaweedfs_ec_service_batch_bytes_sum")
    direct = d("seaweedfs_ec_pipeline_stage_seconds_count", 'stage="decode"')
    platform = status["ec"]["device"]["platform"]
    say(f"{rpc}: host-codec ops={host_ops:.0f}  service jobs ok={svc_ok:.0f}"
        f" err={svc_err:.0f}  device readbacks={readback:.0f}"
        f"  service input bytes={svc_bytes:.0f}"
        f"  service compute+readback s={compute_s + readback_s:.2f}"
        f"  pipeline decode stages={direct:.0f}")
    check(host_ops == 0, f"{rpc}: {host_ops:.0f} ops ran on the HOST codec")
    check(svc_err == 0, f"{rpc}: {svc_err:.0f} codec-service jobs failed")
    if platform == "cpu":
        # the CPU rehearsal: device codecs keep their direct dispatch
        check(direct > 0, f"{rpc}: no pipeline decode stage was recorded")
        say(f"{rpc}: implementation = direct dispatch of codec "
            f"{status['ec']['codec']} on the {platform} backend")
        return
    check(svc_ok > 0 and readback > 0 and svc_bytes > 0,
          f"{rpc}: the device-mode codec service did no work")
    say(f"{rpc}: implementation = codec service, device mode (XOR network "
        f"on uint32 lane tiles over the mesh; NOT the Pallas kernel) on "
        f"{platform}")


def compare_files(a: str, b: str) -> bool:
    if os.path.getsize(a) != os.path.getsize(b):
        return False
    with open(a, "rb") as fa, open(b, "rb") as fb:
        while True:
            ca, cb = fa.read(8 << 20), fb.read(8 << 20)
            if ca != cb:
                return False
            if not ca:
                return True


def served_phase(codec: str, payload_bytes: int, floor_bytes: int,
                 seed: int, expect: dict) -> None:
    from seaweedfs_tpu.native import lib as native
    from seaweedfs_tpu.pb import rpc as rpclib
    from seaweedfs_tpu.pb import volume_server_pb2 as vs
    from seaweedfs_tpu.shell.commands import CommandEnv
    from seaweedfs_tpu.storage.ec.constants import TOTAL_SHARDS, to_ext
    from seaweedfs_tpu.storage.ec.encoder import write_ec_files
    from seaweedfs_tpu.storage.ec.shard_bits import ShardBits

    free = shutil.disk_usage(WORK_DIR).free
    if free < 4.5 * payload_bytes:
        say(f"only {free >> 20} MiB free under {WORK_DIR}: payload cut to "
            f"the {floor_bytes >> 20} MiB floor")
        payload_bytes = floor_bytes
    cluster = Cluster(codec, "served")
    try:
        t = time.monotonic()
        status = cluster.wait_ready()
        say(f"server up in {time.monotonic() - t:.1f}s: /status ec = "
            f"{json.dumps(status['ec'])}")
        held = status["ec"].get("device")
        check(held is not None, "server reports no held device")
        check({k: held[k] for k in ("platform", "kind", "count")} == expect,
              f"server holds {held}, the probe child saw {expect}")

        # -- upload one volume of seeded needles ---------------------------
        needles = Needles(seed, payload_bytes)
        a = http_json(
            f"http://{cluster.master}/dir/assign?count={len(needles)}")
        vid, key_cookie = a["fid"].split(",")
        key0, cookie = int(key_cookie[:-8], 16), key_cookie[-8:]
        fids = [f"{vid},{key0 + i:x}{cookie}" for i in range(len(needles))]
        vid = int(vid)
        local = threading.local()

        def conn():
            if getattr(local, "c", None) is None:
                local.c = http.client.HTTPConnection(
                    "127.0.0.1", cluster.vport, timeout=120)
            return local.c

        def request(method: str, fid: str, body=None) -> bytes:
            for attempt in (0, 1):  # one retry on a dropped keep-alive
                try:
                    c = conn()
                    c.request(method, "/" + fid, body=body, headers={
                        "Content-Type": "application/octet-stream"}
                        if body is not None else {})
                    r = c.getresponse()
                    data = r.read()
                    check(r.status in (200, 201),
                          f"{method} {fid}: HTTP {r.status} {data[:200]!r}")
                    return data
                except (OSError, http.client.HTTPException) as e:
                    local.c = None
                    if attempt:
                        raise SmokeFailure(f"{method} {fid}: {e}") from e
            raise AssertionError

        def upload(ids) -> None:
            pooled(lambda i: request("POST", fids[i], needles.data(i)), ids)

        t = time.monotonic()
        # up to the floor first, so a slow host can stop there
        running = list(itertools.accumulate(needles.sizes))
        uploaded = 1 + bisect.bisect_left(
            running, min(floor_bytes, needles.total))
        upload(range(uploaded))
        rate = running[uploaded - 1] / (time.monotonic() - t)
        rest = needles.total - running[uploaded - 1]
        if rest / rate > 240:
            say(f"upload runs at {rate / 1e6:.0f} MB/s: the remaining "
                f"{rest >> 20} MiB would not fit the time limit")
        else:
            upload(range(uploaded, len(needles)))
            uploaded = len(needles)
        total = running[uploaded - 1]
        say(f"uploaded {uploaded} needles, {total} bytes "
            f"({total / (1 << 30):.2f} GiB; a cut from the upstream 30 GB "
            f"default volume) into volume {vid} in "
            f"{time.monotonic() - t:.1f}s over HTTP")
        check(total >= min(floor_bytes, payload_bytes),
              "payload under the floor")
        sample = sorted(set(
            list(range(0, uploaded, max(1, uploaded // 48)))
            + sorted(range(uploaded), key=lambda i: -needles.sizes[i])[:4]))

        def verify(ids, what: str) -> None:
            t = time.monotonic()
            bad = [i for i, ok in zip(ids, pooled(
                lambda i: request("GET", fids[i]) == needles.data(i), ids))
                if not ok]
            check(not bad, f"{what}: needles {bad[:5]} differ from upload")
            say(f"{what}: {len(ids)} needles byte-identical to the upload "
                f"({time.monotonic() - t:.1f}s)")

        # the encoder deletes the source .dat; a hard link keeps the bytes
        # for the host reference without copying them
        dat = os.path.join(cluster.data_dir, f"{vid}.dat")
        ref_dir = os.path.join(WORK_DIR, "served-ref")
        os.makedirs(ref_dir, exist_ok=True)
        ref_base = os.path.join(ref_dir, str(vid))
        os.link(dat, ref_base + ".dat")

        # -- ec.encode -------------------------------------------------------
        before = cluster.metrics()
        t = time.monotonic()
        cluster.shell(f"ec.encode -volumeId={vid}")
        say(f"ec.encode (rpc VolumeEcShardsGenerate) took "
            f"{time.monotonic() - t:.1f}s for a "
            f"{os.path.getsize(ref_base + '.dat')} byte .dat")
        device_work_report(before, cluster.metrics(),
                           "VolumeEcShardsGenerate", "parity", status)
        check(os.path.getsize(ref_base + ".dat") >= total,
              ".dat smaller than the payload")
        verify(sample, "after ec.encode")

        t = time.monotonic()
        write_ec_files(ref_base, codec_name="cpu")
        check("jax" not in sys.modules, "the reference encode imported jax")
        say(f"host reference: ops/rs_cpu.py via the "
            f"{'native SIMD' if native.available() else 'numpy'} kernel "
            f"encoded the same .dat in {time.monotonic() - t:.1f}s")
        def shard(i: int) -> str:
            return os.path.join(cluster.data_dir, f"{vid}{to_ext(i)}")

        differ = [i for i in range(TOTAL_SHARDS)
                  if not compare_files(shard(i), ref_base + to_ext(i))]
        check(not differ, f"shards {differ} differ from the host reference")
        say(f"all {TOTAL_SHARDS} shard files byte-identical to the host "
            f"reference ({os.path.getsize(shard(0))} bytes each)")

        # -- lose shards, read degraded, ec.rebuild --------------------------
        env = CommandEnv(f"127.0.0.1:{cluster.mport + 10000}")
        vstub = rpclib.volume_server_stub(
            f"127.0.0.1:{cluster.vport + 10000}", timeout=60)

        def wait_shards(n: int) -> None:
            while True:
                cluster.alive()
                have = ShardBits(0)
                for dc in env.topology().data_center_infos:
                    for rack in dc.rack_infos:
                        for dn in rack.data_node_infos:
                            for disk in dn.disk_infos.values():
                                for e in disk.ec_shard_infos:
                                    if e.id == vid:
                                        have = have.plus(
                                            ShardBits(e.ec_index_bits))
                if have.count() == n:
                    return
                time.sleep(0.3)

        wait_shards(TOTAL_SHARDS)
        for lost in ([3], [0, 2, 5, 9]):
            vstub.VolumeEcShardsUnmount(vs.VolumeEcShardsUnmountRequest(
                volume_id=vid, shard_ids=lost))
            vstub.VolumeEcShardsDelete(vs.VolumeEcShardsDeleteRequest(
                volume_id=vid, collection="", shard_ids=lost))
            check(not any(os.path.exists(shard(i)) for i in lost),
                  f"shards {lost} still on disk")
            wait_shards(TOTAL_SHARDS - len(lost))
            before = cluster.metrics()
            verify(sample, f"degraded, shards {lost} lost")
            decodes = metric_delta(
                before, cluster.metrics(), "seaweedfs_ec_singleflight_total",
                'result="leader"')
            say(f"degraded reads reconstructed {decodes:.0f} intervals "
                "(per-needle decode is the host codec by design)")
            before = cluster.metrics()
            t = time.monotonic()
            cluster.shell("ec.rebuild")
            say(f"ec.rebuild (rpc VolumeEcShardsRebuild) of shards {lost} "
                f"took {time.monotonic() - t:.1f}s")
            device_work_report(before, cluster.metrics(),
                               "VolumeEcShardsRebuild", "apply", status)
            differ = [i for i in lost
                      if not compare_files(shard(i), ref_base + to_ext(i))]
            check(not differ, f"rebuilt shards {differ} differ from the "
                  "deleted ones")
            say(f"rebuilt shards {lost} byte-identical to the deleted ones")
            wait_shards(TOTAL_SHARDS)
            verify(sample, f"after ec.rebuild of {lost}")
        verify(list(range(uploaded)), "final pass over EVERY needle")
        end = cluster.status()["ec"]
        say(f"server at exit: /status ec = {json.dumps(end)}")
        check("jax" not in sys.modules, "the smoke's parent imported jax")
    finally:
        cluster.stop()
        shutil.rmtree(cluster.data_dir, ignore_errors=True)
        shutil.rmtree(os.path.join(WORK_DIR, "served-ref"),
                      ignore_errors=True)


# -- the run -------------------------------------------------------------------


def run(codec: str = "tpu", payload_bytes: int = 2 << 30,
        floor_bytes: int = 1 << 30, lib_shard_bytes: int = 64 << 20,
        chips: int = 1, seed: int = 23, gate: bool = True,
        phases: tuple = ("served", "library")) -> None:
    """The whole smoke; never returns (ends in ``finish``).

    The arguments exist for the CPU rehearsal in tests/test_chip_smoke.py
    (tiny sizes, an XLA codec, ``gate=False`` to walk the phases on a CPU
    backend).  None of them can make the last line say ``ok: true``
    off-TPU: ``finish`` decides that from the device alone."""
    device = dict(NO_DEVICE)
    threading.Thread(target=watchdog, args=(device,), daemon=True).start()
    shutil.rmtree(WORK_DIR, ignore_errors=True)
    shutil.rmtree(LOG_DIR, ignore_errors=True)
    os.makedirs(WORK_DIR, exist_ok=True)
    os.makedirs(LOG_DIR, exist_ok=True)

    # first: what device is there?  A short-lived child asks jax and EXITS
    # before anything else starts; this process never touches a backend.
    probed = run_child("device", [], "device-child.err")
    if probed is None:
        say("FAIL: the device child could not initialise a jax backend")
        finish(False, device)
    device.update(probed)
    say(f"device: {json.dumps(device)}")
    if gate and (device["platform"] != "tpu" or device["count"] != chips):
        say(f"FAIL: need {chips} tpu chip(s), found {device['count']} "
            f"{device['platform'] or 'no'} device(s); nothing was started")
        finish(False, device)
    import importlib.util

    if importlib.util.find_spec("seaweedfs_tpu") is None:
        say("FAIL: the seaweedfs_tpu package is not next to this script")
        finish(False, device)

    ok = True
    if chips == 4:
        phases = ("mesh",)
    for phase in phases:
        t = time.monotonic()
        try:
            if phase == "served":
                served_phase(codec, payload_bytes, floor_bytes, seed, device)
            else:
                extra = (["--shard-bytes", str(lib_shard_bytes)]
                         if phase == "library" else [])
                result = run_child(phase, extra + ["--seed", str(seed)],
                                   f"{phase}-child.err")
                check(result is not None and result.get("ok") is True,
                      f"{phase} child reported {result}")
            say(f"{phase} phase: PASS ({time.monotonic() - t:.1f}s)")
        except Exception as e:  # noqa: BLE001 — every failure fails the run
            ok = False
            say(f"{phase} phase: FAIL ({type(e).__name__}: {e})")
            reap_children()
    finish(ok, device)


# -- children: the only code here that imports jax ----------------------------


def child_device() -> int:
    import jax

    d = jax.devices()
    print("RESULT " + json.dumps({
        "platform": d[0].platform, "kind": d[0].device_kind,
        "count": len(d)}), flush=True)
    return 0


def child_library(shard_bytes: int, seed: int) -> int:
    """get_codec("tpu" | "tpu_xor" | "tpu_mxu") through their public
    methods, byte-compared with get_codec("cpu")."""
    import jax
    import numpy as np

    from seaweedfs_tpu.ops import device as dev
    from seaweedfs_tpu.ops import rs_pallas
    from seaweedfs_tpu.ops.codec import get_codec

    cache_dir = dev.enable_compile_cache()
    held = dev.held_device()
    print(f"device {json.dumps(held)}; compile cache at {cache_dir}")
    cpu = get_codec("cpu")
    rng = np.random.default_rng(seed)
    # the XLA formulations materialise 8x (xor) / bit-plane (mxu) temps in
    # HBM, so they run narrower than the Pallas kernel
    widths = {"tpu": shard_bytes, "tpu_xor": min(shard_bytes, 8 << 20),
              "tpu_mxu": min(shard_bytes, 4 << 20)}
    ok = True
    for name, width in widths.items():
        codec = get_codec(name)
        data = rng.integers(0, 256, (10, width), dtype=np.uint8)
        want = np.asarray(cpu.parity_of(data))
        full = [data[i] for i in range(10)] + [want[i] for i in range(4)]
        marks = []

        def timed(label, fn):
            before = dev.compile_cache_stats()
            t = time.perf_counter()
            out = fn()
            dt = time.perf_counter() - t
            after = dev.compile_cache_stats()
            marks.append(
                f"{label} {dt:.2f}s (compile "
                f"{after['compile_seconds'] - before['compile_seconds']:.2f}s"
                f", cache hits +{after['hits'] - before['hits']}"
                f" misses +{after['misses'] - before['misses']})")
            return out

        got = timed("parity_of cold", lambda: codec.parity_of(data))
        same = np.array_equal(np.asarray(got), want)
        got = timed("parity_of warm", lambda: codec.parity_of(data))
        same &= np.array_equal(np.asarray(got), want)
        shards = [s.copy() for s in full[:10]] + [
            np.zeros(width, np.uint8) for _ in range(4)]
        timed("encode", lambda: codec.encode(shards))
        same &= all(np.array_equal(shards[i], full[i]) for i in range(14))
        for lost in ([3], [0, 2, 5, 9]):
            holes = [None if i in lost else full[i] for i in range(14)]
            rec = timed(f"reconstruct {len(lost)} lost",
                        lambda: codec.reconstruct(holes))
            same &= all(np.array_equal(np.asarray(rec[i]), full[i])
                        for i in range(14))
        same &= timed("verify", lambda: codec.verify(full)) is True
        bad = [s.copy() if i == 12 else s for i, s in enumerate(full)]
        bad[12][width // 2] ^= 1
        same &= codec.verify(bad) is False
        print(f"{name} ({codec.impl}) at {width} bytes/shard: "
              f"{'byte-identical to cpu' if same else 'MISMATCH vs cpu'}; "
              + "; ".join(marks))
        ok &= bool(same)
    # the Pallas program must be a Mosaic kernel, not the interpreter
    fn = rs_pallas.parity_fn().as_u32_3d
    rows = shard_bytes // 512
    text = fn.lower(jax.ShapeDtypeStruct(
        (10, rows, 128), np.uint32)).as_text()
    mosaic = "tpu_custom_call" in text and not rs_pallas.INTERPRET
    print(f"pallas parity program lowers to a Mosaic tpu_custom_call: "
          f"{mosaic}")
    ok &= mosaic
    stats = jax.devices()[0].memory_stats() or {}
    print(f"HBM peak_bytes_in_use = {stats.get('peak_bytes_in_use')} "
          f"(limit {stats.get('bytes_limit')})")
    print(f"compile cache totals: {json.dumps(dev.compile_cache_stats())}")
    print("RESULT " + json.dumps({"ok": bool(ok)}), flush=True)
    return 0 if ok else 1


def child_mesh(seed: int, width: int, volumes: int) -> int:
    """The paths that exist only across chips, each byte-compared with
    ops/rs_cpu.py, with the placement of every output checked."""
    import jax
    import numpy as np

    from seaweedfs_tpu.ops import device as dev
    from seaweedfs_tpu.ops import gf256
    from seaweedfs_tpu.ops.codec_service import CodecService
    from seaweedfs_tpu.ops.rs_cpu import ReedSolomon
    from seaweedfs_tpu.parallel.batch import (
        batch_generate_ec_files,
        mesh_rebuild_ec_files,
    )
    from seaweedfs_tpu.parallel.mesh import (
        distributed_reconstruct,
        jobs_apply_sharded,
        make_mesh,
    )
    from seaweedfs_tpu.storage.ec.constants import TOTAL_SHARDS, to_ext
    from seaweedfs_tpu.storage.ec.encoder import generate_ec_files

    dev.enable_compile_cache()
    held = dev.held_device()
    n = held["count"]
    mesh = make_mesh()
    dp, sp = mesh.shape["dp"], mesh.shape["sp"]
    print(f"device {json.dumps(held)}; mesh dp={dp} sp={sp}")
    rs = ReedSolomon()
    rng = np.random.default_rng(seed)
    ok = True

    def placed(arr, what: str, share: int) -> bool:
        """Every device holds its own 1/share of `arr`."""
        shards = arr.addressable_shards
        devices = {s.device for s in shards}
        sizes = {int(np.prod(s.data.shape)) for s in shards}
        good = (len(devices) == n
                and sizes == {int(np.prod(arr.shape)) // share})
        print(f"{what}: shape {arr.shape} on {len(devices)} distinct "
              f"devices, 1/{share} each: {good}  [{arr.sharding}]")
        return good

    # 1. the service's device batch program (uint32 lane tiles both ways),
    # on the mesh the service builds for itself: `volumes` slices at once,
    # each an argument
    block = rng.integers(0, 256, (volumes, 10, width), dtype=np.uint8)
    want = np.stack([rs.parity_of(block[v]) for v in range(volumes)])
    svc = CodecService(mode="device")
    print(f"codec service mesh: {svc.mesh_shape()}")
    t = time.perf_counter()
    out = jobs_apply_sharded(svc._device_mesh(), rs.parity_matrix,
                             [block[v] for v in range(volumes)])
    out.block_until_ready()
    print(f"jobs_apply_sharded {block.shape}: {time.perf_counter() - t:.2f}s"
          " incl. compile")
    # gathered on the devices (ISSUE 38): the whole stack on every one,
    # so the host fetches it from one
    ok &= placed(out.dev, "jobs_apply_sharded output", 1)
    packed = str(out.dev.dtype) == "uint32" and out.dev.shape[-1] == 128
    print(f"jobs_apply_sharded result on the device: {out.dev.dtype} "
          f"{out.dev.shape}, lane tiles: {packed}")
    ok &= packed
    same = np.array_equal(np.asarray(out), want)
    print(f"jobs_apply_sharded vs rs_cpu: byte-identical={same}")
    ok &= same
    futs = svc.submit_parity_many([block[v] for v in range(volumes)])
    got = np.stack([np.stack([np.asarray(r) for r in f.result(600)])
                    for f in futs])
    svc.close()
    same = np.array_equal(got, want)
    print(f"codec service (device mode) {volumes} volumes' slices vs "
          f"rs_cpu: byte-identical={same}")
    ok &= same

    # 2. the psum decode: 4 data shards lost
    shards = [block[0, i] for i in range(10)] + list(want[0])
    present = [1, 3, 4, 6, 7, 8, 10, 11, 12, 13]  # lost 0,2,5,9
    dec = gf256.decode_matrix_for(gf256.rs_matrix(10, 14), 10, present)
    survivors = np.stack([shards[i] for i in present])
    t = time.perf_counter()
    rebuilt = distributed_reconstruct(mesh, dec, survivors)
    rebuilt.block_until_ready()
    print(f"distributed_reconstruct {survivors.shape}: "
          f"{time.perf_counter() - t:.2f}s incl. compile")
    # out_specs P(None, 'sp'): columns split over sp, replicated over dp
    ok &= placed(rebuilt, "distributed_reconstruct output", sp)
    same = all(np.array_equal(np.asarray(rebuilt)[i], shards[i])
               for i in range(10))
    print(f"distributed_reconstruct vs the original data: "
          f"byte-identical={same}")
    ok &= same

    # 3. the file-level flows
    work = os.path.join(WORK_DIR, "mesh")
    shutil.rmtree(work, ignore_errors=True)
    os.makedirs(work)
    bases, expect = [], {}
    for v in range(volumes):
        base = os.path.join(work, f"v{v}")
        size = int(rng.integers(8 * width, 24 * width)) | 1  # uneven, odd
        with open(base + ".dat", "wb") as f:
            f.write(rng.integers(0, 256, size, dtype=np.uint8).tobytes())
        generate_ec_files(base, codec_name="cpu")
        for i in range(TOTAL_SHARDS):
            with open(base + to_ext(i), "rb") as fh:
                expect[base + to_ext(i)] = fh.read()
            os.remove(base + to_ext(i))
        bases.append(base)
    t = time.perf_counter()
    batch_generate_ec_files(bases, mesh=mesh)
    differ = [p for p, w in expect.items() if open(p, "rb").read() != w]
    print(f"batch_generate_ec_files: {volumes} volumes, "
          f"{len(expect)} shard files in {time.perf_counter() - t:.2f}s, "
          f"differing from rs_cpu: {len(differ)}")
    ok &= not differ
    lost = [0, 2, 5, 9]
    for i in lost:
        os.remove(bases[1] + to_ext(i))
    t = time.perf_counter()
    back = mesh_rebuild_ec_files(bases[1], mesh=mesh)
    differ = [i for i in lost if open(bases[1] + to_ext(i), "rb").read()
              != expect[bases[1] + to_ext(i)]]
    print(f"mesh_rebuild_ec_files: rebuilt {back} in "
          f"{time.perf_counter() - t:.2f}s, differing from rs_cpu: {differ}")
    ok &= back == lost and not differ
    shutil.rmtree(work, ignore_errors=True)
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in jax.devices()]
    print(f"HBM peak_bytes_in_use per device = {peaks}")
    print(f"compile cache totals: {json.dumps(dev.compile_cache_stats())}")
    print("RESULT " + json.dumps({"ok": bool(ok)}), flush=True)
    return 0 if ok else 1


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the mesh phase, on four chips")
    ap.add_argument("--child", choices=("device", "library", "mesh"),
                    help=argparse.SUPPRESS)
    ap.add_argument("--shard-bytes", type=int, default=64 << 20,
                    help=argparse.SUPPRESS)
    ap.add_argument("--seed", type=int, default=23, help=argparse.SUPPRESS)
    ap.add_argument("--width", type=int, default=1 << 20,
                    help=argparse.SUPPRESS)
    ap.add_argument("--volumes", type=int, default=16,
                    help=argparse.SUPPRESS)
    args = ap.parse_args()
    if args.child == "device":
        sys.exit(child_device())
    if args.child == "library":
        sys.exit(child_library(args.shard_bytes, args.seed))
    if args.child == "mesh":
        sys.exit(child_mesh(args.seed, args.width, args.volumes))
    run(chips=args.chips)


if __name__ == "__main__":
    main()
