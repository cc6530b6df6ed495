"""Shared test fixtures: synthetic needle-log volumes."""

from __future__ import annotations

import json
import os
import subprocess
import sys
import threading
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

import numpy as np

from seaweedfs_tpu.storage import Needle, SuperBlock
from seaweedfs_tpu.storage.needle import FLAG_HAS_MIME, FLAG_HAS_NAME
from seaweedfs_tpu.storage.volume import Volume


def make_volume(
    directory: str,
    volume_id: int = 1,
    n_needles: int = 50,
    seed: int = 0,
    max_size: int = 2000,
    collection: str = "",
) -> Volume:
    """Create a volume with random needles; returns the open Volume."""
    rng = np.random.default_rng(seed)
    vol = Volume(directory, collection, volume_id, super_block=SuperBlock())
    for i in range(1, n_needles + 1):
        size = int(rng.integers(1, max_size))
        n = Needle(
            cookie=int(rng.integers(0, 2**32)),
            id=i,
            data=rng.integers(0, 256, size).astype(np.uint8).tobytes(),
        )
        if i % 3 == 0:
            n.set(FLAG_HAS_NAME)
            n.name = f"file-{i}.bin".encode()
        if i % 5 == 0:
            n.set(FLAG_HAS_MIME)
            n.mime = b"application/octet-stream"
        vol.append_needle(n)
    return vol


class S3StubHandler(BaseHTTPRequestHandler):
    """Minimal unsigned S3 endpoint: PUT/GET(Range)/DELETE over an
    in-memory dict — enough surface for the remote-tier backend without
    spinning a whole gateway cluster.  Use `start_s3_stub()`."""

    protocol_version = "HTTP/1.1"
    objects: dict[str, bytes] = {}
    range_reads = 0

    def log_message(self, fmt, *args):
        pass

    def _reply(self, code, body=b"", headers=()):
        self.send_response(code)
        for k, v in headers:
            self.send_header(k, v)
        self.send_header("Content-Length", str(len(body)))
        self.end_headers()
        if body:
            self.wfile.write(body)

    def do_PUT(self):
        length = int(self.headers.get("Content-Length") or 0)
        self.objects[self.path] = self.rfile.read(length)
        self._reply(200, headers=[("ETag", '"stub"')])

    def do_GET(self):
        blob = self.objects.get(self.path)
        if blob is None:
            return self._reply(404)
        rng = self.headers.get("Range")
        if rng and rng.startswith("bytes="):
            type(self).range_reads += 1
            lo, _, hi = rng[len("bytes="):].partition("-")
            lo = int(lo)
            hi = int(hi) if hi else len(blob) - 1
            part = blob[lo:hi + 1]
            return self._reply(206, part, headers=[(
                "Content-Range", f"bytes {lo}-{hi}/{len(blob)}")])
        self._reply(200, blob)

    def do_DELETE(self):
        self.objects.pop(self.path, None)
        self._reply(204)


def start_s3_stub():
    """-> (httpd, handler_class).  handler_class.objects is the live
    object dict ('/bucket/key' -> bytes); handler_class.range_reads
    counts ranged GETs.  Caller shuts down via httpd.shutdown()."""
    handler = type("BoundS3Stub", (S3StubHandler,),
                   {"objects": {}, "range_reads": 0})
    httpd = ThreadingHTTPServer(("127.0.0.1", 0), handler)
    threading.Thread(target=httpd.serve_forever, daemon=True).start()
    return httpd, handler


_used_ports: set[int] = set()


def free_port() -> int:
    """A free TCP port, never handed out twice in one test session.

    Reuse matters because pb/rpc.py caches one channel per address
    process-wide: a port recycled from an earlier module's dead server
    would serve its stale, backed-off channel to the new one.

    Ports come from 20000-22767: DISJOINT from the kernel's ephemeral
    range (32768-60999) — and so are the derived grpc_port = port+10000
    siblings (30000-32767, ending just below the ephemeral floor; the
    band also keeps them under 65536).  A port-0 server (fake stores,
    FTP PASV sockets) can therefore never squat on a port this function
    later hands a module fixture — a race that made whole modules error
    with 'Failed to bind' roughly once per several full-suite runs."""
    import random
    import socket

    rng = random.Random()
    for _ in range(20000):  # fail loud, never hang, if the band drains
        port = rng.randrange(20000, 22768)
        if port in _used_ports:
            continue
        try:
            with socket.socket() as s:
                s.bind(("127.0.0.1", port))
        except OSError:
            continue
        _used_ports.add(port)
        return port
    raise RuntimeError(
        "free_port: test port band 20000-22767 exhausted or blocked")


def start_master_cluster(base_dir: str, **kw):
    """Start SEAWEEDFS_TPU_TEST_MASTERS in-process masters (default 1)
    and return ``(leader, all_masters)``.

    n=1 reproduces the classic single-master setup exactly (no peers,
    no raft).  n>=3 starts a raft quorum — each master gets its own
    ``lifecycle_dir`` subdirectory under the caller's (the maintenance
    journal is raft-replicated, so the elected leader's view is the
    cluster's) — letting CI re-run the chaos suites against a 3-master
    quorum without a second copy of every test."""
    import os
    import time

    from seaweedfs_tpu.master.server import MasterServer

    n = int(os.environ.get("SEAWEEDFS_TPU_TEST_MASTERS", "1"))
    if n <= 1:
        m = MasterServer(ip="127.0.0.1", port=free_port(), **kw)
        m.start()
        return m, [m]
    ports = [free_port() for _ in range(n)]
    peers = [f"127.0.0.1:{p}" for p in ports]
    raft_dir = os.path.join(base_dir, "raft-state")
    os.makedirs(raft_dir, exist_ok=True)
    masters = []
    for i, p in enumerate(ports):
        mkw = dict(kw)
        if "lifecycle_dir" in mkw:
            d = os.path.join(mkw["lifecycle_dir"], f"m{i}")
            os.makedirs(d, exist_ok=True)
            mkw["lifecycle_dir"] = d
        m = MasterServer(ip="127.0.0.1", port=p, peers=peers,
                         raft_state_dir=raft_dir, **mkw)
        m.start()
        masters.append(m)
    deadline = time.time() + 30
    while time.time() < deadline:
        leaders = [m for m in masters if m.is_leader()]
        if len(leaders) == 1 and masters[0].leader():
            return leaders[0], masters
        time.sleep(0.05)
    raise AssertionError("master quorum elected no leader")


def run_four_device_child(script: str) -> dict:
    """Run ``script`` in a child whose CPU backend has four devices — a
    codec service there builds its mesh from `jax.devices()`, as a server
    on four chips does — and return the JSON object of its last line."""
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    proc = subprocess.run(
        [sys.executable, "-c", script], env=env, capture_output=True,
        text=True, timeout=600, cwd=root)
    assert proc.returncode == 0, proc.stderr[-3000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


def empty_slice_pool(monkeypatch, pool=None) -> None:
    """Release every free buffer of the EC pipelines' slice pool (the
    process's, unless one is given), so the gauge
    `seaweedfs_ec_slice_pool_bytes` reads what a test does next."""
    from seaweedfs_tpu.storage.ec import encoder

    period = encoder._POOL_IDLE_S
    monkeypatch.setattr(encoder, "_POOL_IDLE_S", 0.0)
    (pool or encoder._SLICE_POOL).release_idle()
    monkeypatch.setattr(encoder, "_POOL_IDLE_S", period)

