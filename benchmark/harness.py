"""Processes, sockets and the result line of one benchmark run.

One `server` child holds the chip for the whole run; this process drives
it from outside and never imports jax.  Children write to log files, never
to our stdout, so nothing can follow the result line.
"""

from __future__ import annotations

import json
import os
import random
import shutil
import socket
import subprocess
import sys
import threading
import time

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)
WORK_ROOT = os.path.join(BENCH_DIR, ".work")
LOG_ROOT = os.path.join(ROOT, "chiprun_out", "benchmark")
# jax's persistent compile cache: a fixed path inside the checkout, so the
# second run of a cell there compiles nothing and two checkouts share nothing
COMPILE_CACHE_DIR = os.path.join(WORK_ROOT, "jax_cache")

T0 = time.monotonic()
_CHILDREN: list[subprocess.Popen] = []


class BenchFailure(Exception):
    """The run cannot produce a result (no chip, server died, ...)."""


def say(msg: str) -> None:
    print(f"[bench +{time.monotonic() - T0:6.1f}s] {msg}",
          file=sys.stderr, flush=True)


# -- children ----------------------------------------------------------------


def child_env(extra: "dict | None" = None) -> dict:
    env = dict(os.environ)
    env["PYTHONPATH"] = ROOT + os.pathsep + env.get("PYTHONPATH", "")
    env.setdefault("TPU_LOG_DIR", "disabled")
    # the program keeps its compile cache where this variable says and sets
    # no other in code (ops/device.py); every compile is kept, however short
    env["JAX_COMPILATION_CACHE_DIR"] = COMPILE_CACHE_DIR
    env["JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"] = "0"
    # no eviction, whatever the machine's default: the cache is a few MB, and
    # an evicting jax fails every write beside an entry a non-evicting one
    # left (PR 25: each run of a call then compiled)
    env["JAX_COMPILATION_CACHE_MAX_SIZE"] = "-1"
    env.update(extra or {})
    return env


def spawn(argv: list, log_path: str, env_extra: "dict | None" = None,
          stdout=None) -> subprocess.Popen:
    os.makedirs(os.path.dirname(log_path), exist_ok=True)
    with open(log_path, "ab") as log:
        proc = subprocess.Popen(
            argv, cwd=ROOT, env=child_env(env_extra),
            stdin=subprocess.DEVNULL,
            stdout=log if stdout is None else stdout, stderr=log)
    _CHILDREN.append(proc)
    return proc


def stop(proc: subprocess.Popen, grace: float = 30.0) -> None:
    if proc.poll() is None:
        proc.terminate()
        try:
            proc.wait(timeout=grace)
        except subprocess.TimeoutExpired:
            proc.kill()
            proc.wait()


def reap_children() -> None:
    for proc in _CHILDREN:
        if proc.poll() is None:
            proc.terminate()
    for proc in _CHILDREN:
        stop(proc, grace=20.0)
    _CHILDREN.clear()


def log_tail(path: str, n: int = 20) -> str:
    try:
        with open(path, "rb") as f:
            lines = f.read().decode("utf-8", "replace").splitlines()
        return "\n".join("    | " + ln for ln in lines[-n:])
    except OSError:
        return "    | (no log)"


# -- sockets -------------------------------------------------------------------


# Both p and its gRPC twin p + 10000 lie below the kernel's ephemeral range
# (32768-60999), so no client socket of this machine can sit on either
# between the probe here and the child's bind, and outside the band the
# repo's other tests draw from (tests/helpers.py: 20000-22767 and its twins
# 30000-32767), which run beside the rehearsals under several workers.
PORT_BAND = (12768, 20000)
# never the same port twice in one process: pb/rpc.py keeps one channel per
# address process-wide, and a dead server's backed-off channel would serve
# the next server on its port
_PORTS_HANDED_OUT: set = set()
BIND_FAILED = ("Address already in use", "Failed to bind to address")


def free_port_pair() -> int:
    """A port p with p and p+10000 (the gRPC twin) both free, drawn at
    random (two processes that start servers at once draw apart) and
    never handed out twice by this process."""
    rng = random.Random()
    for _ in range(2000):
        p = rng.randrange(*PORT_BAND)
        if p in _PORTS_HANDED_OUT:
            continue
        try:
            for q in (p, p + 10000):
                with socket.socket() as s:
                    s.bind(("0.0.0.0", q))
        except OSError:
            continue
        _PORTS_HANDED_OUT.add(p)
        return p
    raise BenchFailure("no free port pair")


class HttpConn:
    """One kept-alive HTTP/1.1 connection with the least parsing that is
    still correct for this server (Content-Length bodies): the clients of
    a latency cell must cost less than the server they time."""

    def __init__(self, host: str, port: int, timeout: float = 60.0):
        self.addr, self.timeout = (host, port), timeout
        self.host = f"{host}:{port}".encode()
        self.sock = None
        self.buf = b""

    def close(self) -> None:
        if self.sock is not None:
            try:
                self.sock.close()
            finally:
                self.sock = None
                self.buf = b""

    def _connect(self) -> None:
        self.sock = socket.create_connection(self.addr, timeout=self.timeout)
        self.sock.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
        self.buf = b""

    def request(self, method: str, path: str, body: bytes = b"",
                ctype: bytes = b"application/octet-stream"):
        """-> (status, body).  One retry, on a connection the server closed
        while it sat idle."""
        head = (method.encode() + b" " + path.encode() + b" HTTP/1.1\r\nHost: "
                + self.host + b"\r\n")
        if body or method in ("POST", "PUT"):
            head += (b"Content-Type: " + ctype + b"\r\nContent-Length: "
                     + str(len(body)).encode() + b"\r\n")
        msg = head + b"\r\n" + body
        for attempt in (0, 1):
            fresh = self.sock is None
            if fresh:
                self._connect()
            try:
                self.sock.sendall(msg)
                return self._response(method)
            except (OSError, EOFError):
                self.close()
                if fresh or attempt:
                    raise
        raise AssertionError

    def _response(self, method: str):
        buf = self.buf
        while True:
            end = buf.find(b"\r\n\r\n")
            if end >= 0:
                break
            chunk = self.sock.recv(65536)
            if not chunk:
                raise EOFError("connection closed before the headers")
            buf += chunk
        head, buf = buf[:end], buf[end + 4:]
        lines = head.split(b"\r\n")
        status = int(lines[0].split(b" ", 2)[1])
        length, close = 0, False
        for ln in lines[1:]:
            key, _, val = ln.partition(b":")
            key = key.strip().lower()
            if key == b"content-length":
                length = int(val)
            elif key == b"connection" and val.strip().lower() == b"close":
                close = True
            elif key == b"transfer-encoding":
                raise BenchFailure("chunked reply: not spoken here")
        if method == "HEAD" or status in (204, 304):
            length = 0
        parts, have = [buf], len(buf)
        while have < length:
            chunk = self.sock.recv(min(1 << 20, length - have))
            if not chunk:
                raise EOFError("connection closed inside the body")
            parts.append(chunk)
            have += len(chunk)
        data = b"".join(parts)
        body, self.buf = data[:length], data[length:]
        if close:
            self.close()
        return status, body


def http_get(host_port: str, path: str, timeout: float = 60.0) -> bytes:
    host, port = host_port.rsplit(":", 1)
    c = HttpConn(host, int(port), timeout)
    try:
        status, body = c.request("GET", path)
    finally:
        c.close()
    if status != 200:
        raise BenchFailure(f"GET {host_port}{path}: HTTP {status}")
    return body


# -- prometheus text (copied from chip_smoke.py, PR 23) ----------------------


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
        if key.split("{", 1)[0] != name \
                or not all(b in key for b in label_bits):
            continue
        total += val - before.get(key, 0.0)
    return total


# -- the server child ------------------------------------------------------------


class Cluster:
    """One `server` process (master + volume server) over `dirs`.

    Untraced it is exactly `python -m seaweedfs_tpu server ...`; traced it
    is benchmark/server_entry.py, which runs the same `cli.main` beside a
    control thread that can start and stop the jax profiler."""

    def __init__(self, dirs: list, codec: str, log_dir: str,
                 env_extra: "dict | None" = None, traced: bool = False,
                 control_dir: str = ""):
        self.dirs, self.codec, self.log_dir = dirs, codec, log_dir
        self.traced, self.control_dir = traced, control_dir
        self.env_extra = env_extra
        self.log_path = os.path.join(log_dir, "server.log")
        self._env = None
        self._launch()

    def _launch(self) -> None:
        self.mport = free_port_pair()
        self.vport = free_port_pair()
        args = ["server", "-dir", ",".join(self.dirs), "-ip", "127.0.0.1",
                "-masterPort", str(self.mport), "-port", str(self.vport),
                "-ec.codec", self.codec]
        if self.traced:
            argv = [sys.executable, os.path.join(BENCH_DIR, "server_entry.py"),
                    "--control-dir", self.control_dir, "--"] + args
        else:
            argv = [sys.executable, "-m", "seaweedfs_tpu"] + args
        self.proc = spawn(argv, self.log_path, self.env_extra)

    master = property(lambda self: f"127.0.0.1:{self.mport}")
    volume = property(lambda self: f"127.0.0.1:{self.vport}")
    master_grpc = property(lambda self: f"127.0.0.1:{self.mport + 10000}")
    volume_grpc = property(lambda self: f"127.0.0.1:{self.vport + 10000}")

    def alive(self) -> None:
        if self.proc.poll() is not None:
            raise BenchFailure(
                f"server exited {self.proc.returncode}; its log ends:\n"
                + log_tail(self.log_path, 25))

    def wait_ready(self, deadline_s: float = 900.0) -> dict:
        """-> /status once the volume server answers and the master can
        assign.  A cold TPU initialisation takes as long as it takes."""
        t_end = time.monotonic() + deadline_s
        status, relaunches = None, 0
        while time.monotonic() < t_end:
            if self.proc.poll() is not None and relaunches < 3 and any(
                    why in log_tail(self.log_path, 40) for why in BIND_FAILED):
                # another process took a port between the probe and the
                # child's bind: the one race a probe cannot close
                relaunches += 1
                say(f"the server could not bind {self.mport} / {self.vport} "
                    f"or a twin: started again on another pair")
                os.replace(self.log_path, f"{self.log_path}.bind{relaunches}")
                self._launch()
            self.alive()
            try:
                if status is None:
                    status = json.loads(http_get(self.volume, "/status", 5))
                json.loads(http_get(self.master, "/dir/assign", 5))["fid"]
                return status
            except (OSError, EOFError, BenchFailure, KeyError, ValueError):
                time.sleep(0.25)
        raise BenchFailure("server not ready in time:\n"
                           + log_tail(self.log_path, 25))

    def status(self) -> dict:
        return json.loads(http_get(self.volume, "/status"))

    def metrics(self) -> dict:
        return parse_metrics(http_get(self.volume, "/metrics").decode())

    def env(self):
        """The operator shell's environment, in this process: the timed
        rpcs go through the shell's own command functions without paying
        an interpreter start per rpc."""
        if self._env is None:
            from seaweedfs_tpu.shell.commands import CommandEnv

            self._env = CommandEnv(self.master_grpc)
        return self._env

    def shell(self, name: str, args: list) -> str:
        from seaweedfs_tpu.shell import ec_commands

        return getattr(ec_commands, name)(self.env(), args)

    def ec_shard_counts(self) -> dict:
        """{vid: shards the master knows of}."""
        from seaweedfs_tpu.storage.ec.shard_bits import ShardBits

        have: dict = {}
        for dc in self.env().topology().data_center_infos:
            for rack in dc.rack_infos:
                for dn in rack.data_node_infos:
                    for disk in dn.disk_infos.values():
                        for e in disk.ec_shard_infos:
                            have[e.id] = have.get(e.id, ShardBits(0)).plus(
                                ShardBits(e.ec_index_bits))
        return {vid: bits.count() for vid, bits in have.items()}

    def wait_shards(self, want: dict, deadline_s: float = 30.0) -> float:
        """Wait until the master's view holds `want` ({vid: count}); -> the
        seconds it took (heartbeat deltas ride a pulse of up to 1 s)."""
        t = time.monotonic()
        while True:
            self.alive()
            have = self.ec_shard_counts()
            if all(have.get(v, 0) == n for v, n in want.items()):
                return time.monotonic() - t
            if time.monotonic() - t > deadline_s:
                raise BenchFailure(f"master sees {have}, wanted {want}")
            time.sleep(0.02)

    def drop_shards(self, vid: int, collection: str, shard_ids: list) -> None:
        from seaweedfs_tpu.pb import volume_server_pb2 as vs

        stub = self.env().volume_server(self.volume_grpc)
        stub.VolumeEcShardsUnmount(vs.VolumeEcShardsUnmountRequest(
            volume_id=vid, shard_ids=shard_ids))
        stub.VolumeEcShardsDelete(vs.VolumeEcShardsDeleteRequest(
            volume_id=vid, collection=collection, shard_ids=shard_ids))

    def mount_shards(self, vid: int, collection: str, shard_ids: list) -> None:
        from seaweedfs_tpu.pb import volume_server_pb2 as vs

        self.env().volume_server(self.volume_grpc).VolumeEcShardsMount(
            vs.VolumeEcShardsMountRequest(
                volume_id=vid, collection=collection, shard_ids=shard_ids))

    def control(self, command: str, deadline_s: float = 120.0) -> dict:
        """Hand one command to server_entry.py's control thread and wait
        for its answer (files under the run's work dir)."""
        req = os.path.join(self.control_dir, "command.json")
        ack = os.path.join(self.control_dir, "ack.json")
        if os.path.exists(ack):
            os.remove(ack)
        with open(req + ".tmp", "w") as f:
            json.dump({"command": command}, f)
        os.replace(req + ".tmp", req)
        t_end = time.monotonic() + deadline_s
        while time.monotonic() < t_end:
            self.alive()
            if os.path.exists(ack):
                with open(ack) as f:
                    out = json.load(f)
                os.remove(ack)
                if out.get("error"):
                    raise BenchFailure(f"{command}: {out['error']}")
                return out
            time.sleep(0.01)
        raise BenchFailure(f"no answer to control command {command}")

    def stop(self) -> None:
        stop(self.proc)


# -- what one run observed ---------------------------------------------------------


class Obs:
    """Everything the readers may read: the harness's own clocks and
    counts (`work`, `clock`), scrapes of the server's /metrics around named
    phases (`prom`), and the reduced device trace (`trace`)."""

    def __init__(self, cluster: "Cluster | None" = None):
        self.cluster = cluster
        self.work: dict = {}
        self.clock: dict = {}
        self.prom: dict = {}
        self.trace: "dict | None" = None
        self.device: dict = {}
        self.peaks: dict = {}
        self.attempted = 0
        self.failed = 0
        self.rpc_listeners: list = []
        self._lock = threading.Lock()

    def add(self, key: str, value: float) -> None:
        with self._lock:
            self.work[key] = self.work.get(key, 0.0) + value

    def count(self, attempted: int, failed: int = 0) -> None:
        with self._lock:
            self.attempted += attempted
            self.failed += failed

    def prom_begin(self, phase: str) -> None:
        self.prom[phase] = [self.cluster.metrics(), None]

    def prom_end(self, phase: str) -> None:
        self.prom[phase][1] = self.cluster.metrics()

    def delta(self, phase: str, name: str, *label_bits) -> "float | None":
        pair = self.prom.get(phase)
        if not pair or pair[1] is None:
            return None
        return metric_delta(pair[0], pair[1], name, *label_bits)

    def has_series(self, phase: str, name: str) -> bool:
        """Whether the program exports a series of `name` at all (by the
        scrape that closed the phase): a counter that stood still is not a
        counter the program lacks."""
        pair = self.prom.get(phase)
        return bool(pair and pair[1]) and any(
            key.split("{", 1)[0] == name for key in pair[1])

    def rpc(self, index: int, edge: str) -> None:
        """A driver says its timed rpc `index` starts ("start"), has ended
        ("end"), or has ended having found nothing to do ("idle")."""
        for fn in self.rpc_listeners:
            fn(index, edge)


# -- the result line -----------------------------------------------------------------


def decide(compared: dict) -> bool:
    """`correct`: every number compared is within its limit, and there is
    at least one.  {name: {"value": v, "limit": l}}; within = v <= l."""
    return bool(compared) and all(
        c["value"] is not None and c["value"] <= c["limit"]
        for c in compared.values())


def result_line(attempted: int, failed: int, metrics: dict, device: dict,
                compared: dict, breakdown: "dict | None" = None) -> str:
    """The one line the driver parses.  Pure; `compared` comes last."""
    out = {
        "correct": decide(compared),
        "attempted": int(attempted),
        "failed": int(failed),
        "metrics": {name: {"value": float(m["value"]), "unit": str(m["unit"])}
                    for name, m in metrics.items()},
        "device": device,
    }
    if breakdown is not None:
        out["breakdown"] = breakdown
    out["compared"] = compared
    return json.dumps(out)


def compared_lines(compared: dict) -> str:
    return "\n".join(
        f"compared {name}: value={c['value']} limit={c['limit']} "
        f"{'ok' if c['value'] is not None and c['value'] <= c['limit'] else 'OVER'}"
        for name, c in compared.items())


def finish(line: "str | None", compared: "dict | None", code: int) -> None:
    """Reap every child, print the compared numbers (stderr, last) and the
    result line (stdout, last), leave.  `line` None = no result."""
    reap_children()
    sys.stdout.flush()
    if compared:
        sys.stderr.write(compared_lines(compared) + "\n")
    sys.stderr.flush()
    if line is not None:
        sys.stdout.write(line + "\n")
        sys.stdout.flush()
    os._exit(code)


def fresh_dir(path: str) -> str:
    shutil.rmtree(path, ignore_errors=True)
    os.makedirs(path)
    return path
