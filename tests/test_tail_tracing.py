"""Where a GET's tail is made: the socket wait before the loop thread has a
request head, the interpreter wait every hand-over pays, the codec
service's two unseen waits (`parked`, `handback`), the histograms' tail
bounds and O(log n) observe, and which spans stay on the profiler's
clock — all on the CPU backend."""

from __future__ import annotations

import http.client
import os
import subprocess
import sys
import threading
import time
from http.server import BaseHTTPRequestHandler
from types import SimpleNamespace

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from seaweedfs_tpu.ops import gf256  # noqa: E402
from seaweedfs_tpu.ops.codec_service import CodecService  # noqa: E402
from seaweedfs_tpu.ops.rs_cpu import ReedSolomon  # noqa: E402
from seaweedfs_tpu.stats import metrics as m  # noqa: E402
from seaweedfs_tpu.stats.metrics import (  # noqa: E402
    EC_SERVICE_STAGE,
    HTTPD_RESIDENT,
    HTTPD_SOCKET_WAIT,
    REGISTRY,
)
from seaweedfs_tpu.telemetry import trace  # noqa: E402
from seaweedfs_tpu.util import httpd  # noqa: E402

ROUND_TRIP = ("queue_wait", "build", "enqueue", "parked", "device_wait",
              "d2h", "deliver", "handback")


# -- the histograms ------------------------------------------------------------


def test_tail_buckets_are_quarter_octaves_over_the_defaults():
    b = m.TAIL_BUCKETS
    grid = [float(f"{1e-4 * 2 ** (k / 4):.4g}") for k in range(65)]
    assert list(b) == sorted(set(grid) | set(m._DEFAULT_BUCKETS))
    steps = [hi / lo for lo, hi in zip(grid, grid[1:])]
    assert all(1.188 <= s <= 1.191 for s in steps), steps
    assert (grid[0], grid[-1], b[-1]) == (0.0001, 6.554, 10.0)
    for fam in (m.HTTPD_RESIDENT, m.HTTPD_SOCKET_WAIT, m.EC_DEGRADED_STAGE,
                m.INTERPRETER_WAIT):
        assert fam.buckets == b


def _old_exposition(fam, observations) -> list[str]:
    """The exposition of `fam` as the cumulative walk of before rendered
    it: every bound >= v bumped at observe time."""
    out = [f"# HELP {fam.name} {fam.help}", f"# TYPE {fam.name} histogram"]
    for key, values in observations.items():
        counts = [0] * len(fam.buckets)
        for v in values:
            for i, b in enumerate(fam.buckets):
                if v <= b:
                    counts[i] += 1
        pairs = ",".join(f'{n}="{x}"' for n, x in zip(fam.label_names, key))
        sep = "," if pairs else ""
        for b, c in zip(fam.buckets, counts):
            out.append(f'{fam.name}_bucket{{{pairs}{sep}le="{m.format_le(b)}"}} {c}')
        out.append(f'{fam.name}_bucket{{{pairs}{sep}le="+Inf"}} {len(values)}')
        ls = "{" + pairs + "}" if pairs else ""
        out.append(f"{fam.name}_sum{ls} {sum(values, 0.0)}")
        out.append(f"{fam.name}_count{ls} {len(values)}")
    return out


@pytest.mark.parametrize("buckets", [m._DEFAULT_BUCKETS, m.TAIL_BUCKETS],
                         ids=["default", "tail"])
def test_exposition_is_byte_identical_to_the_cumulative_walk(buckets):
    """A family as one already exported (seaweedfs_request_seconds'
    labels), fed the same observations: on a bound, between bounds,
    below the first, above the last."""
    fam = m.Histogram("seaweedfs_request_seconds", "request latency",
                      ("type", "op"), buckets)
    rng = np.random.default_rng(44)
    obs = {("volumeServer", "get"): [0.0, 0.0001, 0.0025, 10.0, 11.0]
           + list(rng.lognormal(-5, 2, 300)),
           ("master", "assign"): [1e-9, 0.05]}
    for key, values in obs.items():
        for v in values:
            fam.labels(*key).observe(v)
    assert fam.render() == _old_exposition(fam, obs)


def test_exemplar_lands_in_the_first_bucket_at_or_above_the_value():
    fam = m.Histogram("t_exemplar", "", (), (0.1, 1.0))
    child = fam.labels()
    for v, tid in ((0.1, "a"), (0.5, "b"), (2.0, "c")):
        child.observe(v, trace_id=tid)
    assert {i: e[1] for i, e in child.exemplars.items()} == {
        0: "a", 1: "b", 2: "c"}
    assert child.cumulative() == ([1, 2], 3, 2.6)


# -- the socket wait -----------------------------------------------------------


class _Handler(BaseHTTPRequestHandler):
    protocol_version = "HTTP/1.1"

    def log_message(self, fmt, *args):
        pass

    def do_GET(self):
        self.send_response(200)
        self.send_header("Content-Length", "2")
        self.end_headers()
        self.wfile.write(b"ok")

    do_POST = do_GET


def _serve(surface: str):
    srv = httpd.EventLoopHTTPServer(("127.0.0.1", 0), _Handler,
                                    surface=surface)
    threading.Thread(target=srv.serve_forever, daemon=True).start()
    return srv


def _stop(srv) -> None:
    srv.shutdown()
    srv.server_close()


def _wait_count(child, n: int) -> None:
    deadline = time.monotonic() + 10
    while child.count < n and time.monotonic() < deadline:
        time.sleep(0.001)


@pytest.mark.skipif(not httpd._RX_STAMPS, reason="Linux socket options")
def test_a_head_the_loop_reads_late_waited_at_least_that_long():
    """The loop thread reads each head 50 ms after select said it was
    there: the socket wait observes at least that, per method, once per
    request of a kept-alive connection, and `resident` starts after it."""
    surface = "tail_socket_wait"
    srv = _serve(surface)
    late = srv._readable

    def readable(conn):
        time.sleep(0.05)
        late(conn)

    srv._readable = readable
    try:
        conn = http.client.HTTPConnection(*srv.server_address, timeout=10)
        for method in ("GET", "GET", "POST"):
            conn.request(method, "/x", body=b"y" if method == "POST" else None)
            assert conn.getresponse().read() == b"ok"
        conn.close()
        gets = HTTPD_SOCKET_WAIT.labels(surface, "GET")
        posts = HTTPD_SOCKET_WAIT.labels(surface, "POST")
        _wait_count(HTTPD_RESIDENT.labels(surface, "POST"), 1)
        assert (gets.count, posts.count) == (2, 1)
        assert gets.total >= 2 * 0.05 and posts.total >= 0.05
        assert gets.total < 2 * 5.0   # a receive time, not an epoch
    finally:
        _stop(srv)


def test_a_socket_without_the_option_exports_no_series(monkeypatch):
    """A kernel that stamps nothing (here: no option asked for) leaves the
    family without a series for the surface — never a zero — and the
    requests are served as before."""
    monkeypatch.setattr(httpd, "_RX_STAMPS", {})
    surface = "tail_no_stamps"
    srv = _serve(surface)
    try:
        conn = http.client.HTTPConnection(*srv.server_address, timeout=10)
        for _ in range(3):
            conn.request("GET", "/x")
            assert conn.getresponse().read() == b"ok"
        conn.close()
        _wait_count(HTTPD_RESIDENT.labels(surface, "GET"), 3)
    finally:
        _stop(srv)
    text = REGISTRY.render(["seaweedfs_httpd_socket_wait_seconds"])
    assert f'surface="{surface}"' not in text
    assert HTTPD_RESIDENT.labels(surface, "GET").count == 3


def test_rx_stamp_reads_either_control_message():
    import socket
    import struct

    if not httpd._RX_STAMPS:
        pytest.skip("Linux socket options")
    ns = (socket.SOL_SOCKET, 35, struct.pack("qq", 1700000000, 250000000))
    us = (socket.SOL_SOCKET, 29, struct.pack("qq", 1700000000, 250000))
    other = (socket.SOL_SOCKET, 99, struct.pack("qq", 1, 2))
    assert httpd._rx_stamp([ns]) == pytest.approx(1700000000.25)
    assert httpd._rx_stamp([other, us]) == pytest.approx(1700000000.25)
    assert httpd._rx_stamp([other]) == 0.0 and httpd._rx_stamp(()) == 0.0


# -- the interpreter wait ------------------------------------------------------


def test_the_probe_observes_and_ends_with_its_process():
    code = (
        "import time\n"
        "from seaweedfs_tpu.stats.metrics import INTERPRETER_WAIT\n"
        "from seaweedfs_tpu.telemetry.interpreter import start_wait_probe\n"
        "t = start_wait_probe()\n"
        "time.sleep(0.5)\n"
        "child = INTERPRETER_WAIT.labels()\n"
        "print(child.count, child.total, t.daemon, t.is_alive())\n")
    t0 = time.monotonic()
    proc = subprocess.run([sys.executable, "-c", code], cwd=ROOT,
                          capture_output=True, text=True, timeout=60)
    assert proc.returncode == 0, proc.stderr[-2000:]
    count, total, daemon, alive = proc.stdout.split()
    assert 10 <= int(count) <= 51          # 100 a second, at most
    assert 0 <= float(total) < 0.5 and daemon == alive == "True"
    # the process exited with the probe still sleeping: nothing joins it
    assert time.monotonic() - t0 < 30


def test_no_probe_runs_in_this_process():
    assert not [t for t in threading.enumerate()
                if t.name == "interpreter-wait-probe"]


def test_server_and_volume_commands_start_the_probe():
    import inspect

    from seaweedfs_tpu import cli

    for cmd in (cli.cmd_volume, cli.cmd_server):
        src = inspect.getsource(cmd)
        assert src.index("start_wait_probe()") < src.index("VolumeServer(")


# -- the codec service's round trip --------------------------------------------


def _block(seed: int, width: int) -> np.ndarray:
    return np.random.default_rng(seed).integers(
        0, 256, (10, width), dtype=np.uint8)


def _plan():
    rs = ReedSolomon()
    return gf256.decode_plan_for(rs.matrix, 10, list(range(4, 14)),
                                 (0, 1, 2, 3))


def _snap(cls: str) -> dict:
    return {st: (EC_SERVICE_STAGE.labels(st, cls).total,
                 EC_SERVICE_STAGE.labels(st, cls).count)
            for st in ROUND_TRIP}


def _moved(before: dict, cls: str) -> dict:
    now = _snap(cls)
    return {st: (now[st][0] - before[st][0], now[st][1] - before[st][1])
            for st in ROUND_TRIP}


@pytest.fixture(scope="module")
def device_service():
    svc = CodecService(mode="device", codec_name="tpu_xor")
    svc.warm_reads(4, 10)
    svc.submit_parity(_block(0, 4096)).result(120)  # compile outside tests
    svc.submit_apply(_plan(), _block(1, 200 << 10),
                     job_class="read").result(120)
    yield svc
    svc.close()


@pytest.mark.parametrize("cls", ["pipeline", "read"])
def test_parked_once_a_batch_and_handback_once_a_job(device_service, cls):
    before = _snap(cls)
    jobs = 6
    if cls == "read":
        futs = [device_service.submit_apply(_plan(), _block(i, 4096),
                                            job_class="read")
                for i in range(jobs)]
    else:
        futs = [device_service.submit_parity(_block(i, 4096))
                for i in range(jobs)]
    for f in futs:
        f.result(120)
        f.result(120)                     # a second result() observes nothing
    d = _moved(before, cls)
    batches = d["build"][1]
    assert 1 <= batches <= jobs
    assert d["parked"][1] == d["enqueue"][1] == batches
    assert d["handback"][1] == d["queue_wait"][1] == jobs
    assert d["parked"][0] >= 0 and d["handback"][0] >= 0


def test_host_mode_hands_back_every_job_and_parks_none():
    svc = CodecService(mode="host")
    try:
        before = _snap("pipeline")
        futs = [svc.submit_parity(_block(i, 4096)) for i in range(5)]
        for f in futs:
            f.result(60)
        d = _moved(before, "pipeline")
        assert d["handback"][1] == 5 and d["parked"][1] == 0
    finally:
        svc.close()


def test_a_read_jobs_stages_add_up_to_its_round_trip(device_service):
    """submit -> result() of one read job, alone in the service, against
    the sum of the eight stages it was observed in (best of three: a
    preempted gap between two stages is nobody's)."""
    best, plan = 0.0, _plan()
    for seed in range(3):
        block = _block(seed, 200 << 10)
        before = _snap("read")
        t0 = time.perf_counter()
        device_service.submit_apply(plan, block, job_class="read").result(120)
        wall = time.perf_counter() - t0
        d = _moved(before, "read")
        assert all(d[st][1] == 1 for st in ROUND_TRIP), d
        parts = sum(t for t, _n in d.values())
        assert parts <= wall * 1.001
        best = max(best, parts / wall)
    assert best >= 0.85


def test_a_failed_job_is_handed_back_too(monkeypatch):
    svc = CodecService(mode="host")

    def fails(batch, tags):
        raise RuntimeError("compute failed")

    monkeypatch.setattr(svc, "_compute_host", fails)
    try:
        before = _snap("pipeline")
        fut = svc.submit_parity(_block(0, 4096))
        with pytest.raises(RuntimeError, match="compute failed"):
            fut.result(60)
        assert _moved(before, "pipeline")["handback"][1] == 1
    finally:
        svc.close()


def test_job_seconds_is_gone():
    assert REGISTRY.family("seaweedfs_ec_service_job_seconds") is None
    assert "seaweedfs_ec_service_job_seconds" not in REGISTRY.render()


# -- the profiler's clock ------------------------------------------------------


@pytest.fixture
def annotations(monkeypatch):
    seen: list[str] = []
    real = trace.annotate

    def stub(name, **attrs):
        seen.append(name)
        return real(name, **attrs)

    monkeypatch.setattr(trace, "annotate", stub)
    return seen


def test_stage_asked_not_to_annotate_is_observed_and_in_the_ring(annotations):
    child = EC_SERVICE_STAGE.labels("test_only_unannotated", "read")
    with trace.start_span("volumeServer.get") as root:
        with trace.stage("ec.test.wait", child, annotated=False,
                         batch=1) as st:
            pass
        with trace.stage("ec.test.work", child):
            pass
    assert annotations == ["ec.test.work"]
    assert child.count == 2
    assert st.span.parent_id == root.span_id and st.span.attrs == {"batch": 1}


def _ec_store(tmp_path):
    """A store holding EC volume 5 without shard 0, whose needles 1-3 lie
    in shard 0's first block, and a plain volume 6."""
    from seaweedfs_tpu.storage.needle import Needle
    from seaweedfs_tpu.storage.store import Store

    store = Store([str(tmp_path)], needle_cache_mb=0)
    for vid in (5, 6):
        store.add_volume(vid, "")
        for key in (1, 2, 3):
            store.write_needle(vid, Needle(cookie=0x1234, id=key,
                                           data=bytes([key]) * 3000))
    store.generate_ec_shards(5, "")
    store.unmount_volume(5)
    os.remove(store._ec_base(5) + ".ec00")
    store.mount_ec_shards(5, "", list(range(1, 14)))
    return store


def test_volume_server_get_puts_only_working_leaves_on_the_clock(
        tmp_path, annotations):
    from seaweedfs_tpu.storage.file_id import FileId
    from seaweedfs_tpu.volume.http_handlers import VolumeHttpHandler

    store = _ec_store(tmp_path)
    vs = SimpleNamespace(store=store, guard=SimpleNamespace(networks=None),
                         ip="127.0.0.1", port=0, jwt_signing_key=None)
    handler = type("H", (VolumeHttpHandler,), {"volume_server": vs})
    srv = httpd.EventLoopHTTPServer(("127.0.0.1", 0), handler,
                                    surface="tail_volume")
    threading.Thread(target=srv.serve_forever, daemon=True).start()
    try:
        conn = http.client.HTTPConnection(*srv.server_address, timeout=30)
        conn.request("GET", "/" + str(FileId(5, 2, 0x1234)))
        resp = conn.getresponse()
        assert resp.status == 200 and resp.read() == bytes([2]) * 3000
        ec_get = list(annotations)
        annotations.clear()
        conn.request("GET", "/" + str(FileId(6, 2, 0x1234)))
        assert conn.getresponse().read() == bytes([2]) * 3000
        conn.close()
    finally:
        _stop(srv)
        store.close()
    assert "ec.degraded.gather" in ec_get
    assert not [n for n in ec_get
                if n.startswith("volumeServer.") or n == "ec.degraded.decode"]
    assert "volume.read" not in ec_get
    assert annotations.count("volume.read") == 1
    assert not [n for n in annotations if n.startswith("volumeServer.")]


def test_needle_append_is_a_volume_write_stage(tmp_path, annotations):
    from seaweedfs_tpu.storage.needle import Needle
    from seaweedfs_tpu.storage.volume import Volume

    v = Volume(str(tmp_path), "", 9)
    try:
        v.append_needle(Needle(cookie=1, id=1, data=b"x" * 100))
    finally:
        v.close()
    assert annotations == ["volume.write"]
