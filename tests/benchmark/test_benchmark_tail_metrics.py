"""The per-layer metrics that say where a GET's tail is made: the
`prom_quantile_ms` reader against fixture scrape pairs, the nine
entries and their data files, and what they read from the program's own
registry (CPU only).

The socket wait (`seaweedfs_httpd_socket_wait_seconds`) has no entry: the
chip machine's kernel (gVisor) stamps no TCP receive, so the family has no
series there and an entry would read nothing in its cell. Its reading on a
kernel that stamps is checked here through the reader with `SOCKET_WAIT`'s
arguments."""

import http.client
import json
import os
import sys
import threading
import time

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark import harness as hz  # noqa: E402
from benchmark import readers, run  # noqa: E402
from benchmark.readers import prom_mean_ms, prom_quantile_ms  # noqa: E402

BENCH = run.load_benchmark()
NEW = {
    "http_get_resident_p95_ms.live": ("live-1k-under-encode", "get_p95_ms"),
    "http_put_resident_p95_ms.live": ("live-1k-under-encode", "put_p95_ms"),
    "interpreter_wait_p95_ms.live": ("live-1k-under-encode", "get_p95_ms"),
    "http_get_resident_p95_ms.degraded": ("degraded-get-storm", "get_p95_ms"),
    "ec_degraded_decode_p95_ms.degraded": ("degraded-get-storm", "get_p95_ms"),
    "svc_read_parked_ms.degraded": ("degraded-get-storm", "get_p95_ms"),
    "svc_read_handback_ms.degraded": ("degraded-get-storm", "get_p95_ms"),
    "interpreter_wait_p95_ms.degraded": ("degraded-get-storm", "get_p95_ms"),
    "interpreter_wait_p95_ms.encode": ("ec-encode-warm", "encode_MBps"),
}

SOCKET_WAIT = "seaweedfs_httpd_socket_wait_seconds"


def _socket_wait_ms(obs, method: str, phase: str, reader) -> float | None:
    """The socket wait of `method` requests to the volume surface over
    `phase`, as an entry's data file would name it."""
    args = {"phase": phase, "name": SOCKET_WAIT,
            "labels": ['surface="volume"', f'method="{method}"']}
    if reader is prom_quantile_ms:
        args["q"] = 0.95
    return reader.read(obs, args)


FAMILY = "seaweedfs_x_seconds"
ARGS = {"phase": "read", "name": FAMILY, "labels": ['method="GET"'],
        "q": 0.95}


def _scrape(counts: dict, method: str = "GET", extra: str = "") -> dict:
    """{le: cumulative count} -> the parsed text of one histogram child."""
    lines = [f'{FAMILY}_bucket{{surface="volume",method="{method}"{extra},'
             f'le="{le}"}} {c}' for le, c in counts.items()]
    lines.append(f'{FAMILY}_count{{surface="volume",method="{method}"}} '
                 f'{counts["+Inf"]}')
    return hz.parse_metrics("\n".join(lines) + "\n")


def _obs(before: dict, after: dict) -> hz.Obs:
    obs = hz.Obs()
    obs.prom["read"] = [before, after]
    return obs


BEFORE = {"0.001": 10, "0.002": 20, "0.004": 30, "+Inf": 30}


@pytest.mark.parametrize("after,want", [
    # 100 new: 50 under 1 ms, 30 in (1, 2], 20 in (2, 4]; rank 95 lies in
    # the third bucket, 15 of its 20 in: 2 + 2 x 0.75
    ({"0.001": 60, "0.002": 100, "0.004": 130, "+Inf": 130}, 3.5),
    # rank 9.5 of 10, all in the first bucket: from 0, linearly
    ({"0.001": 20, "0.002": 20, "0.004": 30, "+Inf": 40}, 0.95),
    # rank 9.5 of 10 lies in +Inf: the highest finite bound
    ({"0.001": 10, "0.002": 20, "0.004": 31, "+Inf": 40}, 4.0),
    # no movement
    (BEFORE, None),
], ids=["inside", "first", "inf", "still"])
def test_quantile_interpolates_as_histogram_quantile(after, want):
    got = prom_quantile_ms.read(_obs(_scrape(BEFORE), _scrape(after)), ARGS)
    assert got == (None if want is None else pytest.approx(want))


def test_quantile_sums_the_series_of_one_bound_and_keeps_to_its_labels():
    before = {**_scrape(BEFORE), **_scrape(BEFORE, extra=',x="b"'),
              **_scrape({"0.001": 0, "0.002": 0, "0.004": 0, "+Inf": 0},
                        method="POST")}
    moved = {"0.001": 10, "0.002": 20, "0.004": 30, "+Inf": 40}
    after = {**_scrape(moved), **_scrape(moved, extra=',x="b"'),
             **_scrape({"0.001": 0, "0.002": 0, "0.004": 0, "+Inf": 500},
                       method="POST")}
    # 20 new GETs, every one above 4 ms: +Inf, the POSTs not counted
    assert prom_quantile_ms.read(_obs(before, after), ARGS) == 4.0


def test_quantile_of_an_absent_family_or_phase_is_none():
    other = hz.parse_metrics('seaweedfs_y_seconds_bucket{le="+Inf"} 5\n')
    assert prom_quantile_ms.read(_obs(other, other), ARGS) is None
    assert prom_quantile_ms.read(hz.Obs(), ARGS) is None
    half = hz.Obs()
    half.prom["read"] = [other, None]
    assert prom_quantile_ms.read(half, ARGS) is None


def test_the_tail_entries_are_appended():
    entries = BENCH["per_layer"]
    names = [m["name"] for m in entries]
    assert names[-len(NEW):] == list(NEW)
    layers = {m["layer"] for m in entries[:-len(NEW)]}
    for m in entries[-len(NEW):]:
        cell, moves = NEW[m["name"]]
        assert m["workloads"] == [cell] and m["moves"] == moves
        assert m["unit"] == "ms" and m["better"] == "lower"
        assert m["source"] in ("program_counter", "program_span")
        if not m["name"].startswith("interpreter_wait"):
            assert m["layer"] in layers, m["name"]
        spec = readers.metric_spec(m["name"])
        assert set(spec) == {"reader", "args"}
        want = "prom_quantile_ms" if "_p95_" in m["name"] else "prom_mean_ms"
        assert spec["reader"] == want
        if want == "prom_quantile_ms":
            assert spec["args"]["q"] == 0.95
    assert len({m["layer"] for m in entries[-len(NEW):]
                if m["name"].startswith("interpreter_wait")}) == 1


def _registry_obs(work, phases):
    from seaweedfs_tpu.stats.metrics import REGISTRY

    obs = hz.Obs()
    before = hz.parse_metrics(REGISTRY.render())
    work()
    after = hz.parse_metrics(REGISTRY.render())
    for phase in phases:
        obs.prom[phase] = [before, after]
    return obs


def test_the_live_entries_read_what_the_front_end_observes():
    """The resident and interpreter entries, and the socket wait, against the
    program's own registry, with the volume surface's event loop serving
    GETs and POSTs whose heads the loop reads 20 ms late, and the probe
    observing in this process for the length of the work."""
    from http.server import BaseHTTPRequestHandler

    from seaweedfs_tpu.stats.metrics import INTERPRETER_WAIT
    from seaweedfs_tpu.util import httpd

    if not httpd._RX_STAMPS:
        pytest.skip("Linux socket options")

    class Handler(BaseHTTPRequestHandler):
        protocol_version = "HTTP/1.1"

        def log_message(self, fmt, *args):
            pass

        def do_GET(self):
            self.send_response(200)
            self.send_header("Content-Length", "1")
            self.end_headers()
            self.wfile.write(b"z")

        do_POST = do_GET

    srv = httpd.EventLoopHTTPServer(("127.0.0.1", 0), Handler,
                                    surface="volume")
    late = srv._readable
    srv._readable = lambda conn: (time.sleep(0.02), late(conn))
    threading.Thread(target=srv.serve_forever, daemon=True).start()
    probe = INTERPRETER_WAIT.labels()

    posts = srv._wait_children["POST"][1]
    posts0 = posts.count

    def work():
        conn = http.client.HTTPConnection(*srv.server_address, timeout=10)
        for method in ("GET", "POST") * 4:
            conn.request(method, "/1,01", body=b"z" if method == "POST"
                         else None)
            conn.getresponse().read()
        conn.close()
        deadline = time.monotonic() + 10
        while time.monotonic() < deadline and posts.count < posts0 + 4:
            time.sleep(0.001)
        for _ in range(20):   # as the probe would, in a server process
            t0 = time.perf_counter()
            time.sleep(0.001)
            probe.observe(time.perf_counter() - t0 - 0.001)

    try:
        obs = _registry_obs(work, ("read", "write"))
    finally:
        srv.shutdown()
        srv.server_close()
    got = {name: readers.read_metric(name, obs) for name in NEW
           if name.endswith(".live")}
    got["socket_wait_get_ms"] = _socket_wait_ms(obs, "GET", "read",
                                                prom_mean_ms)
    got["socket_wait_get_p95_ms"] = _socket_wait_ms(obs, "GET", "read",
                                                    prom_quantile_ms)
    got["socket_wait_put_p95_ms"] = _socket_wait_ms(obs, "POST", "write",
                                                    prom_quantile_ms)
    assert all(v is not None for v in got.values()), got
    assert got["socket_wait_get_ms"] >= 20.0
    assert got["socket_wait_get_p95_ms"] >= 20.0
    assert got["socket_wait_put_p95_ms"] >= 20.0
    assert got["socket_wait_get_p95_ms"] >= 0.8 * got["socket_wait_get_ms"]
    # each p95 at least its mean, as the mean is read from _sum / _count
    for p95, mean in (("http_get_resident_p95_ms.live",
                       "http_get_resident_ms"),
                      ("http_put_resident_p95_ms.live",
                       "http_put_resident_ms")):
        assert got[p95] >= 0.8 * readers.read_metric(mean, obs), p95
    assert got["interpreter_wait_p95_ms.live"] >= 0


def test_the_degraded_entries_read_what_the_codec_service_observes():
    from seaweedfs_tpu.ops import gf256
    from seaweedfs_tpu.ops.codec_service import CodecService
    from seaweedfs_tpu.ops.rs_cpu import ReedSolomon
    from seaweedfs_tpu.stats.metrics import EC_DEGRADED_STAGE

    plan = gf256.decode_plan_for(ReedSolomon().matrix, 10,
                                 list(range(4, 14)), (0, 1, 2, 3))
    block = np.random.default_rng(3).integers(0, 256, (10, 4096),
                                              dtype=np.uint8)
    svc = CodecService(mode="device", codec_name="tpu_xor")
    decode = EC_DEGRADED_STAGE.labels("decode")
    try:
        svc.warm_reads(4, 10)

        def work():
            for _ in range(4):
                t0 = time.perf_counter()
                svc.submit_apply(plan, block, job_class="read").result(120)
                decode.observe(time.perf_counter() - t0)

        obs = _registry_obs(work, ("read",))
    finally:
        svc.close()
    for name in ("svc_read_parked_ms.degraded", "svc_read_handback_ms.degraded",
                 "ec_degraded_decode_p95_ms.degraded"):
        assert readers.read_metric(name, obs) is not None, name
    assert readers.read_metric("ec_degraded_decode_p95_ms.degraded", obs) \
        >= 0.8 * readers.read_metric("ec_degraded_decode_ms.degraded", obs)
    # a family this process has no series of in the window reads
    # nothing: left out, never 0
    assert _socket_wait_ms(obs, "GET", "read", prom_quantile_ms) is None


def test_new_metric_files_are_data_only():
    for name in NEW:
        with open(os.path.join(ROOT, "benchmark", "metrics",
                               name + ".json")) as f:
            assert set(json.load(f)) == {"reader", "args"}
