"""The served path's stages on the profiler's clock (ISSUE 27): the one
stage helper, the codec service's split waits, the pipeline's byte
counter, the event-loop front end's waits, and the benchmark metrics that
read them — all on the CPU backend."""

from __future__ import annotations

import http.client
import json
import os
import subprocess
import sys
import time
from collections import Counter
from http.server import BaseHTTPRequestHandler

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from benchmark import harness as hz  # noqa: E402
from benchmark import readers, run, trace_reduce  # noqa: E402
from helpers import empty_slice_pool  # noqa: E402
from seaweedfs_tpu.ops.codec_service import CodecService  # noqa: E402
from seaweedfs_tpu.stats.metrics import (  # noqa: E402
    EC_PIPELINE_BYTES,
    EC_SERVICE_STAGE,
    EC_SLICE_BUFFERS,
    EC_SLICE_POOL_BYTES,
    HTTPD_DISPATCH_WAIT,
    HTTPD_RESIDENT,
    REGISTRY,
)
from seaweedfs_tpu.telemetry import record_op, trace  # noqa: E402

BENCH = run.load_benchmark()
NEW_METRICS = [
    "ec_prefetch_s_per_GB.encode", "ec_prefetch_s_per_GB.rebuild",
    "svc_queue_wait_s_per_GB.encode", "svc_queue_wait_s_per_GB.rebuild",
    "svc_enqueue_s_per_GB.encode", "svc_enqueue_s_per_GB.rebuild",
    "svc_device_wait_s_per_GB.encode", "svc_device_wait_s_per_GB.rebuild",
    "svc_d2h_s_per_GB.encode", "svc_d2h_s_per_GB.rebuild",
    "http_get_dispatch_wait_ms", "http_get_resident_ms",
    "http_put_resident_ms", "http_put_server_ms",
]
SPLIT = ("ec.svc.build", "ec.svc.enqueue", "ec.svc.device_wait", "ec.svc.d2h")


def _stage(label: str):
    child = EC_SERVICE_STAGE.labels(label, "pipeline")
    return child.total, child.count


def _block(seed: int, width: int = 4096) -> np.ndarray:
    return np.random.default_rng(seed).integers(
        0, 256, (10, width), dtype=np.uint8)


@pytest.fixture(scope="module")
def device_service():
    svc = CodecService(mode="device", codec_name="tpu_xor")
    svc.submit_parity(_block(0)).result(120)  # compile outside every test
    yield svc
    svc.close()


# -- the helper ----------------------------------------------------------------


def test_stage_observes_and_keeps_child_spans_rule_for_the_ring():
    child = EC_SERVICE_STAGE.labels("test_only", "pipeline")
    tracer_before = len(trace.TRACER.spans())
    with trace.stage("ec.test.outside", child, batch=1) as st:
        pass
    assert st.span is None and child.count == 1
    assert child.total == pytest.approx(st.seconds)
    assert len(trace.TRACER.spans()) == tracer_before   # no root span
    with trace.start_span("volumeServer.get") as root:
        with trace.stage("ec.test.inside", child, batch=2) as st:
            pass
    assert child.count == 2
    assert st.span.parent_id == root.span_id
    assert st.span.attrs == {"batch": 2}


def test_stage_observes_when_the_block_raises():
    child = EC_SERVICE_STAGE.labels("test_only_raises", "pipeline")
    with pytest.raises(KeyError):
        with trace.stage("ec.test.raises", child):
            raise KeyError("x")
    assert child.count == 1


def test_stage_without_jax_imports_no_jax():
    """(d) master, filer and gateway processes never import jax for a
    span, and their histograms are observed all the same."""
    code = (
        "import sys\n"
        "from seaweedfs_tpu.stats.metrics import EC_SERVICE_STAGE, "
        "REQUEST_HISTOGRAM\n"
        "from seaweedfs_tpu.telemetry import record_op, trace\n"
        "child = EC_SERVICE_STAGE.labels('build', 'pipeline')\n"
        "with trace.stage('ec.svc.build', child, batch=1, jobs=2) as st:\n"
        "    pass\n"
        "with record_op('master', 'assign', collection='c'):\n"
        "    pass\n"
        "assert child.count == 1 and st.seconds >= 0\n"
        "assert REQUEST_HISTOGRAM.labels('master', 'assign').count == 1\n"
        "assert 'jax' not in sys.modules, 'jax imported'\n"
        "print('ok')\n")
    proc = subprocess.run([sys.executable, "-c", code], cwd=ROOT,
                          capture_output=True, text=True, timeout=120)
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert proc.stdout.strip() == "ok"


# -- the codec service under a profiler session --------------------------------


def test_profiler_session_holds_one_split_span_per_batch(
        device_service, tmp_path):
    """(a) the `/host:` plane of a real profiler session, read back with
    the benchmark's own loader."""
    import jax
    from jax.profiler import ProfileData

    from seaweedfs_tpu.pb.rpc import _traced_unary

    class Context:
        def invocation_metadata(self):
            return ()

    batches = 3

    def generate(request, context):
        for i in range(batches):   # one job a batch: each waits for its result
            device_service.submit_parity(_block(i + 1)).result(120)
        return "done"

    rpc = _traced_unary("volumeServerGrpc", "VolumeEcShardsGenerate", generate)
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0   # as benchmark/server_entry.py
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    try:
        assert rpc(None, Context()) == "done"
        with record_op("volumeServer", "get", method="GET", path="/3"):
            pass
    finally:
        jax.profiler.stop_trace()
    path = trace_reduce.find_xplane(str(tmp_path))
    host = Counter(
        name for pname, lines in trace_reduce.load_planes(path)
        if pname.startswith("/host:")
        for _lname, events in lines for name, _s, _d in events)
    for name in SPLIT:
        assert host[name] == batches, (name, host)
    # (a result is handed over inside `deliver`, which closes just after)
    assert batches - 1 <= host["ec.svc.deliver"] <= batches
    assert host["volumeServer.get"] == 1          # request spans are bridged
    assert not [n for n in host if "VolumeEcShardsGenerate" in n]  # enclosing
    seen = {name: [] for name in SPLIT}
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name in seen:
                    stats = dict(ev.stats)
                    assert stats["jobs"] == 1 and stats["bytes"] == 10 * 4096
                    seen[ev.name].append(stats["batch"])
    first = seen["ec.svc.build"]
    assert len(set(first)) == batches   # a sequence number of its own each
    for name in SPLIT:
        assert sorted(seen[name]) == sorted(first), name


def test_split_adds_up_to_the_stages_the_benchmark_reads(device_service):
    """(c) enqueue == compute, device_wait + d2h == readback, and one
    queue_wait observation per job."""
    labels = ("queue_wait", "build", "enqueue", "device_wait", "d2h",
              "deliver", "compute", "readback")
    before = {lb: _stage(lb) for lb in labels}
    jobs = 5
    futs = [device_service.submit_parity(_block(20 + i)) for i in range(jobs)]
    for f in futs:
        f.result(120)
    d = {lb: (_stage(lb)[0] - before[lb][0], _stage(lb)[1] - before[lb][1])
         for lb in labels}
    assert d["queue_wait"][1] == jobs
    batches = d["build"][1]
    assert 1 <= batches <= jobs
    for lb in ("enqueue", "device_wait", "d2h", "compute", "readback"):
        assert d[lb][1] == batches, lb
    # (a result is handed over inside `deliver`, which closes just after)
    assert batches - 1 <= d["deliver"][1] <= batches
    assert d["enqueue"][0] == pytest.approx(d["compute"][0], rel=0.01)
    assert d["device_wait"][0] + d["d2h"][0] == pytest.approx(
        d["readback"][0], rel=0.01)
    assert d["readback"][0] > 0


def test_program_name_on_the_device_plane():
    """The codec service's jitted GF program, over V jobs' arrays, says
    what it is and its matrix shape, whatever V."""
    from seaweedfs_tpu.parallel.mesh import (
        _rows_of,
        _sharded_apply_jobs,
        make_mesh,
    )

    rows = _rows_of(np.arange(1, 41, dtype=np.uint8).reshape(4, 10))
    mesh = make_mesh()
    for n in (1, 2):
        text = _sharded_apply_jobs(mesh, rows, n).lower(
            *[np.zeros((10, 8 * mesh.size, 128), np.uint32)] * n).as_text()
        assert "jit_gf_apply_r4_s10" in text


# -- the reduction names a gap by the program's span ---------------------------

MS = 1_000_000


def test_gap_is_labelled_by_the_programs_span():
    """(b) synthetic planes with the program's span names through the
    benchmark's reduction, untouched."""
    device = ("/device:TPU:0", [
        ("XLA Modules", [("jit_gf_apply_r4_s10(1)", 0, 10 * MS),
                         ("jit_gf_apply_r4_s10(1)", 110 * MS, 10 * MS)]),
        ("XLA Ops", [("%pad_add_fusion = u8[] fusion()", 0, 10 * MS),
                     ("%xor_xor_fusion = u8[] fusion()", 110 * MS, 10 * MS)]),
    ])
    # the gap is [10, 110) ms: 20 of d2h, 50 of build (10 of them under a
    # prefetch on another thread), 10 of enqueue, 20 that nothing covers
    host = ("/host:CPU", [
        ("ec-codec-service", [
            ("ec.svc.d2h", 10 * MS, 20 * MS),
            ("np.asarray(jax.Array)", 11 * MS, 18 * MS),
            ("ec.svc.build", 40 * MS, 50 * MS),
            ("ec.svc.enqueue", 90 * MS, 10 * MS)]),
        ("ec-encode-prefetch", [("ec.pipeline.prefetch", 35 * MS, 15 * MS)]),
    ])
    out = trace_reduce.reduce_planes([device, host])
    (label, seconds), = out["idle_gaps"]
    assert seconds == pytest.approx(0.100)
    assert label == ("ec.svc.build 50%, no host span 15%; "
                     "after pad_add_fusion")
    assert out["modules"] == [["jit_gf_apply_r4_s10", pytest.approx(0.020)]]


# -- the pipeline's byte counter -----------------------------------------------


def test_pipeline_bytes_write_is_1_4_times_prefetch(tmp_path):
    """(f) work counted where it is done: an encode writes 14 bytes for
    every 10 it brings in (the zero fill of the last row included)."""
    from seaweedfs_tpu.storage.ec.encoder import (
        rebuild_ec_files,
        write_ec_files,
    )

    base = str(tmp_path / "7")
    dat = np.random.default_rng(3).integers(
        0, 256, (2 << 20) + 12345, dtype=np.uint8)
    dat.tofile(base + ".dat")
    pre, wr = (EC_PIPELINE_BYTES.labels(s) for s in ("prefetch", "write"))
    p0, w0 = pre.value, wr.value
    # a device codec on the CPU backend takes the pipelined path
    write_ec_files(base, codec_name="tpu_xor", slice_size=1 << 19)
    read, written = pre.value - p0, wr.value - w0
    assert read == 10 << 20                       # one 1 MiB row, zero filled
    assert 0 <= read - dat.nbytes < 10 << 20      # = the .dat up to the padding
    assert written == 14 << 20 == 1.4 * read
    assert written == sum(os.path.getsize(f"{base}.ec{i:02d}")
                          for i in range(14))
    # a rebuild reads ten survivors for the shards it re-makes
    for sid in (0, 11):
        os.remove(f"{base}.ec{sid:02d}")
    p0, w0 = pre.value, wr.value
    assert rebuild_ec_files(base, codec_name="tpu_xor",
                            slice_size=1 << 19) == [0, 11]
    assert (pre.value - p0, wr.value - w0) == (10 << 20, 2 << 20)


# -- the pipelines' slice buffers (ISSUE 34) -----------------------------------


def test_prefetch_span_and_counter_say_where_a_slice_buffer_came_from(
        tmp_path, monkeypatch):
    """The `ec.pipeline.prefetch` span's `buffer`, the counter's two
    labels, the gauge, and the one benchmark metric that reads them."""
    from seaweedfs_tpu.storage.ec import encoder

    empty_slice_pool(monkeypatch)
    seen = []
    real_stage = trace.stage
    monkeypatch.setattr(trace, "stage", lambda name, hist=None, **attrs: (
        seen.append((name, attrs)), real_stage(name, hist, **attrs))[1])
    base = str(tmp_path / "7")
    np.random.default_rng(5).integers(
        0, 256, 3 << 20, dtype=np.uint8).tofile(base + ".dat")
    counts = {(p, s): EC_SLICE_BUFFERS.labels(p, s)
              for p in ("encode", "rebuild") for s in ("pooled", "fresh")}
    before = {k: c.value for k, c in counts.items()}

    def work():
        encoder.write_ec_files(base, codec_name="tpu_xor", slice_size=1 << 18)
        os.remove(base + ".ec05")
        encoder.rebuild_ec_files(base, codec_name="tpu_xor",
                                 slice_size=1 << 18)

    obs = _obs_over(work, ("window",))
    took = {k: c.value - before[k] for k, c in counts.items()}
    buffers = [attrs["buffer"] for name, attrs in seen
               if name == "ec.pipeline.prefetch"]
    assert all("buffer" not in attrs for name, attrs in seen
               if name != "ec.pipeline.prefetch")
    # four slices, each of four 1 MiB rows a quarter wide, both ways
    assert len(buffers) == 8 and set(buffers) == {"pooled", "fresh"}
    assert Counter(buffers) == {
        "fresh": took["encode", "fresh"] + took["rebuild", "fresh"],
        "pooled": took["encode", "pooled"] + took["rebuild", "pooled"]}
    assert 1 <= took["encode", "fresh"] <= encoder._POOL_SLICES
    assert took["encode", "fresh"] + took["encode", "pooled"] == 4
    assert took["rebuild", "fresh"] == 0 and took["rebuild", "pooled"] == 4
    free = EC_SLICE_POOL_BYTES.labels().value
    assert free == took["encode", "fresh"] * 10 * (1 << 18)
    assert 'seaweedfs_ec_slice_buffers_total{pipeline="encode",source="fresh"}' \
        in REGISTRY.render()
    entry = next(m for m in BENCH["per_layer"]
                 if m["name"] == "ec_slice_fresh_pct.encode")
    assert entry == {
        "name": "ec_slice_fresh_pct.encode", "unit": "%", "better": "lower",
        "source": "program_counter", "moves": "encode_MBps",
        "layer": next(m["layer"] for m in BENCH["per_layer"]
                      if m["name"] == "ec_prefetch_s_per_GB.encode"),
        "workloads": ["ec-encode-warm"]}
    spec = readers.metric_spec(entry["name"])
    assert set(spec) == {"reader", "args"} and spec["reader"] == "prom_ratio"
    assert readers.read_metric(entry["name"], obs) == pytest.approx(
        100.0 * took["encode", "fresh"] / 8)


def test_slice_fresh_pct_is_left_out_by_a_program_without_the_counter():
    """The parent has no such family: the reader finds nothing and the
    line leaves the metric out."""
    obs = hz.Obs()
    scrape = hz.parse_metrics(
        'seaweedfs_ec_pipeline_bytes_total{stage="prefetch"} 5\n')
    obs.prom["window"] = [scrape, dict(scrape)]
    assert readers.read_metric("ec_slice_fresh_pct.encode", obs) is None


# -- the codec service's readbacks (ISSUE 38) ----------------------------------


def test_readback_ready_pct_reads_the_services_counter(device_service):
    """`svc_readback_ready_pct.batch4`: the last entry of `per_layer`, a
    data file for the reader the benchmark has, 100 x `ready` / all of the
    family the service moves once a device batch."""
    from seaweedfs_tpu.stats.metrics import EC_SERVICE_READBACKS

    counts = {st: EC_SERVICE_READBACKS.labels(st, "pipeline")
              for st in ("ready", "waited")}
    before = {st: c.value for st, c in counts.items()}

    def work():
        for seed in range(5):
            device_service.submit_parity(_block(seed)).result(120)

    obs = _obs_over(work, ("window",))
    moved = {st: c.value - before[st] for st, c in counts.items()}
    assert sum(moved.values()) == 5
    for state in moved:
        assert ('seaweedfs_ec_service_readbacks_total{state="%s",'
                'class="pipeline"}' % state) in REGISTRY.render()
    entry = BENCH["per_layer"][-1]
    assert entry == {
        "name": "svc_readback_ready_pct.batch4", "unit": "%",
        "better": "higher", "source": "program_counter",
        "moves": "encode_MBps",
        "layer": next(m["layer"] for m in BENCH["per_layer"]
                      if m["name"] == "svc_d2h_s_per_GB.batch4"),
        "workloads": ["ec-batch-4chip"]}
    spec = readers.metric_spec(entry["name"])
    assert set(spec) == {"reader", "args"} and spec["reader"] == "prom_ratio"
    assert readers.read_metric(entry["name"], obs) == pytest.approx(
        100.0 * moved["ready"] / 5)


def test_readback_ready_pct_is_left_out_by_a_program_without_the_counter():
    """The parent has no such family: the reader finds nothing and the
    line leaves the metric out.  A window in which every batch was waited
    for reads 0."""
    obs = hz.Obs()
    scrape = hz.parse_metrics(
        'seaweedfs_ec_service_batch_jobs_count{class="pipeline"} 5\n')
    obs.prom["window"] = [scrape, dict(scrape)]
    assert readers.read_metric("svc_readback_ready_pct.batch4", obs) is None
    series = 'seaweedfs_ec_service_readbacks_total{state="%s",class="pipeline"} %d\n'
    obs.prom["window"] = [
        hz.parse_metrics(series % ("ready", 0) + series % ("waited", 2)),
        hz.parse_metrics(series % ("ready", 0) + series % ("waited", 9))]
    assert readers.read_metric("svc_readback_ready_pct.batch4", obs) == 0.0


# -- the event-loop front end --------------------------------------------------


class _Handler(BaseHTTPRequestHandler):
    protocol_version = "HTTP/1.1"

    def log_message(self, fmt, *args):
        pass

    def _reply(self):
        body = b"x" * 1024
        self.send_response(200)
        self.send_header("Content-Length", str(len(body)))
        self.end_headers()
        self.wfile.write(body)

    def do_GET(self):
        self._reply()

    def do_POST(self):
        self.rfile.read(int(self.headers.get("Content-Length") or 0))
        self._reply()


def test_front_end_counts_dispatch_wait_and_resident_per_method():
    """(e) N keep-alive GETs and POSTs: resident counts each, and no
    request waits for its worker longer than it is resident."""
    import threading

    from seaweedfs_tpu.util.httpd import EventLoopHTTPServer

    surface = "stage_spans_test"
    srv = EventLoopHTTPServer(("127.0.0.1", 0), _Handler, surface=surface)
    t = threading.Thread(target=srv.serve_forever, daemon=True)
    t.start()
    try:
        conn = http.client.HTTPConnection(*srv.server_address, timeout=10)
        n = 7
        for method, body in (("GET", None), ("POST", b"y" * 1024)):
            wait = HTTPD_DISPATCH_WAIT.labels(surface, method)
            resident = HTTPD_RESIDENT.labels(surface, method)
            for i in range(n):
                w0, r0 = wait.total, resident.total
                conn.request(method, f"/{i}", body=body)
                assert conn.getresponse().read() == b"x" * 1024
                # the worker observes after the flush the client waited for
                deadline = time.monotonic() + 10
                while resident.count < i + 1 and time.monotonic() < deadline:
                    time.sleep(0.001)
                assert wait.count == resident.count == i + 1
                assert 0 <= wait.total - w0 <= resident.total - r0
        # a method the handler class does not serve is counted as "other"
        conn.request("BREW", "/pot")
        assert conn.getresponse().status == 501
        conn.close()
        conn = http.client.HTTPConnection(*srv.server_address, timeout=10)
        conn.request("GET", "/after")
        conn.getresponse().read()
        assert HTTPD_RESIDENT.labels(surface, "other").count == 1
        text = REGISTRY.render(["seaweedfs_httpd_resident"])
        assert f'surface="{surface}",method="BREW"' not in text
        conn.close()
    finally:
        srv.shutdown()
        srv.server_close()


# -- the benchmark metrics that read all this ----------------------------------


def test_benchmark_names_the_fourteen_new_metrics():
    entries = {m["name"]: m for m in BENCH["per_layer"]}
    # PR 25's fourteen, then these fourteen; later PRs append after them
    assert [m["name"] for m in BENCH["per_layer"]][14:28] == NEW_METRICS
    layers = {m["layer"] for m in BENCH["per_layer"][:14]}
    for name in NEW_METRICS:
        m = entries[name]
        assert m["source"] == "program_span" and m["layer"] in layers
        spec = readers.metric_spec(name)
        assert spec["reader"] in ("prom_sum_per_gb", "prom_mean_ms")


def _obs_over(fn, phases) -> hz.Obs:
    """An Obs whose scrapes are this process's own registry around fn."""
    obs = hz.Obs()
    before = hz.parse_metrics(REGISTRY.render())
    fn()
    after = hz.parse_metrics(REGISTRY.render())
    for phase in phases:
        obs.prom[phase] = [before, after]
    return obs


def test_new_ec_metrics_read_what_the_program_observes(
        device_service, tmp_path):
    """Each new svc_* / ec_prefetch_* data file names series the program
    really exposes, and per GB the split adds up to svc_devwait."""
    from seaweedfs_tpu.storage.ec.encoder import (
        rebuild_ec_files,
        write_ec_files,
    )

    base = str(tmp_path / "9")
    np.random.default_rng(4).integers(
        0, 256, 3 << 20, dtype=np.uint8).tofile(base + ".dat")

    def work():
        write_ec_files(base, codec_name="tpu_xor", slice_size=1 << 18,
                       service=device_service)
        os.remove(base + ".ec03")
        rebuild_ec_files(base, codec_name="tpu_xor", slice_size=1 << 18,
                         service=device_service)

    obs = _obs_over(work, ("window",))
    got = {name: readers.read_metric(name, obs) for name in NEW_METRICS[:10]}
    assert all(v is not None and v > 0 for v in got.values()), got
    for cell in ("encode", "rebuild"):
        whole = readers.read_metric(f"svc_devwait_s_per_GB.{cell}", obs)
        parts = sum(got[f"svc_{part}_s_per_GB.{cell}"]
                    for part in ("enqueue", "device_wait", "d2h"))
        assert parts == pytest.approx(whole, rel=0.02)


def test_new_http_metrics_read_what_the_program_observes():
    import threading

    from seaweedfs_tpu.stats.metrics import REQUEST_HISTOGRAM
    from seaweedfs_tpu.util.httpd import EventLoopHTTPServer

    srv = EventLoopHTTPServer(("127.0.0.1", 0), _Handler, surface="volume")
    threading.Thread(target=srv.serve_forever, daemon=True).start()

    def work():
        conn = http.client.HTTPConnection(*srv.server_address, timeout=10)
        for method in ("GET", "POST", "GET"):
            conn.request(method, "/1,01", body=b"z" if method == "POST"
                         else None)
            conn.getresponse().read()
        conn.close()
        REQUEST_HISTOGRAM.labels("volumeServer", "post").observe(0.002)

    try:
        obs = _obs_over(work, ("read", "write"))
    finally:
        srv.shutdown()
        srv.server_close()
    got = {name: readers.read_metric(name, obs) for name in NEW_METRICS[10:]}
    assert all(v is not None and v > 0 for v in got.values()), got
    assert got["http_get_dispatch_wait_ms"] <= got["http_get_resident_ms"]
    assert got["http_put_server_ms"] == pytest.approx(2.0)


def test_new_metric_files_are_data_only():
    for name in NEW_METRICS:
        with open(os.path.join(ROOT, "benchmark", "metrics",
                               name + ".json")) as f:
            spec = json.load(f)
        assert set(spec) == {"reader", "args"}
