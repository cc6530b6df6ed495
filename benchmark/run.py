#!/usr/bin/env python3
"""Run one cell of BENCHMARK.json once and print its result line.

    python benchmark/run.py --workload NAME --seed N --seconds S --trace 0|1

Everything that belongs to one cell is found by name: the cell's entry in
BENCHMARK.json gives its configuration (benchmark/configs/<config>.json)
and its traffic mix (benchmark/traffic/<traffic>.json, whose "driver" is
a module in benchmark/drivers/); each metric is benchmark/metrics/<name>
.json with a reader in benchmark/readers/.  This file holds no table of
cells or metrics.

The last line of stdout is the result; progress and, last of all, every
number compared beside its limit go to stderr.  Exit 0 with a result;
another code and no result when there is no TPU with the chips the cell
asks for, or the run could not be made.  `--control NAME` plants a fault
(benchmark/faults.py) and must end `correct: false`; `--rehearse CODEC`
walks the whole path on whatever backend there is (the result then says
`correct: false` for want of a TPU).
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import sys
import threading
import time
import traceback
import zlib

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmark import harness as hz  # noqa: E402  (first: starts the clock)
from benchmark import drivers, readers  # noqa: E402
from benchmark.faults import Fault  # noqa: E402

DEADLINE_S = 345.0  # the driver allows 360 s for a run


def load_json(*parts: str) -> dict:
    with open(os.path.join(hz.BENCH_DIR, *parts)) as f:
        return json.load(f)


def load_benchmark() -> dict:
    with open(os.path.join(hz.ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def cell_metrics(bench: dict, cell: str, traced: bool) -> list:
    """The entries of `end_to_end` (untraced) or `per_layer` (traced) this
    cell reports: those that list it, and those that list no cells if the
    cell reports the metric they move."""
    e2e = [m for m in bench["end_to_end"]
           if "workloads" not in m or cell in m["workloads"]]
    if not traced:
        return e2e
    mine = {m["name"] for m in e2e}
    return [m for m in bench["per_layer"]
            if (cell in m["workloads"] if "workloads" in m
                else m["moves"] in mine)]


class Run:
    """What the drivers of one run share."""

    def __init__(self, cell: str, config: dict, seed: int, work_dir: str,
                 fault: Fault):
        self.cell, self.config, self.seed = cell, config, seed
        self.work_dir = work_dir
        self.dirs = [os.path.join(work_dir, f"d{i}")
                     for i in range(config.get("data_dirs", 1))]
        self.ref_dir = os.path.join(work_dir, "ref")
        for d in self.dirs + [self.ref_dir]:
            os.makedirs(d)
        self.fault = fault
        self.obs = hz.Obs()
        self.compared: dict = {}
        self._vid = 0

    def compare(self, name: str, value, limit=0) -> None:
        """Add to a number compared (several drivers may feed one); a
        value of None means there was nothing to compare, which fails."""
        have = self.compared.get(name)
        if have and have["value"] is not None and value is not None:
            value += have["value"]
        self.compared[name] = {"value": value, "limit": limit}

    def alloc_vid(self) -> int:
        self._vid += 1
        return self._vid

    def rng(self, name: str):
        import numpy as np

        return np.random.default_rng([self.seed, zlib.crc32(name.encode())])


class Tracer:
    """Starts and stops the profiler in the server around a slice of the
    window: one whole timed rpc (`{"mode": "rpc", "index": k}`: the first
    rpc at or after `k` that did work; one that found nothing to do is
    dropped and the next is traced), the whole window (`{"mode":
    "window"}`), or a stretch of time (`{"mode": "time", "start_s": a,
    "length_s": b}`).  The first two hold whole jobs, so the service's byte
    counter is exact for the slice.
    """

    def __init__(self, spec: dict, cluster, obs):
        self.spec, self.cluster, self.obs = spec, cluster, obs
        self.window_s = None
        self._t = None
        self._thread = None
        if spec["mode"] == "rpc":
            obs.rpc_listeners.append(self._on_rpc)

    def _start(self) -> None:
        if self._t is not None:
            return
        self.obs.prom_begin("trace")
        took = self.cluster.control("trace_start")["seconds"]
        self._t = time.monotonic()
        hz.say(f"profiler started in {took:.2f}s")

    def _stop(self) -> None:
        if self._t is None or self.window_s is not None:
            return
        self.window_s = time.monotonic() - self._t
        took = self.cluster.control("trace_stop", 300.0)["seconds"]
        self.obs.prom_end("trace")
        hz.say(f"profiler stopped after a {self.window_s:.2f}s slice "
               f"(stop took {took:.2f}s)")

    def _drop(self) -> None:
        """The traced rpc did no work: its slice holds no device plane and
        is thrown away, so that the next start records a slice of its own."""
        if self._t is None or self.window_s is not None:
            return
        self.cluster.control("trace_stop", 300.0)
        shutil.rmtree(os.path.join(self.cluster.control_dir, "trace"),
                      ignore_errors=True)
        self._t = None
        hz.say("the traced rpc found nothing to do: slice dropped, the "
               "next rpc is traced")

    def _on_rpc(self, index: int, edge: str) -> None:
        if index < self.spec["index"]:
            return
        {"start": self._start, "end": self._stop, "idle": self._drop}[edge]()

    def window_opens(self) -> None:
        if self.spec["mode"] == "window":
            self._start()
        elif self.spec["mode"] == "time":
            def timed():
                time.sleep(self.spec["start_s"])
                self._start()
                time.sleep(self.spec["length_s"])
                self._stop()
            self._thread = threading.Thread(target=timed, daemon=True)
            self._thread.start()

    def window_closed(self) -> None:
        if self._thread is not None:
            self._thread.join()
        self._stop()


def reduce_trace(control_dir: str, log_dir: str) -> "dict | None":
    """trace_reduce.py in a child (it imports jax), after the server has
    gone."""
    trace_dir = os.path.join(control_dir, "trace")
    if not os.path.isdir(trace_dir):
        return None
    from benchmark.trace_reduce import find_xplane

    xplane = find_xplane(trace_dir)
    if xplane:   # kept for reading by hand; well under a megabyte per rpc
        shutil.copy(xplane, os.path.join(log_dir, "trace.xplane.pb"))
    out_path = os.path.join(log_dir, "trace_reduce.json")
    with open(out_path, "wb") as out:
        proc = hz.spawn(
            [sys.executable, os.path.join(hz.BENCH_DIR, "trace_reduce.py"),
             trace_dir, os.path.join(log_dir, "trace_planes.txt")],
            os.path.join(log_dir, "trace_reduce.err"), stdout=out,
            env_extra={"JAX_PLATFORMS": "cpu"})
        rc = proc.wait()
    if rc != 0:
        hz.say("trace_reduce.py failed:\n" + hz.log_tail(
            os.path.join(log_dir, "trace_reduce.err")))
        return None
    with open(out_path) as f:
        return json.loads(f.read().strip().splitlines()[-1])


def run_cell(cell: str, chips: int, config: dict, traffic: dict,
             metric_entries: list, seed: int, seconds: float, traced: bool,
             require_tpu: bool = True, control: "str | None" = None,
             tag: str = "") -> "tuple[str, dict]":
    """One run of one cell -> (result line, numbers compared).  Raises
    BenchFailure where no result can be given."""
    work_dir = hz.fresh_dir(os.path.join(hz.WORK_ROOT, cell + tag))
    log_dir = hz.fresh_dir(os.path.join(
        hz.LOG_ROOT, f"{cell}{tag}-seed{seed}-trace{int(traced)}"))
    control_dir = os.path.join(work_dir, "control")
    os.makedirs(control_dir)
    run = Run(cell, config, seed, work_dir, Fault(control))
    obs, compared = run.obs, run.compared
    cluster = None
    try:
        driver = drivers.make(traffic, run)
        t = time.monotonic()
        driver.prepare()
        hz.say(f"data built in {time.monotonic() - t:.2f}s")

        t = time.monotonic()
        cluster = hz.Cluster(run.dirs, config["codec"], log_dir,
                             config.get("server_env"), traced, control_dir)
        obs.cluster = cluster
        status = cluster.wait_ready()
        held = status["ec"].get("device") or {
            "platform": "", "kind": "", "count": 0}
        hz.say(f"server up in {time.monotonic() - t:.2f}s: /status ec = "
               f"{json.dumps(status['ec'])}")
        on_tpu = held["platform"] == "tpu" and held["count"] >= chips
        if require_tpu and not on_tpu:
            raise hz.BenchFailure(
                f"need {chips} tpu chip(s); the server holds {held}")
        obs.device = {k: held[k] for k in ("platform", "kind", "count")}
        obs.peaks = load_json("peaks.json").get(held["kind"], {})
        if on_tpu and not obs.peaks:
            raise hz.BenchFailure(f"no peaks for device kind {held['kind']!r}")

        t = time.monotonic()
        driver.warm(cluster)
        hz.say(f"warm-up took {time.monotonic() - t:.2f}s")
        tracer = Tracer(traffic["trace"], cluster, obs) if traced else None

        # -- the window ------------------------------------------------------
        cache0 = cluster.status()["ec"].get("compileCache", {})
        obs.prom_begin("window")
        obs.work["setup_s"] = time.monotonic() - hz.T0
        run.fault.armed = True
        if tracer:
            tracer.window_opens()
        driver.run_window(cluster, seconds)
        if tracer:
            tracer.window_closed()
        obs.prom_end("window")
        end = cluster.status()["ec"]
        cache1 = end.get("compileCache", {})
        hz.say(f"window closed; server /status ec = {json.dumps(end)}")

        def d(name, *bits):
            return obs.delta("window", name, *bits)

        compared["not_on_tpu"] = {"value": 0 if on_tpu else 1, "limit": 0}
        compared["host_codec_ops"] = {
            "value": d("seaweedfs_ec_op_seconds_count", 'impl="cpu"'),
            "limit": 0}
        compared["service_jobs_failed"] = {
            "value": d("seaweedfs_ec_service_jobs_total", 'result="error"'),
            "limit": 0}
        compared["window_without_device_batches"] = {
            "value": 0 if d("seaweedfs_ec_service_stage_seconds_count",
                            'stage="readback"') > 0 else 1, "limit": 0}
        compared["compiles_in_window"] = {
            "value": cache1.get("misses", 0) - cache0.get("misses", 0),
            "limit": 0}
        hz.say(f"compile cache over the server's life: {json.dumps(cache1)}; "
               f"service input bytes in the window: "
               f"{d('seaweedfs_ec_service_batch_bytes_sum'):.0f}")
        device = dict(obs.device)
        device["memory_peak_bytes"] = int(end.get("hbmPeakBytes", 0))

        # -- outputs, while the server still answers ---------------------------
        t = time.monotonic()
        driver.check_live(cluster)
        cluster.stop()
        if traced:
            obs.trace = reduce_trace(control_dir, log_dir)
            if obs.trace and tracer.window_s:
                obs.trace["trace_span_s"] = obs.trace.get("window_s")
                obs.trace["window_s"] = tracer.window_s
        driver.check_files()
        hz.say(f"outputs checked in {time.monotonic() - t:.2f}s "
               f"(control fired {run.fault.fired}x)" if control else
               f"outputs checked in {time.monotonic() - t:.2f}s")
    finally:
        if cluster is not None:
            cluster.stop()
        shutil.rmtree(work_dir, ignore_errors=True)

    metrics = {}
    for m in metric_entries:
        value = readers.read_metric(m["name"], obs)
        if value is None:
            if not traced:
                raise hz.BenchFailure(f"no reading for {m['name']}")
            hz.say(f"no reading for {m['name']}: left out")
            continue
        metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    breakdown = None
    if traced:
        tr = obs.trace or {}
        if tr.get("busy_s") is not None:
            device["busy_s"] = tr["busy_s"]
            device["window_s"] = tr["window_s"]
            breakdown = {"device_ops": tr.get("device_ops", []),
                         "idle_gaps": tr.get("idle_gaps", [])}
            hz.say(f"trace: {json.dumps({k: tr[k] for k in tr if k not in ('device_ops', 'idle_gaps')})}")
        elif on_tpu:
            raise hz.BenchFailure("the traced run holds no device plane")
    line = hz.result_line(obs.attempted, obs.failed, metrics, device,
                          compared, breakdown)
    return line, compared


def main(argv: "list | None" = None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", default=None)
    ap.add_argument("--rehearse", default=None, metavar="CODEC",
                    help="walk the whole path off-TPU with this -ec.codec "
                    "(tpu_xor runs on a CPU backend); never `correct`")
    args = ap.parse_args(argv)

    def too_long():
        hz.say(f"FAIL: not done after {DEADLINE_S:.0f}s")
        hz.finish(None, None, 4)

    watchdog = threading.Timer(DEADLINE_S, too_long)
    watchdog.daemon = True
    watchdog.start()
    try:
        bench = load_benchmark()
        cell = next((w for w in bench["workloads"]
                     if w["name"] == args.workload), None)
        if cell is None:
            raise hz.BenchFailure(f"no cell {args.workload!r} in BENCHMARK.json")
        cfg_entry = next(c for c in bench["configs"]
                         if c["name"] == cell["config"])
        with open(os.path.join(hz.ROOT, cfg_entry["file"])) as f:
            config = json.load(f)
        traffic = load_json("traffic", cell["traffic"] + ".json")
        if args.rehearse:
            config["codec"] = args.rehearse
        line, compared = run_cell(
            cell["name"], cell["chips"], config, traffic,
            cell_metrics(bench, cell["name"], bool(args.trace)),
            args.seed, args.seconds, bool(args.trace),
            require_tpu=args.rehearse is None, control=args.control)
    except BaseException as e:  # noqa: BLE001 — no result, whatever it was
        if not isinstance(e, hz.BenchFailure):
            traceback.print_exc()
        hz.say(f"FAIL: {type(e).__name__}: {e}")
        hz.finish(None, None, 3)
    if "jax" in sys.modules:
        hz.say("FAIL: the harness process imported jax")
        hz.finish(None, None, 5)
    hz.finish(line, compared, 0)


if __name__ == "__main__":
    main()
