"""Flight recorder + heavy-hitter attribution suite (ISSUE 20).

Tier-1: the space-saving sketch's guarantees, hot-key window rotation
and cardinality bounds, and the full bundle journey on an in-process
master + volume cluster (manual capture over HTTP, listing, retention,
traversal guard, single-flight 409, /cluster/hot federation).

Chaos (slow): SIGKILL a volume-holding node under zipf-hot canary load
— the availability page fires and the flight recorder auto-captures a
bundle that covers every live node, pins the alert's exemplar trace,
and names the zipf-hot needle in the hot-key tables, while the client
load sees zero 5xx.
"""

from __future__ import annotations

import json
import os
import signal
import subprocess
import sys
import threading
import time
import urllib.error
import urllib.request

import pytest

from helpers import free_port

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _get_json(url, timeout=10):
    with urllib.request.urlopen(url, timeout=timeout) as r:
        return json.loads(r.read())


def _wait(cond, deadline_s, what):
    deadline = time.time() + deadline_s
    while time.time() < deadline:
        if cond():
            return
        time.sleep(0.2)
    raise TimeoutError(what)


# -- space-saving sketch -----------------------------------------------------


def test_space_saving_heavy_hitter_guarantee():
    from seaweedfs_tpu.telemetry.hotkeys import SpaceSaving

    s = SpaceSaving(k=8)
    # 1000 hits on the hot key buried in 500 distinct cold keys: any key
    # with true frequency > N/k must survive, with count exact to within
    # its reported error
    for i in range(500):
        s.record(f"cold-{i}")
        s.record("hot", 2)
    assert len(s) <= 8
    top = s.top(1)[0]
    assert top["key"] == "hot"
    assert top["count"] - top["error"] <= 1000 <= top["count"]


def test_space_saving_eviction_inherits_error():
    from seaweedfs_tpu.telemetry.hotkeys import SpaceSaving

    s = SpaceSaving(k=2)
    s.record("a", 5)
    s.record("b", 3)
    s.record("c")  # evicts b (min=3); c inherits 3 as its error floor
    entries = {e["key"]: e for e in s.top()}
    assert set(entries) == {"a", "c"}
    assert entries["c"]["count"] == 4 and entries["c"]["error"] == 3
    assert entries["a"]["error"] == 0


def test_hotkey_recorder_window_rotation_and_gauge_bound():
    from seaweedfs_tpu.stats.metrics import HOTKEY_TOP
    from seaweedfs_tpu.telemetry.hotkeys import (
        DIMENSIONS,
        TOP_GAUGE_KEYS,
        HotKeyRecorder,
    )

    r = HotKeyRecorder(k=16, window_s=0.1)
    for i in range(40):
        r.record("needle", f"3,{i:08x}")
    r.record("bucket", "photos", 7)
    snap = r.snapshot()
    assert snap["dims"]["bucket"]["current"][0]["key"] == "photos"
    time.sleep(0.15)
    snap = r.snapshot()  # lazy rotation on read
    assert snap["dims"]["bucket"]["previous"][0]["key"] == "photos"
    assert snap["dims"]["bucket"]["current"] == []
    # rotation republished the gauge children wholesale: hard bound
    with HOTKEY_TOP._lock:
        children = len(HOTKEY_TOP._children)
    assert children <= len(DIMENSIONS) * TOP_GAUGE_KEYS


def test_hotkeys_kill_switch(monkeypatch):
    from seaweedfs_tpu.telemetry import hotkeys

    monkeypatch.setenv(hotkeys.DISABLE_VAR, "0")
    hotkeys.reset()
    try:
        hotkeys.record("needle", "3,01010101")
        snap = hotkeys.snapshot()
        assert snap["enabled"] is False
        assert snap["dims"]["needle"]["current"] == []
    finally:
        hotkeys.reset()


# -- bundle journey on an in-process cluster ---------------------------------


def test_flight_recorder_bundle_journey(tmp_path, monkeypatch):
    from seaweedfs_tpu.master.server import MasterServer
    from seaweedfs_tpu.telemetry import hotkeys
    from seaweedfs_tpu.volume.server import VolumeServer

    monkeypatch.setenv("SEAWEEDFS_TPU_DEBUG_BUNDLE_RETAIN", "2")
    hotkeys.reset()
    debug_dir = tmp_path / "debug-bundles"
    master = MasterServer(ip="127.0.0.1", port=free_port(),
                          pulse_seconds=0.5, debug_dir=str(debug_dir))
    master.start()
    vol_dir = tmp_path / "vol"
    vol_dir.mkdir()
    vs = VolumeServer(
        directories=[str(vol_dir)],
        master_addresses=[f"127.0.0.1:{master.grpc_port}"],
        ip="127.0.0.1", port=free_port(), pulse_seconds=0.5,
        max_volume_count=8)
    vs.start()
    base = f"http://127.0.0.1:{master.port}"
    try:
        _wait(lambda: master.topo.nodes, 15, "node registered")
        _get_json(f"{base}/vol/grow?count=2")
        a = _get_json(f"{base}/dir/assign?count=1")
        req = urllib.request.Request(
            f"http://{a['url']}/{a['fid']}", data=b"x" * 256,
            headers={"Content-Type": "application/octet-stream"},
            method="POST")
        urllib.request.urlopen(req, timeout=10).read()
        urllib.request.urlopen(
            f"http://{a['url']}/{a['fid']}", timeout=10).read()

        # the GET fed the needle dimension; visible per node and merged
        hot = _get_json(f"http://{a['url']}/debug/hot")
        needle_keys = {e["key"] for e in hot["dims"]["needle"]["current"]}
        assert a["fid"] in needle_keys
        merged = _get_json(f"{base}/cluster/hot?n=16")
        assert a["fid"] in {e["key"]
                            for e in merged["dims"]["needle"]["current"]}
        assert f"127.0.0.1:{vs.port}" in merged["nodes"]
        assert _get_json(f"{base}/cluster/hot")  # default n
        with pytest.raises(urllib.error.HTTPError) as ei:
            _get_json(f"{base}/cluster/hot?n=0")
        assert ei.value.code == 400

        # manual capture over HTTP
        meta = _get_json(f"{base}/cluster/debug/capture", timeout=30)
        assert meta["trigger"] == "manual" and meta["sizeBytes"] > 0
        assert f"127.0.0.1:{vs.port}" in meta["nodes"]
        assert f"127.0.0.1:{master.port}" in meta["nodes"]

        doc = _get_json(f"{base}/cluster/debug")
        assert doc["debugDir"] == str(debug_dir) and doc["retain"] == 2
        assert [b["name"] for b in doc["bundles"]] == [meta["name"]]

        bundle = _get_json(f"{base}/cluster/debug?bundle={meta['name']}")
        assert bundle["trigger"] == "manual"
        vol_sections = bundle["nodes"][f"127.0.0.1:{vs.port}"]
        assert "seaweedfs_" in vol_sections["metrics"]
        assert "traces" in vol_sections["spans"]
        assert "windows" in vol_sections["profile"]
        assert a["fid"] in json.dumps(vol_sections["hot"])
        assert "states" in bundle["cluster"]["sloStates"]
        assert "lifecycle" in bundle["cluster"]

        # unknown + traversal-shaped names are rejected, not served
        with pytest.raises(urllib.error.HTTPError) as ei:
            _get_json(f"{base}/cluster/debug?bundle=bundle-nope")
        assert ei.value.code == 404
        assert master.flight.bundle("../../etc/passwd") is None
        assert master.flight.bundle("bundle-x/../y") is None

        # retention: captures 2..3 prune down to the newest 2
        for _ in range(2):
            time.sleep(1.1)  # distinct second-resolution bundle stamps
            _get_json(f"{base}/cluster/debug/capture", timeout=30)
        names = [b["name"] for b in master.flight.list_bundles()]
        assert len(names) == 2 and meta["name"] not in names

        # single-flight: 409 while a capture holds the lock
        assert master.flight._capture_lock.acquire(blocking=False)
        try:
            with pytest.raises(urllib.error.HTTPError) as ei:
                _get_json(f"{base}/cluster/debug/capture")
            assert ei.value.code == 409
        finally:
            master.flight._capture_lock.release()

        # /cluster/alerts lists the bundles alongside the history
        alerts = _get_json(f"{base}/cluster/alerts")
        assert [b["name"] for b in alerts["debugBundles"]] == names
    finally:
        vs.stop()
        master.stop()
        hotkeys.reset()


def test_flight_recorder_memory_ring_and_sink_gating(tmp_path):
    """No -debugDir: bundles land in a bounded in-memory ring.  The SLO
    sink only captures on firing transitions and honors the cooldown."""
    from seaweedfs_tpu.master.server import MasterServer

    master = MasterServer(ip="127.0.0.1", port=free_port(),
                          pulse_seconds=0.5)
    master.start()
    try:
        fr = master.flight
        assert fr.debug_dir == "" and fr.list_bundles() == []
        fr.cooldown_s = 3600.0

        fr.sink({"state": "ok", "slo": "availability"})
        fr.sink({"state": "pending", "slo": "availability"})
        time.sleep(0.3)
        assert fr.list_bundles() == []  # non-firing never captures

        fr.sink({"state": "firing", "slo": "availability",
                 "severity": "page", "exemplars": []})
        _wait(lambda: len(fr.list_bundles()) == 1, 20, "sink capture")
        fr.sink({"state": "firing", "slo": "availability",
                 "severity": "page", "exemplars": []})
        time.sleep(0.5)
        assert len(fr.list_bundles()) == 1  # cooldown coalesced

        name = fr.list_bundles()[0]["name"]
        doc = fr.bundle(name)
        assert doc["trigger"] == "alert"
        assert doc["alert"]["slo"] == "availability"

        # ring is bounded at retain even without a directory
        fr.cooldown_s = 0.0
        for _ in range(fr.retain + 2):
            fr.capture(trigger="manual")
        assert len(fr.list_bundles()) == fr.retain
    finally:
        master.stop()


# -- chaos: alert-triggered auto-capture under zipf load ---------------------

PULSE_S = 3.0
WINDOW_SCALE = 0.005
CANARY_TICK_S = 0.3
SLO_TICK_S = 0.4


def _spawn_volume(tmp_path, i, master_port):
    d = tmp_path / f"vol{i}"
    d.mkdir()
    env = dict(os.environ)
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    port = free_port()
    proc = subprocess.Popen(
        [sys.executable, "-m", "seaweedfs_tpu", "volume",
         "-dir", str(d), "-mserver", f"127.0.0.1:{master_port}",
         "-ip", "127.0.0.1", "-port", str(port),
         "-rack", f"rack{i % 2}", "-max", "30"],
        cwd=str(tmp_path), env=env,
        stdout=subprocess.DEVNULL, stderr=subprocess.STDOUT)
    return proc, f"127.0.0.1:{port}"


class _ZipfLoad:
    """Background GET load, zipf-skewed over fids, tallying statuses."""

    def __init__(self, fids):
        self.fids = fids  # [(fid, url)], rank 0 hottest
        self.stop = threading.Event()
        self.codes: list[int] = []
        self.errors: list[str] = []
        self._t = threading.Thread(target=self._run, daemon=True)

    def _run(self):
        i = 0
        while not self.stop.is_set():
            # deterministic zipf-ish schedule: rank 0 gets ~half the hits
            rank = 0
            step = i
            while step % 2 == 1 and rank < len(self.fids) - 1:
                rank += 1
                step //= 2
            fid, url = self.fids[rank]
            try:
                with urllib.request.urlopen(
                        f"http://{url}/{fid}", timeout=10) as r:
                    self.codes.append(r.status)
            except urllib.error.HTTPError as e:
                self.codes.append(e.code)
            except Exception as e:  # noqa: BLE001 — tallied, asserted on
                self.errors.append(str(e))
            i += 1
            time.sleep(0.02)

    def start(self):
        self._t.start()

    def finish(self):
        self.stop.set()
        self._t.join(timeout=10)


@pytest.mark.chaos
def test_a_host_booted_within_the_cooldown_captures_its_first_page(
        monkeypatch):
    """The monotonic clock starts near zero at boot: the first firing
    transition captures however young the host is; the next one within
    the cooldown does not."""
    from seaweedfs_tpu.master import flight

    fr = flight.FlightRecorder(master=None, cooldown_s=3600.0)
    started = []
    monkeypatch.setattr(flight.time, "monotonic", lambda: 5.0)
    monkeypatch.setattr(fr, "_capture_safe",
                        lambda trigger, alert: started.append(trigger))
    fr.sink({"state": "firing", "slo": "availability"})
    _wait(lambda: started == ["alert"], 5, "first capture")
    fr._last_capture = 5.0  # what the capture stamps
    fr.sink({"state": "firing", "slo": "availability"})
    time.sleep(0.2)
    assert started == ["alert"]


def test_chaos_page_auto_captures_bundle(tmp_path, monkeypatch):
    """Kill a volume-holding node under canary + zipf-hot client load:
    the availability page fires, the flight recorder auto-captures a
    bundle covering every live node with the alert's exemplar trace
    pinned and the hot needle named — and the client load (which never
    touched the victim) sees zero 5xx."""
    from seaweedfs_tpu.master.server import MasterServer
    from seaweedfs_tpu.telemetry import hotkeys

    monkeypatch.setenv("SEAWEEDFS_TPU_DEBUG_BUNDLE_COOLDOWN_S", "0")
    hotkeys.reset()
    debug_dir = tmp_path / "debug-bundles"
    master = MasterServer(
        ip="127.0.0.1", port=free_port(), pulse_seconds=PULSE_S,
        slo_interval=SLO_TICK_S, canary_interval=0.0,
        slo_window_scale=WINDOW_SCALE, debug_dir=str(debug_dir))
    master.canary.timeout_s = 5.0
    master.start()
    procs, load = [], None
    try:
        nodes = []
        for i in range(4):
            proc, addr = _spawn_volume(tmp_path, i, master.port)
            procs.append(proc)
            nodes.append(addr)
        _wait(lambda: len(master.topo.nodes) == 4, 30,
              "4 volume servers registered")

        def covered():
            with master.topo.lock:
                return sum(1 for n in master.topo.nodes.values()
                           if n.volumes) == 4

        _get_json(f"http://127.0.0.1:{master.port}/vol/grow?count=10")
        for _ in range(8):
            deadline = time.time() + 6
            while time.time() < deadline and not covered():
                time.sleep(0.3)
            if covered():
                break
            _get_json(f"http://127.0.0.1:{master.port}/vol/grow?count=4")
        _wait(covered, 10, "every node holds a volume")

        fids = []
        for _ in range(24):
            a = _get_json(
                f"http://127.0.0.1:{master.port}/dir/assign?count=1")
            body = os.urandom(1024)
            req = urllib.request.Request(
                f"http://{a['url']}/{a['fid']}", data=body,
                headers={"Content-Type": "application/octet-stream"},
                method="POST")
            urllib.request.urlopen(req, timeout=10).read()
            fids.append((a["fid"], a["url"]))

        # victim: a volume-holding node that serves NONE of the loaded
        # fids — client traffic must survive the kill untouched
        by_url: dict[str, list] = {}
        for fid, url in fids:
            by_url.setdefault(url, []).append(fid)
        victim_addr = next(n for n in nodes
                           if len(by_url.get(n, [])) <= min(
                               len(v) for v in by_url.values()))
        survivor_fids = [(f, u) for f, u in fids if u != victim_addr]
        assert len(survivor_fids) >= 4

        master.canary.interval_s = CANARY_TICK_S
        master.canary.start()

        # clean baseline: error-free for a full long burn window, so the
        # kill below is the FIRST burn source and the firing transition
        # (which triggers the auto-capture) is unambiguous
        def error_count():
            from seaweedfs_tpu.stats.metrics import REGISTRY

            total = 0.0
            for name, v in REGISTRY.snapshot_samples(max_samples=1 << 20):
                if (name.startswith("seaweedfs_canary_probe_total")
                        and 'result="error"' in name):
                    total += v
            return total

        long_window_s = 3600.0 * WINDOW_SCALE
        last_count, last_change = error_count(), time.time()
        deadline = time.time() + 90
        while time.time() - last_change < long_window_s + 1.0:
            if time.time() > deadline:
                raise TimeoutError("canary error-free baseline")
            time.sleep(0.5)
            cur = error_count()
            if cur != last_count:
                last_count, last_change = cur, time.time()

        load = _ZipfLoad(survivor_fids)
        load.start()
        time.sleep(1.0)

        hist_idx = len(master.slo.alert_history)
        pre_bundles = {b["name"] for b in master.flight.list_bundles()}
        victim = procs[nodes.index(victim_addr)]
        victim.send_signal(signal.SIGKILL)
        victim.wait(timeout=10)

        def page_fired():
            return any(h["severity"] == "page" and h["state"] == "firing"
                       and h["slo"] == "availability"
                       for h in list(master.slo.alert_history)[hist_idx:])

        fast_window_s = 300.0 * WINDOW_SCALE
        _wait(page_fired, 3 * PULSE_S + fast_window_s + 15.0,
              "availability page alert")

        # the firing transition auto-captures a bundle in the background
        def alert_bundle():
            for b in master.flight.list_bundles():
                if "-alert-" in b["name"] and b["name"] not in pre_bundles:
                    return master.flight.bundle(b["name"])
            return None

        _wait(lambda: alert_bundle() is not None, 30,
              "alert-triggered bundle capture")
        bundle = alert_bundle()

        # covers every live node (victim may appear with scrape errors)
        survivors = [n for n in nodes if n != victim_addr]
        for addr in survivors + [f"127.0.0.1:{master.port}"]:
            assert addr in bundle["nodes"], sorted(bundle["nodes"])
            assert "seaweedfs_" in bundle["nodes"][addr].get(
                "metrics", ""), addr

        # the alert's exemplar trace id is pinned in the bundle's spans
        alert = bundle["alert"]
        assert alert["slo"] == "availability"
        assert alert.get("exemplars"), alert
        tid = alert["exemplars"][0]["traceId"]
        stitched = bundle.get("exemplarTrace", {})
        assert stitched.get("traceId") == tid
        assert stitched.get("spans"), stitched

        # hot-key tables name the zipf-hot needle on its serving node
        hot_fid, hot_url = survivor_fids[0]
        hot_doc = bundle["nodes"][hot_url]["hot"]
        seen = {e["key"]
                for w in ("current", "previous")
                for e in hot_doc["dims"]["needle"][w]}
        assert hot_fid in seen, (hot_fid, sorted(seen)[:8])
        # ... and the federated live view agrees
        merged = _get_json(
            f"http://127.0.0.1:{master.port}/cluster/hot?n=32")
        merged_keys = {e["key"]
                       for w in ("current", "previous")
                       for e in merged["dims"]["needle"][w]}
        assert hot_fid in merged_keys

        # client traffic never saw a server error across kill + capture
        load.finish()
        assert load.codes and all(c < 500 for c in load.codes), (
            sorted(set(load.codes)))
        assert not load.errors, load.errors[:3]
    finally:
        if load is not None:
            load.finish()
        for p in procs:
            if p.poll() is None:
                p.kill()
        for p in procs:
            try:
                p.wait(timeout=10)
            except subprocess.TimeoutExpired:
                pass
        master.stop()
        hotkeys.reset()
