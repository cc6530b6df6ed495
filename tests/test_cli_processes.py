"""Tier-4 compose-style harness: REAL subprocesses through the CLI.

Every other integration test runs servers in-process; this one spawns
`python -m seaweedfs_tpu master|volume|filer` exactly as an operator
would (SURVEY §4 tier 4, the reference's local-cluster-compose.yml), so
CLI flag wiring, module entry points, and cross-process gRPC/HTTP all
get exercised end to end.
"""

import json
import os
import signal
import subprocess
import sys
import time
import urllib.error
import urllib.request

from helpers import free_port

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _env():
    env = dict(os.environ)
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    return env


def _spawn(args, cwd):
    env = _env()
    # the servers run the default (cpu) codec and never import jax
    # DEVNULL: the output is never asserted on, and an unread PIPE would
    # block a chatty server once the 64KB buffer fills
    return subprocess.Popen(
        [sys.executable, "-m", "seaweedfs_tpu", *args],
        cwd=cwd, env=env,
        stdout=subprocess.DEVNULL, stderr=subprocess.STDOUT,
    )


def _wait_http(url, deadline_s=25):
    deadline = time.time() + deadline_s
    while time.time() < deadline:
        try:
            with urllib.request.urlopen(url, timeout=2) as r:
                return r.status
        except (urllib.error.URLError, ConnectionError, OSError):
            time.sleep(0.3)
    raise TimeoutError(url)


def test_cli_three_process_cluster(tmp_path):
    mport, vport, fport = free_port(), free_port(), free_port()
    vol_dir = tmp_path / "v1"
    vol_dir.mkdir()
    procs = []
    try:
        procs.append(_spawn(["master", "-port", str(mport)],
                            str(tmp_path)))
        _wait_http(f"http://127.0.0.1:{mport}/cluster/healthz")
        procs.append(_spawn(
            ["volume", "-dir", str(vol_dir), "-port", str(vport),
             "-mserver", f"127.0.0.1:{mport}", "-ec.codec", "cpu"],
            str(tmp_path)))
        procs.append(_spawn(
            ["filer", "-master", f"127.0.0.1:{mport}",
             "-port", str(fport),
             "-store", str(tmp_path / "filer.db")],
            str(tmp_path)))
        _wait_http(f"http://127.0.0.1:{fport}/")

        # wait for the volume server to register with the master
        deadline = time.time() + 20
        while time.time() < deadline:
            try:
                with urllib.request.urlopen(
                    f"http://127.0.0.1:{mport}/dir/assign", timeout=2
                ) as r:
                    assign = json.loads(r.read())
                if assign.get("fid"):
                    break
            except (urllib.error.URLError, OSError):
                pass
            time.sleep(0.3)
        else:
            raise AssertionError("master never produced an assignment")

        # filer write + read across three real processes
        payload = b"three-process-cluster!"
        req = urllib.request.Request(
            f"http://127.0.0.1:{fport}/dir/hello.txt", data=payload,
            method="PUT")
        with urllib.request.urlopen(req, timeout=10) as r:
            assert r.status in (200, 201)
        with urllib.request.urlopen(
            f"http://127.0.0.1:{fport}/dir/hello.txt", timeout=10
        ) as r:
            assert r.read() == payload

        # the shell subcommand drives the live cluster as a 4th process
        out = subprocess.run(
            [sys.executable, "-m", "seaweedfs_tpu", "shell",
             "-m", f"127.0.0.1:{mport}", "-c", "volume.list"],
            cwd=str(tmp_path), capture_output=True, text=True,
            timeout=30, env=_env(),
        )
        assert out.returncode == 0
        assert f"127.0.0.1:{vport}" in out.stdout
    finally:
        for p in procs:
            p.send_signal(signal.SIGTERM)
        for p in procs:
            try:
                p.wait(timeout=10)
            except subprocess.TimeoutExpired:
                p.kill()


def test_cli_three_master_raft_quorum(tmp_path):
    """A 3-master raft quorum as real CLI subprocesses: exactly one
    leader, followers redirect admin writes, and /cluster/status agrees
    (reference: local-cluster-compose.yml's 3-master raft tier)."""
    ports = [free_port() for _ in range(3)]
    peers = ",".join(f"127.0.0.1:{p}" for p in ports)
    procs = []
    try:
        for i, p in enumerate(ports):
            d = tmp_path / f"m{i}"
            d.mkdir()
            procs.append(_spawn(
                ["master", "-port", str(p), "-peers", peers,
                 "-raftDir", str(d)], str(tmp_path)))
        for p in ports:
            _wait_http(f"http://127.0.0.1:{p}/cluster/healthz")

        # a leader emerges and every node names the same one
        deadline = time.time() + 30
        leaders = set()
        while time.time() < deadline:
            leaders = set()
            ok = True
            for p in ports:
                try:
                    with urllib.request.urlopen(
                        f"http://127.0.0.1:{p}/cluster/status",
                        timeout=2,
                    ) as r:
                        st = json.loads(r.read())
                    leaders.add(st.get("Leader") or st.get("leader"))
                except (urllib.error.URLError, OSError, ValueError):
                    ok = False
            if ok and len(leaders) == 1 and None not in leaders:
                break
            time.sleep(0.5)
        assert len(leaders) == 1 and None not in leaders, leaders
        leader = leaders.pop()

        # followers answer admin writes with a redirect to that leader
        follower = next(f"127.0.0.1:{p}" for p in ports
                        if f"127.0.0.1:{p}" != leader)
        req = urllib.request.Request(
            f"http://{follower}/vol/grow?count=1", method="GET")

        class NoRedirect(urllib.request.HTTPRedirectHandler):
            def redirect_request(self, *a, **k):
                return None

        opener = urllib.request.build_opener(NoRedirect)
        try:
            resp = opener.open(req, timeout=5)
            code, location = resp.status, resp.headers.get("Location", "")
        except urllib.error.HTTPError as e:
            code, location = e.code, e.headers.get("Location", "")
        assert code in (307, 503), code
        if code == 307:
            assert leader in location
    finally:
        for p in procs:
            p.send_signal(signal.SIGTERM)
        for p in procs:
            try:
                p.wait(timeout=10)
            except subprocess.TimeoutExpired:
                p.kill()
