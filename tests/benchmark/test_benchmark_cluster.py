"""`harness.Cluster` and its ports: the rehearsals start real servers
beside five other test workers, so a port probed free can be gone by the
time the child binds it."""

import os
import socket
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark import harness as hz  # noqa: E402


def ephemeral_range():
    try:
        with open("/proc/sys/net/ipv4/ip_local_port_range") as f:
            lo, hi = map(int, f.read().split())
        return lo, hi
    except OSError:
        return 32768, 60999


def test_port_pairs_keep_clear_of_client_sockets_and_of_the_other_tests():
    lo, _hi = ephemeral_range()
    seen = set()
    for _ in range(50):
        p = hz.free_port_pair()
        assert p not in seen
        seen.add(p)
        for q in (p, p + 10000):
            assert q < lo                                  # no client socket
            assert not 20000 <= q < 22768                  # tests/helpers.py
            assert not 30000 <= q < 32768                  # ... and its twins
    hz._PORTS_HANDED_OUT.difference_update(seen)


def test_a_server_that_lost_its_port_is_started_again(tmp_path, monkeypatch):
    """The race itself: the port is free when probed and taken when the
    child binds (here: taken all along, and the probe told otherwise)."""
    taken = hz.free_port_pair()
    real = hz.free_port_pair
    draws = []

    def probe_that_was_outrun():
        draws.append(taken if not draws else real())
        return draws[-1]

    monkeypatch.setattr(hz, "free_port_pair", probe_that_was_outrun)
    data = tmp_path / "d0"
    data.mkdir()
    with socket.socket() as squatter:
        squatter.bind(("0.0.0.0", taken))
        squatter.listen(1)
        cluster = hz.Cluster([str(data)], "cpu", str(tmp_path / "log"),
                             {"JAX_PLATFORMS": "cpu"})
        try:
            status = cluster.wait_ready(120.0)
        finally:
            cluster.stop()
            hz.reap_children()
    assert "ec" in status
    assert len(draws) == 4 and taken not in (cluster.mport, cluster.vport)
    assert os.path.exists(cluster.log_path + ".bind1")
    with open(cluster.log_path + ".bind1") as f:
        assert "Address already in use" in f.read()
