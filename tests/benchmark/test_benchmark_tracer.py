"""`run.Tracer`: which slice of the window each mode records, on a fake
server that only counts the profiler's starts and stops."""

import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark import harness as hz  # noqa: E402
from benchmark import run  # noqa: E402


class FakeCluster:
    def __init__(self, control_dir):
        self.control_dir = control_dir
        self.commands = []

    def control(self, command, deadline_s=120.0):
        self.commands.append(command)
        trace_dir = os.path.join(self.control_dir, "trace")
        if command == "trace_start":
            os.makedirs(trace_dir)
            with open(os.path.join(trace_dir, f"{len(self.commands)}.pb"),
                      "w") as f:
                f.write("x")
        return {"seconds": 0.0}

    def metrics(self):
        return {"scrape": float(len(self.commands))}


def make(spec, tmp_path):
    cluster = FakeCluster(str(tmp_path))
    obs = hz.Obs(cluster)
    return run.Tracer(spec, cluster, obs), cluster, obs


# (index, edge) as a driver reports them -> the commands the server gets,
# and which start the kept slice belongs to (the name of its trace file)
@pytest.mark.parametrize("edges,commands,kept", [
    # the plain case: rpc 1 is traced, no other
    ([(0, "start"), (0, "end"), (1, "start"), (1, "end"), (2, "start"),
      (2, "end")], ["trace_start", "trace_stop"], "1.pb"),
    # a poll at the traced index (the rebuild loop does not count it, so
    # the index comes again): its slice is dropped, the next rpc is traced
    ([(0, "start"), (0, "end"), (1, "start"), (1, "idle"), (1, "start"),
      (1, "end"), (2, "start"), (2, "end")],
     ["trace_start", "trace_stop", "trace_start", "trace_stop"], "3.pb"),
    # two polls, then work
    ([(1, "start"), (1, "idle"), (1, "start"), (1, "idle"), (1, "start"),
      (1, "end")], ["trace_start", "trace_stop"] * 3, "5.pb"),
    # a poll before the traced index costs nothing
    ([(0, "start"), (0, "idle"), (0, "start"), (0, "end"), (1, "start"),
      (1, "end")], ["trace_start", "trace_stop"], "1.pb"),
    # a poll after the kept slice leaves it alone
    ([(1, "start"), (1, "end"), (2, "start"), (2, "idle")],
     ["trace_start", "trace_stop"], "1.pb"),
    # the window closes inside the traced rpc: what was recorded is kept
    ([(1, "start")], ["trace_start", "trace_stop"], "1.pb"),
    # the traced index never came: nothing was traced
    ([(0, "start"), (0, "end")], [], None),
])
def test_rpc_mode_traces_the_first_rpc_at_its_index_that_did_work(
        tmp_path, edges, commands, kept):
    tracer, cluster, obs = make({"mode": "rpc", "index": 1}, tmp_path)
    tracer.window_opens()
    assert cluster.commands == []
    for index, edge in edges:
        obs.rpc(index, edge)
    tracer.window_closed()
    assert cluster.commands == commands
    trace_dir = os.path.join(str(tmp_path), "trace")
    if kept is None:
        assert tracer.window_s is None and not os.path.exists(trace_dir)
        assert "trace" not in obs.prom
    else:
        assert os.listdir(trace_dir) == [kept]
        assert tracer.window_s is not None and tracer.window_s >= 0.0
        # the byte counters are read around the kept slice, not a dropped one
        before, after = obs.prom["trace"]
        assert (before["scrape"], after["scrape"]) == (
            len(commands) - 2, len(commands))


def test_window_mode_traces_from_open_to_close(tmp_path):
    tracer, cluster, obs = make({"mode": "window"}, tmp_path)
    assert cluster.commands == [] and not obs.rpc_listeners
    tracer.window_opens()
    assert cluster.commands == ["trace_start"]
    for i in range(3):      # rpcs come and go: none starts or stops a trace
        obs.rpc(i, "start")
        obs.rpc(i, "end")
    assert cluster.commands == ["trace_start"] and tracer.window_s is None
    tracer.window_closed()
    assert cluster.commands == ["trace_start", "trace_stop"]
    assert tracer.window_s is not None
    assert [s["scrape"] for s in obs.prom["trace"]] == [0.0, 2.0]
    tracer.window_closed()  # run_cell's `finally` may come again
    assert cluster.commands == ["trace_start", "trace_stop"]


def test_time_mode_is_a_stretch_inside_the_window(tmp_path):
    tracer, cluster, obs = make(
        {"mode": "time", "start_s": 0.05, "length_s": 0.1}, tmp_path)
    tracer.window_opens()
    assert cluster.commands == []
    tracer.window_closed()
    assert cluster.commands == ["trace_start", "trace_stop"]
    assert 0.1 <= tracer.window_s < 1.0
