"""Rehearsal of `ec-rebuild-4data` at toy size, sound and broken."""

import pytest

from rehearsal_util import OFF_CHIP, over, rehearse

CELL = "ec-rebuild-4data"


def test_sound_run_fails_only_for_want_of_a_tpu():
    out = rehearse(CELL, seed=3, seconds=2.5)
    assert out["correct"] is False and over(out) == OFF_CHIP
    assert out["attempted"] >= 2 and out["failed"] == 0
    assert set(out["metrics"]) == {"rebuild_MBps", "setup_s"}


@pytest.mark.parametrize("control", ["rs-10-3", "flip-shard-byte",
                                     "lose-output", "half-rows"])
def test_broken_path_comes_out_not_correct(control):
    out = rehearse(CELL, seed=12, control=control, seconds=2.5)
    assert "shard_bytes_differ" in over(out)
    assert over(out) <= OFF_CHIP | {"shard_bytes_differ", "ec_needles_differ"}


def test_a_poll_at_the_traced_index_does_not_use_up_the_trace(monkeypatch):
    """The first `ec.rebuild` the tracer starts on is made a dry run
    (`-plan`: the real command, and it re-makes nothing), as a round that
    outran the master's view of the next loss is.  The traced slice must
    then be the next round, one that re-made shards (PR 34: `the traced run
    holds no device plane`, rc 3)."""
    import os

    from rehearsal_util import run

    hz = run.hz
    starts, forced = [], []
    real_start, real_shell = run.Tracer._start, hz.Cluster.shell

    def start(self):
        before = self._t
        real_start(self)
        if self._t is not before:
            starts.append(self._t)

    def shell(self, name, args):
        if name == "ec_rebuild" and starts and not forced:
            forced.append(1)
            args = ["-plan"]
        return real_shell(self, name, args)

    monkeypatch.setattr(run.Tracer, "_start", start)
    monkeypatch.setattr(hz.Cluster, "shell", shell)
    out = rehearse(CELL, seed=2**31 + 21, traced=True, seconds=2.5)
    assert over(out) == OFF_CHIP and out["failed"] == 0
    assert forced and len(starts) == 2
    assert "ec_write_s_per_GB.rebuild" in out["metrics"]
    # off the chip the slice has no device plane; that it is the round
    # that did the work shows in the program's own spans on the host plane
    log_dir = os.path.join(hz.LOG_ROOT, f"{CELL}-test{os.getpid()}"
                           f"-seed{2**31 + 21}-trace1")
    with open(os.path.join(log_dir, "trace_planes.txt")) as f:
        planes = f.read()
    assert "ec.pipeline.write" in planes
