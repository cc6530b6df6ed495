"""A needle read of an EC volume sends every lost interval's decode to the
codec service before it waits on any (`EcVolume._reconstruct_intervals`):
the bytes, the order of submits and results, the overlap counter, and the
single-flight calls under that order.  The service is a device-mode one on
the CPU backend, as a `-ec.codec tpu` server holds on the chip."""

import os
import sys
import threading
import time

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from seaweedfs_tpu.ops.codec_service import CodecService  # noqa: E402
from seaweedfs_tpu.stats.metrics import (  # noqa: E402
    EC_DEGRADED_INTERVALS,
    EC_DEGRADED_OVERLAPPED,
    EC_DEGRADED_STAGE,
    EC_SINGLEFLIGHT,
)
from seaweedfs_tpu.storage.ec.constants import to_ext  # noqa: E402
from seaweedfs_tpu.storage.ec.encoder import (  # noqa: E402
    generate_ec_files,
    write_sorted_file_from_idx,
)
from seaweedfs_tpu.storage.ec import volume as ec_volume  # noqa: E402
from seaweedfs_tpu.storage.ec.volume import EcVolume  # noqa: E402
from seaweedfs_tpu.storage.needle import Needle  # noqa: E402
from seaweedfs_tpu.storage.super_block import SuperBlock  # noqa: E402
from seaweedfs_tpu.storage.volume import Volume  # noqa: E402

SMALL = 4096        # block rows of 4 KiB: a needle of a few KiB spans blocks
LARGE = 1 << 20     # a volume under 10 MiB has small rows only
LOST = (0, 1, 2, 3)
LONG = 81           # a needle of more than two stripe rows
OVERLAPPED = EC_DEGRADED_OVERLAPPED.labels()
DECODED = EC_DEGRADED_INTERVALS.labels("decoded")
CACHED = EC_DEGRADED_INTERVALS.labels("cached")
COALESCED = EC_SINGLEFLIGHT.labels("coalesced")
DECODE = EC_DEGRADED_STAGE.labels("decode")
GATHER = EC_DEGRADED_STAGE.labels("gather")


@pytest.fixture(scope="module")
def stored(tmp_path_factory):
    """-> (base of an EC volume with all 14 shards, {needle id: payload},
    {lost intervals a read of it needs: a needle id with that many})."""
    rng = np.random.default_rng(45)
    vol = Volume(str(tmp_path_factory.mktemp("ov")), "", 9,
                 super_block=SuperBlock())
    payloads = {}
    for nid in range(1, 81):
        size = int(rng.integers(SMALL // 2, 5 * SMALL))
        payloads[nid] = rng.integers(0, 256, size, dtype=np.uint8).tobytes()
        vol.append_needle(Needle(cookie=7, id=nid, data=payloads[nid]))
    payloads[LONG] = rng.integers(0, 256, 25 * SMALL, dtype=np.uint8).tobytes()
    vol.append_needle(Needle(cookie=7, id=LONG, data=payloads[LONG]))
    base = vol.file_name()
    vol.close()
    generate_ec_files(base, large_block_size=LARGE, small_block_size=SMALL,
                      codec_name="cpu")
    write_sorted_file_from_idx(base)
    ev = EcVolume(base, volume_id=9, large_block_size=LARGE,
                  small_block_size=SMALL)
    by_lost = {}
    try:
        for nid in payloads:
            by_lost.setdefault(len(_lost_spans(ev, nid)), nid)
    finally:
        ev.close()
    assert {1, 2, 3, 4} <= set(by_lost), sorted(by_lost)
    assert max(by_lost) > ec_volume._DECODES_IN_FLIGHT
    assert by_lost[max(by_lost)] == LONG
    return base, payloads, by_lost


def _lost_spans(ev: EcVolume, nid: int) -> list:
    """(shard, offset, length) of each interval of `nid` in a lost shard."""
    _off, _size, intervals = ev.locate(nid)
    spans = []
    for iv in intervals:
        sid, off = iv.to_shard_id_and_offset(LARGE, SMALL)
        if sid in LOST:
            spans.append((sid, off, iv.size))
    return spans


def _shard_bytes(base: str, span) -> bytes:
    sid, off, length = span
    with open(base + to_ext(sid), "rb") as f:
        f.seek(off)
        return f.read(length)


@pytest.fixture(scope="module")
def service():
    svc = CodecService(mode="device", codec_name="tpu_xor")
    yield svc
    svc.close()


class Recorder:
    """The codec service as a volume sees it, with every `submit_apply`
    and every `result()` of what it returned written down in order;
    `gate(n)`, where set, runs before the n-th submit (from 1) and may
    return the future that submit hands back in place of the service's."""

    def __init__(self, svc):
        self.svc = svc
        self.events: list[str] = []
        self.gate = None
        self.lock = threading.Lock()

    def submit_apply(self, *args, **kwargs):
        with self.lock:
            self.events.append("submit")
            n = self.events.count("submit")
        if self.gate is not None:
            fut = self.gate(n)
            if fut is not None:
                return fut
        return _Recorded(self.svc.submit_apply(*args, **kwargs), self)

    def submits(self) -> int:
        return self.events.count("submit")


class _Recorded:
    def __init__(self, fut, rec: Recorder):
        self.fut, self.rec = fut, rec

    def result(self, timeout=None):
        with self.rec.lock:
            self.rec.events.append("result")
        return self.fut.result(120 if timeout is None else timeout)


@pytest.fixture
def volume(tmp_path, stored, service):
    """-> (an EcVolume over hard links of the stored volume without shards
    0-3, its decode service's Recorder)."""
    base = stored[0]
    mine = str(tmp_path / "9")
    for i in range(14):
        if i not in LOST:
            os.link(base + to_ext(i), mine + to_ext(i))
    os.link(base + ".ecx", mine + ".ecx")
    ev = EcVolume(mine, volume_id=9, codec_name="tpu_xor",
                  large_block_size=LARGE, small_block_size=SMALL)
    rec = Recorder(service)
    ev.decode_service = rec
    yield ev, rec
    ev.close()


@pytest.mark.parametrize("n_lost", [2, 3, 4])
def test_every_lost_interval_is_submitted_before_any_is_waited_on(
        volume, stored, n_lost):
    ev, rec = volume
    _base, payloads, by_lost = stored
    nid = by_lost[n_lost]
    counts = [c.value for c in (OVERLAPPED, DECODED)] + [
        h.count for h in (DECODE, GATHER)]
    assert ev.read_needle(nid).data == payloads[nid]
    assert rec.events == ["submit"] * n_lost + ["result"] * n_lost
    moved = [c.value for c in (OVERLAPPED, DECODED)] + [
        h.count for h in (DECODE, GATHER)]
    # one overlap, one decode, one decode and one gather stage a lost
    # interval
    assert [b - a for a, b in zip(counts, moved)] == [n_lost] * 4


def test_a_long_needle_keeps_no_more_decodes_in_flight_than_the_cap(
        volume, stored):
    """A needle of more lost intervals than `_DECODES_IN_FLIGHT` submits
    up to the cap, then waits on its oldest decode before each further
    submit: never more than the cap outstanding, every decode overlapped,
    the bytes the stored ones."""
    ev, rec = volume
    _base, payloads, by_lost = stored
    cap = ec_volume._DECODES_IN_FLIGHT
    n_lost = max(by_lost)
    overlapped, decoded = OVERLAPPED.value, DECODED.value
    assert ev.read_needle(LONG).data == payloads[LONG]
    assert rec.events[:cap + 1] == ["submit"] * cap + ["result"]
    assert rec.submits() == n_lost
    outstanding = peak = 0
    for ev_ in rec.events:
        outstanding += 1 if ev_ == "submit" else -1
        peak = max(peak, outstanding)
    assert peak == cap and outstanding == 0
    assert OVERLAPPED.value == overlapped + n_lost
    assert DECODED.value == decoded + n_lost


def test_a_needle_of_one_lost_interval_submits_once_and_overlaps_nothing(
        volume, stored):
    ev, rec = volume
    _base, payloads, by_lost = stored
    nid = by_lost[1]
    overlapped, decoded = OVERLAPPED.value, DECODED.value
    assert ev.read_needle(nid).data == payloads[nid]
    assert rec.events == ["submit", "result"]
    assert OVERLAPPED.value == overlapped and DECODED.value == decoded + 1


def test_an_interval_the_cache_holds_is_not_submitted(volume, stored):
    ev, rec = volume
    base, payloads, by_lost = stored
    nid = by_lost[3]
    first, *rest = _lost_spans(ev, nid)
    assert ev._reconstruct_interval(*first) == _shard_bytes(base, first)
    assert rec.submits() == 1
    cached, overlapped = CACHED.value, OVERLAPPED.value
    assert ev.read_needle(nid).data == payloads[nid]
    assert rec.submits() == 1 + len(rest)
    assert CACHED.value == cached + 1
    assert OVERLAPPED.value == overlapped + len(rest)
    # all three now cached: the second read submits nothing
    assert ev.read_needle(nid).data == payloads[nid]
    assert rec.submits() == 1 + len(rest)


def test_a_process_without_a_device_service_decodes_inline(tmp_path, stored):
    base, payloads, by_lost = stored
    mine = str(tmp_path / "9")
    for i in range(14):
        if i not in LOST:
            os.link(base + to_ext(i), mine + to_ext(i))
    os.link(base + ".ecx", mine + ".ecx")
    ev = EcVolume(mine, volume_id=9, large_block_size=LARGE,
                  small_block_size=SMALL)
    try:
        assert ev._decode_service() is None
        overlapped, decoded = OVERLAPPED.value, DECODED.value
        nid = by_lost[4]
        assert ev.read_needle(nid).data == payloads[nid]
        assert OVERLAPPED.value == overlapped and DECODED.value == decoded + 4
    finally:
        ev.close()


def _run(fn, *args) -> dict:
    """Start `fn(*args)` on a thread -> its record: "thread", and once it
    has ended "value" or "error"."""
    rec: dict = {}

    def body():
        try:
            rec["value"] = fn(*args)
        except Exception as e:  # noqa: BLE001 — the test reads it
            rec["error"] = e

    rec["thread"] = threading.Thread(target=body, daemon=True)
    rec["thread"].start()
    return rec


def _until(cond, seconds: float = 10.0) -> None:
    deadline = time.monotonic() + seconds
    while not cond():
        assert time.monotonic() < deadline, "timed out"
        time.sleep(0.002)


def test_two_reads_that_each_lead_what_the_other_follows_both_finish(
        volume, stored):
    """Read A leads X and follows Y, read B leads Y and follows X: each
    resolves what it leads before it follows, so neither waits on the
    other.  The first submits of the two meet at a barrier, so each has
    registered its lead before the other looks at it."""
    ev, rec = volume
    base, _payloads, by_lost = stored
    x, y = _lost_spans(ev, by_lost[2])
    barrier = threading.Barrier(2, timeout=10)

    def meet(n):
        barrier.wait()

    rec.gate = meet
    decoded, coalesced, cached = DECODED.value, COALESCED.value, CACHED.value
    t0 = time.monotonic()
    a = _run(ev._reconstruct_intervals, [x, y])
    b = _run(ev._reconstruct_intervals, [y, x])
    for r in (a, b):
        r["thread"].join(10)
        assert not r["thread"].is_alive(), "a read is waiting on the other"
    assert time.monotonic() - t0 < 10
    want = [_shard_bytes(base, x), _shard_bytes(base, y)]
    assert a.get("value") == want, a.get("error")
    assert b.get("value") == want[::-1], b.get("error")
    # each interval decoded once, by its leader; the other read took it
    # from the leader's call or, once released, from the interval cache
    assert rec.submits() == 2 and DECODED.value == decoded + 2
    assert COALESCED.value - coalesced + CACHED.value - cached == 2


def test_a_failed_job_fails_the_read_and_releases_every_call_it_led(
        volume, stored):
    """The first of a read's two jobs fails: the needle read raises, and a
    follower of each interval it led returns long before the followers'
    60 s bound — the failed one's with its error, the other's with its
    bytes."""
    ev, rec = volume
    base, _payloads, by_lost = stored
    nid = by_lost[2]
    x, y = _lost_spans(ev, nid)
    go = threading.Event()

    class Broken:
        def result(self, timeout=None):
            go.wait(10)
            raise RuntimeError("decode failed")

    rec.gate = lambda n: Broken() if n == 1 else None
    coalesced = COALESCED.value
    t0 = time.monotonic()
    reader = _run(ev.read_needle, nid)
    _until(lambda: rec.submits() == 2)
    followers = [_run(ev._reconstruct_interval, *span) for span in (x, y)]
    _until(lambda: COALESCED.value == coalesced + 2)
    go.set()
    for r in [reader] + followers:
        r["thread"].join(10)
        assert not r["thread"].is_alive()
    assert time.monotonic() - t0 < 10
    assert isinstance(reader.get("error"), RuntimeError)
    assert isinstance(followers[0].get("error"), RuntimeError)
    assert followers[1].get("value") == _shard_bytes(base, y)
    assert not ev._sf_calls


def test_a_decode_that_fails_at_the_cap_ends_the_reads_submits(
        volume, stored):
    """The long needle's first decode fails when the read waits on it to
    make room for a fifth: the read submits nothing more, raises, and has
    released every call it led — the other three with their bytes in the
    interval cache."""
    ev, rec = volume
    base, _payloads, _by_lost = stored
    cap = ec_volume._DECODES_IN_FLIGHT

    class Broken:
        def result(self, timeout=None):
            raise RuntimeError("decode failed")

    rec.gate = lambda n: Broken() if n == 1 else None
    with pytest.raises(RuntimeError, match="decode failed"):
        ev.read_needle(LONG)
    assert rec.submits() == cap
    assert not ev._sf_calls
    spans = _lost_spans(ev, LONG)
    cached = CACHED.value
    for span in spans[1:cap]:
        assert ev._reconstruct_interval(*span) == _shard_bytes(base, span)
    assert CACHED.value == cached + cap - 1 and rec.submits() == cap


def test_each_overlapped_decode_is_one_span_of_the_request_trace(
        volume, stored):
    """Inside a request trace every decode in flight is recorded in the
    ring as the request's child, from its submit to its result in hand,
    beside its gather: the spans do not nest in each other."""
    from seaweedfs_tpu.telemetry import trace

    ev, _rec = volume
    _base, payloads, by_lost = stored
    nid = by_lost[3]
    with trace.start_span("volumeServer.get") as root:
        t0 = time.time()
        assert ev.read_needle(nid).data == payloads[nid]
    mine = [s for s in trace.TRACER.spans() if s.trace_id == root.trace_id]
    decodes = [s for s in mine if s.name == "ec.degraded.decode"]
    gathers = [s for s in mine if s.name == "ec.degraded.gather"]
    assert len(decodes) == len(gathers) == 3
    for s in decodes + gathers:
        assert s.parent_id == root.span_id and s.status == "ok"
        assert t0 - 0.01 <= s.start and s.duration > 0
    # the first decode was in flight while the later intervals gathered
    first = min(decodes, key=lambda s: s.start)
    last_gather = max(gathers, key=lambda s: s.start)
    assert first.start <= last_gather.start
    assert first.start + first.duration >= last_gather.start
