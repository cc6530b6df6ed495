"""EC file pipeline: `.dat` -> `.ec00`..`.ec13` shards + `.ecx` sorted index.

Behavior matches the reference pipeline (ec_encoder.go:57-231): stripe the
volume into rows of 10 large (1GB) blocks while MORE than one full large row
remains, then rows of 10 small (1MB) blocks, zero-padding the tail; parity
is RS(10,4) over columns; shard files get byte-identical contents.

The batching geometry differs from the reference's fixed 256KB loop: we
stream column slices of a configurable width through the codec, which for
the TPU codec means big (10, W) uint8 blocks DMA'd to HBM and one fused
GF-matmul kernel per slice — the reference's 14 shard buffers map to one
device-resident matrix.  Output bytes are identical for any slice width
because parity is columnwise.
"""

from __future__ import annotations

import contextlib
import os
import threading
import time
from collections import deque

import numpy as np

from ...ops import codec_service, gf256
from ...ops.codec import get_codec
from ...stats.metrics import (
    EC_ENCODES_INFLIGHT,
    EC_PARTIAL_FALLBACK,
    EC_PIPELINE_BYTES,
    EC_PIPELINE_STAGE,
    EC_REBUILD_BYTES,
    EC_REBUILD_RESULT,
    EC_REBUILD_SECONDS,
    EC_REBUILD_SHARDS,
    EC_SLICE_BUFFERS,
    EC_SLICE_POOL_BYTES,
)
from ...telemetry import trace
from ...util import faultpoint
from ..needle_map import NeedleMap
from .constants import (
    DATA_SHARDS,
    LARGE_BLOCK_SIZE,
    SMALL_BLOCK_SIZE,
    TOTAL_SHARDS,
    to_ext,
)

# Device batch: bytes per shard per codec call (64 x 256KB reference batches)
DEFAULT_SLICE = 16 * 1024 * 1024
# slice buffers one pipelined encode holds at once: one in prefetch, two
# with the codec, one being written.  The bound is the writer's
# back-pressure on the reader and nothing more — the buffers are the
# process's (_SlicePool) and outlive the encode
_POOL_SLICES = 4
# ... and a rebuild: one in prefetch, one in compute, one in the writer
_REBUILD_SLICES = 3
# seconds a free slice buffer may lie untaken before it is released: this
# long after the process's last EC pipeline ended, the pool holds nothing
_POOL_IDLE_S = 30.0

# per-slice stage timings for the pipelined encode/rebuild: the pipeline
# runs at max(stage), so bottleneck attribution = the widest histogram
# (children resolved once — these observe on every slice)
_STAGE_PREFETCH = EC_PIPELINE_STAGE.labels("prefetch")
_STAGE_DECODE = EC_PIPELINE_STAGE.labels("decode")
_STAGE_WRITE = EC_PIPELINE_STAGE.labels("write")
_BYTES_PREFETCH = EC_PIPELINE_BYTES.labels("prefetch")
_BYTES_WRITE = EC_PIPELINE_BYTES.labels("write")
_POOL_BYTES = EC_SLICE_POOL_BYTES.labels()


class _SlicePool:
    """The process's free (DATA_SHARDS, slice_size) uint8 buffers, by
    slice_size: the one place an EC pipeline's slice buffers come from.

    A fresh (10, 16 MiB) array is 40,960 pages to fault in and an
    mmap/munmap pair, and every pipeline of the process takes its one
    address-space lock for each.  Buffers that died with their pipeline
    (numpy hands 160 MiB back with munmap) would have every rpc read its
    first slices into never-touched memory; these outlive the rpc, so a
    server that encodes or rebuilds volume after volume reads into pages
    that are already mapped.  `take` hands out the most recently returned
    buffer, so what a lighter load does not need lies still and ages.

    Ownership: a submitted slice is the codec service's until its future
    resolves, so a buffer comes back ONLY from a writer's success path,
    after the parity is back and the slice's rows are in the shard files.
    A pipeline that fails or is stopped drops what it holds — the pool
    never hands out memory a device transfer may still be reading.

    A reused buffer holds another volume's bytes: a pipeline writes every
    byte it uses (fill_stripe_rows zero-fills past EOF, a tail slice is
    buf[:, :total], the rebuild fills buf[:, :width]).

    Bounded: a pipeline holds at most its own bound at once
    (_PipelineBuffers), so the pool has no more than its pipelines held at
    their peak (eight encodes: 32 buffers, 5 GiB, as before); a buffer
    nobody took for _POOL_IDLE_S is released, so an idle server keeps
    nothing."""

    def __init__(self):
        self._lock = threading.Lock()
        # slice_size -> (returned at, buffer), the longest free first
        self._free: dict[int, deque] = {}
        self._timer: "threading.Timer | None" = None

    def take(self, slice_size: int) -> "tuple[np.ndarray, str]":
        """-> (buffer, "pooled" | "fresh")."""
        with self._lock:
            free = self._free.get(slice_size)
            if free:
                buf = free.pop()[1]
                _POOL_BYTES.dec(buf.nbytes)
                return buf, "pooled"
        return np.empty((DATA_SHARDS, slice_size), dtype=np.uint8), "fresh"

    def give_back(self, buf: np.ndarray) -> None:
        now = time.monotonic()
        with self._lock:
            self._free.setdefault(buf.shape[1], deque()).append((now, buf))
            _POOL_BYTES.inc(buf.nbytes)
            self._release_idle(now)

    def _release_idle(self, now: float) -> None:
        """Lock held.  Drops what has lain free for _POOL_IDLE_S and keeps
        one timer pending while anything is free."""
        oldest = None
        for free in self._free.values():
            while free and now - free[0][0] >= _POOL_IDLE_S:
                _POOL_BYTES.dec(free.popleft()[1].nbytes)
            if free and (oldest is None or free[0][0] < oldest):
                oldest = free[0][0]
        if oldest is not None and self._timer is None:
            self._timer = threading.Timer(
                max(oldest + _POOL_IDLE_S - now, 0.0), self.release_idle)
            self._timer.daemon = True
            self._timer.start()

    def release_idle(self) -> None:
        """What the timer runs."""
        with self._lock:
            self._timer = None
            self._release_idle(time.monotonic())


_SLICE_POOL = _SlicePool()


class _PipelineBuffers:
    """One pipeline's hold on the pool: at most `bound` buffers at once.  A
    reader that asks for one more waits for its writer to give one back,
    which is the pipeline's back-pressure."""

    def __init__(self, pipeline: str, slice_size: int, bound: int,
                 stop: threading.Event):
        self._slice_size, self._stop = slice_size, stop
        self._slots = threading.Semaphore(bound)
        self._taken = {source: EC_SLICE_BUFFERS.labels(pipeline, source)
                       for source in ("pooled", "fresh")}

    def take(self) -> "tuple[np.ndarray | None, str]":
        """-> (buffer, "pooled" | "fresh"); no buffer once the consumer
        has bailed (a failed writer gives nothing back, so a bare wait
        would strand the reader and wedge the pipeline's join)."""
        while not self._stop.is_set():
            if self._slots.acquire(timeout=0.1):
                buf, source = _SLICE_POOL.take(self._slice_size)
                self._taken[source].inc()
                return buf, source
        return None, ""

    def give_back(self, buf: np.ndarray) -> None:
        """For the writer's success path alone (see _SlicePool)."""
        _SLICE_POOL.give_back(buf)
        self._slots.release()


_encodes_lock = threading.Lock()
_encodes_in_flight = 0


@contextlib.contextmanager
def _encode_in_flight():
    """Counts an encode among those this process runs at once (the gauge
    `seaweedfs_ec_encodes_inflight`)."""
    global _encodes_in_flight
    with _encodes_lock:
        _encodes_in_flight += 1
        EC_ENCODES_INFLIGHT.set(_encodes_in_flight)
    try:
        yield
    finally:
        with _encodes_lock:
            _encodes_in_flight -= 1
            EC_ENCODES_INFLIGHT.set(_encodes_in_flight)


def write_sorted_file_from_idx(base_name: str, ext: str = ".ecx") -> None:
    """Generate the sorted .ecx index from the .idx log (ec_encoder.go:27-54)."""
    nm = NeedleMap.load_from_idx(base_name + ".idx")
    nm.write_sorted_index(base_name + ext)


def write_ec_files(base_name: str, codec_name: str = "cpu",
                   slice_size: int = DEFAULT_SLICE, service=None) -> None:
    """Generate .ec00 ~ .ec13 from .dat (ec_encoder.go:57-59)."""
    generate_ec_files(
        base_name,
        large_block_size=LARGE_BLOCK_SIZE,
        small_block_size=SMALL_BLOCK_SIZE,
        codec_name=codec_name,
        slice_size=slice_size,
        service=service,
    )


def generate_ec_files(
    base_name: str,
    large_block_size: int = LARGE_BLOCK_SIZE,
    small_block_size: int = SMALL_BLOCK_SIZE,
    codec_name: str = "cpu",
    slice_size: int = DEFAULT_SLICE,
    progress=None,
    sync: bool = False,
    service=None,
) -> None:
    """`progress(volume_bytes_done)` fires after each slice's shard bytes
    hit the output files — lets callers (bench, shell) report live rates.
    `sync=True` fsyncs every shard file before returning, so a completed
    encode means the shards survive a crash (and so a timed encode shares
    accounting with an fsync'd raw-write baseline).

    `service` routes the GF parity compute through the shared codec
    service (ops.codec_service): slices become queued jobs the scheduler
    coalesces with OTHER concurrent volumes' slices into device-resident
    (or slab-SIMD) batches.  Default: the service engages automatically
    for device codecs when this process holds an accelerator
    (codec_service.service_for_codec); host encodes keep the direct mmap
    path unless a caller that knows it is concurrent passes a service
    explicitly."""
    codec = get_codec(codec_name)
    if service is None:
        service = codec_service.service_for_codec(codec_name)
    dat_path = base_name + ".dat"
    dat_size = os.path.getsize(dat_path)
    # this volume's slices are one stream of the service's, open while the
    # encode runs: beside other encodes' streams they share device batches
    stream = (service.stream(dat_path) if service is not None
              else contextlib.nullcontext())
    with _encode_in_flight(), stream:
        _generate_ec_files(
            base_name, dat_path, dat_size, codec, large_block_size,
            small_block_size, slice_size, progress, sync, service)


def _generate_ec_files(base_name, dat_path, dat_size, codec, large_block_size,
                       small_block_size, slice_size, progress, sync,
                       service) -> None:
    outs = [open(base_name + to_ext(i), "wb") for i in range(TOTAL_SHARDS)]
    try:
        with open(dat_path, "rb") as f:
            if (hasattr(codec, "parity_into") or service is not None) \
                    and not hasattr(codec, "encode_device") and dat_size > 0:
                # host codecs: zero-copy path — stripe rows are views into
                # the mmap'd .dat, consumed in place by the GF kernel and
                # handed to writev as-is; the only user-space byte traffic
                # is the parity output.  On this class of single-core host
                # the pipeline is a SUM of stage costs, so removing the
                # (10, W) gather memcpy and the per-1MB write syscalls is
                # worth ~2x end-to-end.
                _encode_stream_mmap(
                    f, dat_size, outs, codec, large_block_size,
                    small_block_size, slice_size, progress, service,
                )
            else:
                # device codecs: overlap the prefetch thread's disk reads
                # with HBM transfer + kernel via the async dispatch
                _encode_stream_pipelined(
                    f, dat_size, outs, codec, large_block_size,
                    small_block_size, slice_size, progress, service,
                )
        if sync:
            for o in outs:
                o.flush()
                os.fsync(o.fileno())
            # new files also need their directory entry durable
            dfd = os.open(os.path.dirname(os.path.abspath(dat_path))
                          or ".", os.O_RDONLY)
            try:
                os.fsync(dfd)
            finally:
                os.close(dfd)
    finally:
        for o in outs:
            o.close()


def _segments(dat_size: int, large: int, small: int, slice_size: int):
    """Yield (row_start, block_size, col, width) in shard-file write order."""
    processed = 0
    remaining = dat_size
    # large rows: strictly-greater loop per the reference (ec_encoder.go:214)
    while remaining > large * DATA_SHARDS:
        for col in range(0, large, slice_size):
            yield processed, large, col, min(slice_size, large - col)
        remaining -= large * DATA_SHARDS
        processed += large * DATA_SHARDS
    while remaining > 0:
        for col in range(0, small, slice_size):
            yield processed, small, col, min(slice_size, small - col)
        remaining -= small * DATA_SHARDS
        processed += small * DATA_SHARDS


def _slice_tasks(dat_size: int, large: int, small: int, slice_size: int):
    """Group stripe segments into codec-call batches of up to slice_size
    bytes per shard.

    Parity is columnwise, so segments from DIFFERENT stripe rows can share
    one codec call: shard i's bytes for consecutive rows are consecutive in
    its .ecNN file, so a batch is just a per-shard concatenation.  This
    matters enormously for the small-row region (any volume tail, and the
    whole volume when it is under 10GB): without batching every codec call
    is a (10, 1MB) stripe — 16x the dispatch count and, for device codecs,
    16x the host<->HBM round trips.

    Yields lists of (row_start, block_size, col, width) whose widths sum to
    <= slice_size, in shard-file write order.
    """
    batch: list[tuple[int, int, int, int]] = []
    batch_width = 0
    for seg in _segments(dat_size, large, small, slice_size):
        width = seg[3]
        if batch and batch_width + width > slice_size:
            yield batch
            batch, batch_width = [], 0
        batch.append(seg)
        batch_width += width
    if batch:
        yield batch


try:
    _IOV_MAX = os.sysconf("SC_IOV_MAX")
    if _IOV_MAX <= 0:  # sysconf returns -1 for "unlimited/unknown"
        _IOV_MAX = 1024
except (ValueError, OSError, AttributeError):
    _IOV_MAX = 1024


def _writev_all(fd: int, bufs: list) -> None:
    """os.writev with partial-write resume, chunked to IOV_MAX iovecs
    (a small slice_size/small_block ratio can exceed the kernel limit).
    Consumed iovecs advance an index instead of pop(0)-shifting the
    list — the shift made large batches O(n^2) in iovec count."""
    i = 0
    while i < len(bufs):
        n = os.writev(fd, bufs[i : i + _IOV_MAX])
        while i < len(bufs) and n >= len(bufs[i]):
            n -= len(bufs[i])
            i += 1
        if n and i < len(bufs):
            bufs[i] = memoryview(bufs[i])[n:]


def _encode_stream_mmap(
    f, dat_size, outs, codec, large, small, slice_size, progress=None,
    service=None,
) -> None:
    """Single-threaded zero-copy encode for host codecs.

    Per _slice_tasks batch: each stripe row of each segment is a 1-D view
    into the mmap'd .dat (page cache), passed directly to the SIMD GF
    kernel (codec.parity_into) and to writev for the data-shard appends —
    no (10, W) stripe materialisation, no per-MB write() syscalls.  Rows
    that cross EOF fall back to a small zero-padded copy (the reference
    zero-pads tail buffers, ec_encoder.go:162-192); rows fully past EOF
    share one zeros buffer.

    Threads deliberately absent: on a single-core host the prefetch/writer
    threads of the pipelined path only add GIL churn, and the kernel-side
    page-cache copies writev does are CPU work that cannot overlap itself.
    """
    import mmap

    # no MAP_POPULATE: prefaulting a 30GB volume upfront would stall the
    # encode (no progress callbacks) and thrash hosts with RAM < volume;
    # MADV_SEQUENTIAL readahead streams pages just ahead of the kernel
    mm = mmap.mmap(f.fileno(), 0, prot=mmap.PROT_READ)
    view = None
    try:
        if hasattr(mm, "madvise"):
            try:
                mm.madvise(mmap.MADV_SEQUENTIAL)
            except (ValueError, OSError):
                pass
        view = np.frombuffer(mm, dtype=np.uint8)
        n_parity = len(codec.parity_matrix) if hasattr(
            codec, "parity_matrix") else 4
        zeros: np.ndarray | None = None
        done = 0
        parity = np.empty((n_parity, slice_size), dtype=np.uint8)
        for batch in _slice_tasks(dat_size, large, small, slice_size):
            total = sum(seg[3] for seg in batch)
            # per shard: the ordered list of row buffers for this batch
            per_shard: list[list[np.ndarray]] = [[] for _ in range(DATA_SHARDS)]
            for row_start, block, col, width in batch:
                for i in range(DATA_SHARDS):
                    off = row_start + i * block + col
                    if off + width <= dat_size:
                        row = view[off:off + width]
                    elif off >= dat_size:
                        if zeros is None or len(zeros) < width:
                            zeros = np.zeros(
                                max(width, small), dtype=np.uint8)
                        row = zeros[:width]
                    else:
                        row = np.zeros(width, dtype=np.uint8)
                        n = dat_size - off
                        row[:n] = view[off:off + n]
                    per_shard[i].append(row)
            # parity per segment into contiguous per-batch output slabs
            at = 0
            futures = []
            if service is not None:
                # one vectored submit for the whole batch of segments:
                # the service coalesces them (and any concurrent
                # volume's) into one kernel call, and the data-shard
                # writev below overlaps the parity compute
                seg_ins, seg_outs = [], []
                for s, (_, _, _, width) in enumerate(batch):
                    seg_ins.append(
                        [per_shard[i][s] for i in range(DATA_SHARDS)])
                    seg_outs.append(
                        [parity[j, at:at + width] for j in range(n_parity)])
                    at += width
                futures = service.submit_parity_many(seg_ins, seg_outs)
            else:
                for s, (_, _, _, width) in enumerate(batch):
                    codec.parity_into(
                        [per_shard[i][s] for i in range(DATA_SHARDS)],
                        [parity[j, at:at + width] for j in range(n_parity)],
                    )
                    at += width
            for i in range(DATA_SHARDS):
                outs[i].flush()  # keep the buffered layer empty around writev
                _writev_all(outs[i].fileno(), per_shard[i])
            for fut in futures:
                fut.result()  # parity slab must be full before its writev
            for j in range(n_parity):
                outs[DATA_SHARDS + j].flush()
                _writev_all(outs[DATA_SHARDS + j].fileno(),
                            [parity[j, :total]])
            done += total * DATA_SHARDS
            if progress is not None:
                progress(min(done, dat_size))
    finally:
        del view  # release the exported buffer before closing the map
        try:
            mm.close()
        except BufferError:
            pass  # stray view still alive; the map dies with the process


def _encode_stream_pipelined(
    f, dat_size, outs, codec, large, small, slice_size, progress=None,
    service=None,
) -> None:
    """Overlap disk reads with compute for every codec; device codecs
    also overlap HBM transfer + kernel.

    Three stages run concurrently (SURVEY §7 hard part (b)):
      * a prefetch thread reads (10, W) stripe slices from the .dat into a
        bounded queue (disk/page-cache -> host RAM);
      * the main thread dispatches the GF matmul asynchronously (JAX returns
        before the device finishes) — one slice is always in flight;
      * while slice k+1 computes, slice k's data shards are written and its
        parity is read back (the only blocking point) and written.

    The codec's ``encode_device`` takes the slice as host bytes and packs
    it for its kernel itself (rs_pallas.pack_lane_tiles: a free uint32
    view, so the device program is exactly the kernel).
    """
    import queue

    is_device_codec = hasattr(codec, "encode_device")
    vid = os.path.basename(f.name).rsplit(".", 1)[0]  # the spans' `vid`

    q: queue.Queue = queue.Queue(maxsize=2)
    stop = threading.Event()

    def _put(item) -> bool:
        """Bounded put that gives up when the consumer has bailed."""
        while not stop.is_set():
            try:
                q.put(item, timeout=0.1)
                return True
            except queue.Full:
                continue
        return False

    # slice buffers from the process's pool, given back by the writer once
    # a slice's rows are in the shard files (_SlicePool has the rules)
    buffers = _PipelineBuffers("encode", slice_size, _POOL_SLICES, stop)

    def reader() -> None:
        try:
            for batch in _slice_tasks(dat_size, large, small, slice_size):
                total = sum(seg[3] for seg in batch)
                # a wait for the writer is no prefetch
                buf, source = buffers.take()
                if buf is None:
                    return
                # a full slice is the buffer itself: one contiguous block
                # the service can hand to the device as it is
                data = buf if total == slice_size else buf[:, :total]
                with trace.stage("ec.pipeline.prefetch", _STAGE_PREFETCH,
                                 vid=vid, offset=batch[0][0], buffer=source):
                    fill_stripe_rows(f, batch, data)
                _BYTES_PREFETCH.inc(data.nbytes)  # zero fill past EOF included
                if not _put(data):
                    return
        except Exception as e:  # surfaced by the consumer
            _put(e)
            return
        _put(None)

    t = threading.Thread(target=reader, name="ec-encode-prefetch", daemon=True)
    t.start()

    def dispatch(data: np.ndarray):
        """-> parity future — async on the device (or in the codec
        service); synchronous parity for host-only codecs."""
        if service is not None:
            # the codec service owns device transfer + double buffering;
            # slices become jobs it may coalesce with other volumes'
            # (this volume's slices are one stream: a batch takes one)
            return service.submit_parity(data, stream=f.name)
        if not is_device_codec:
            return codec.parity_of(data)
        return codec.encode_device(data)

    # writer thread: shard appends overlap the next slice's compute (the
    # write side is 1.4x the read side, so on write-bound disks this is
    # the difference between sum and max of the two)
    wq: queue.Queue = queue.Queue(maxsize=2)
    write_err: list[Exception] = []
    done = 0

    def writer() -> None:
        nonlocal done
        while True:
            pending = wq.get()
            if pending is None:
                return
            if write_err:
                continue  # drain the queue so producers never block
            try:  # EVERYTHING must land in write_err, or drain() deadlocks
                data, parity = pending
                with trace.stage("ec.pipeline.write", _STAGE_WRITE,
                                 vid=vid, offset=done):
                    for i in range(DATA_SHARDS):
                        outs[i].write(data[i])  # buffer-protocol, no copy
                    # parity is a (P, W) array or a list of P rows (the
                    # codec-service future resolves to a row list)
                    for pi, prow in enumerate(parity):
                        outs[DATA_SHARDS + pi].write(prow)
                _BYTES_WRITE.inc(data.shape[1] * (DATA_SHARDS + len(parity)))
                done += data.shape[1] * DATA_SHARDS
                buffers.give_back(data if data.base is None else data.base)
                if progress is not None:
                    progress(min(done, dat_size))
            except Exception as e:  # surfaced by the main thread
                write_err.append(e)

    wt = threading.Thread(target=writer, name="ec-encode-writer", daemon=True)
    wt.start()

    def drain(pending) -> None:
        data, parity_dev = pending
        if hasattr(parity_dev, "result"):  # codec-service future
            with _STAGE_DECODE.time():  # wait = batch compute completion
                parity = parity_dev.result()
        elif isinstance(parity_dev, np.ndarray):  # host: timed at dispatch
            parity = np.ascontiguousarray(parity_dev)
        else:
            with _STAGE_DECODE.time():  # device readback = compute completion
                parity = np.ascontiguousarray(np.asarray(parity_dev))
        wq.put((data, parity))
        if write_err:
            raise write_err[0]

    # service dispatch is a queue submit, so TWO slices ride in flight
    # (the service double-buffers H2D against compute against D2H);
    # direct device dispatch keeps the original one-async-slice window
    async_mode = is_device_codec or service is not None
    max_pending = 2 if service is not None else 1
    pending_q: deque = deque()
    try:
        while True:
            item = q.get()
            if isinstance(item, Exception):
                raise item
            if item is None:
                break
            if not async_mode:
                # synchronous codec: compute here, overlap only the writes
                with _STAGE_DECODE.time():
                    parity = dispatch(item)
                drain((item, parity))
                continue
            pending_q.append((item, dispatch(item)))
            if len(pending_q) > max_pending:
                drain(pending_q.popleft())
        while pending_q:
            drain(pending_q.popleft())
        wq.put(None)
        wt.join()
        if write_err:
            raise write_err[0]
    finally:
        # unblock the prefetch + writer threads on error paths
        stop.set()
        while True:
            try:
                q.get_nowait()
            except queue.Empty:
                break
        t.join()
        if wt.is_alive():
            while True:
                try:
                    wq.get_nowait()
                except queue.Empty:
                    break
            wq.put(None)
            wt.join()


def fill_stripe_rows(f, batch, dest: np.ndarray) -> None:
    """Fill dest[(DATA_SHARDS, total_width)] with one _slice_tasks batch:
    row i gathers the batch's segments at `row_start + i*block + col`.
    The single home of the stripe-gather arithmetic — the serial and
    multi-volume batch encoders both call it, so their geometry cannot
    drift."""
    for i in range(DATA_SHARDS):
        row = memoryview(dest[i])
        at = 0
        for row_start, block, col, width in batch:
            _read_into(f, row_start + i * block + col, row[at:at + width])
            at += width


def _read_at(f, offset: int, length: int) -> np.ndarray:
    """Read with zero-fill past EOF (the reference zero-pads tail buffers)."""
    arr = np.empty(length, dtype=np.uint8)
    _read_into(f, offset, memoryview(arr))
    return arr


def _read_into(f, offset: int, dest: memoryview) -> None:
    """Fill `dest` from f[offset:], zero-filling past EOF, without
    intermediate bytes allocations (readinto straight to the stripe row)."""
    f.seek(offset)
    n = f.readinto(dest)
    if n is None:
        n = 0
    while 0 < n < len(dest):  # short read mid-file
        more = f.readinto(dest[n:])
        if not more:
            break
        n += more
    if n < len(dest):
        dest[n:] = bytes(len(dest) - n)


# fires once per rebuilt slice, before the source reads — chaos tests
# kill a rebuild mid-stream here and assert the clean-error contract
# (partial .ecNN outputs removed, retry succeeds)
FP_REBUILD_READ = faultpoint.register("ec.rebuild.read")


def _pread_into(fd: int, dest, offset: int) -> None:
    """Positioned read straight into a writable buffer (numpy row), no
    intermediate bytes object; loops short reads and raises on EOF
    (shard files have a fixed extent, so a short tail means a racing
    truncate/re-copy)."""
    got = 0
    length = len(dest)
    while got < length:
        n = os.preadv(fd, [dest[got:]], offset + got)
        if n <= 0:
            raise IOError(f"short shard read at {offset + got}")
        got += n


def _pick_rebuild_sources(
    base_name: str, local: list[int], remote_fetch, partial=None
) -> tuple[list[int], set[int], set[int]]:
    """-> (DATA_SHARDS source ids local-first, the remote subset of those,
    ALL remotely-available shard ids).

    With a partial-repair client, remote availability and ORDER come
    from its holder map — same-rack sources are drawn before cross-rack
    ones (topology.placement.order_ec_sources), so the expensive links
    carry as few partials as possible.  Without one, remote availability
    is probed with a 1-byte interval read through the same fetch hook
    the streaming loop uses.  Either way every non-local shard is
    covered so the caller can limit the rebuild to GLOBALLY missing
    shards — regenerating a local copy of a shard that is healthy on a
    peer would double the repair traffic and register duplicate holders
    with the master."""
    sources = list(local[:DATA_SHARDS])
    remote: set[int] = set()
    remote_available: set[int] = set()
    if partial is not None:
        holders = {sid: h for sid, h in partial.remote_shards().items()
                   if sid not in local}
        remote_available = set(holders)
        # the holder map can list a dead node (heartbeat not yet timed
        # out); a 1-byte probe of each CHOSEN source keeps that from
        # sinking the whole rebuild when a live alternate shard exists —
        # the map still decides what is globally missing, exactly like
        # the shell's planning.  Mass-repair batch clients skip the
        # probes (trust_holders): their maps were refreshed by the
        # master's dead-node notice moments ago, and a stale holder
        # costs one per-volume fallback, not a stalled batch.
        probe = (remote_fetch is not None
                 and not getattr(partial, "trust_holders", False))
        for sid in partial.order(holders):
            if len(sources) >= DATA_SHARDS:
                break
            if probe:
                try:
                    if not remote_fetch(sid, 0, 1):
                        continue
                except Exception:
                    continue
            sources.append(sid)
            remote.add(sid)
    elif remote_fetch is not None:
        for sid in range(TOTAL_SHARDS):
            if sid in local:
                continue
            try:
                probe = remote_fetch(sid, 0, 1)
            except Exception:
                probe = None
            if probe:
                remote_available.add(sid)
                if len(sources) < DATA_SHARDS:
                    sources.append(sid)
                    remote.add(sid)
    if len(sources) < DATA_SHARDS:
        raise ValueError(
            f"cannot rebuild: only {len(sources)} of {TOTAL_SHARDS} shards "
            f"reachable ({len(local)} local)"
        )
    return sources, remote, remote_available


def rebuild_ec_files(base_name: str, codec_name: str = "cpu",
                     slice_size: int = DEFAULT_SLICE,
                     progress=None, remote_fetch=None,
                     shard_size: int | None = None,
                     service=None, partial=None) -> list[int]:
    """Regenerate whichever .ecNN files are missing (ec_encoder.go:61-62).

    Runs the same three-stage pipeline as the encode path: a prefetch
    thread preads the DATA_SHARDS source shards IN PARALLEL into pooled
    slice buffers, the main thread applies the cached decode plan (async
    device dispatch for device codecs, one slice always in flight; host
    codecs compute inline on the SIMD kernel), and a writer thread
    appends the reconstructed shards — so the rebuild runs at
    max(read, decode, write) instead of their sum, and reads exactly
    DATA_SHARDS sources instead of every present shard.

    `remote_fetch(shard_id, offset, length) -> bytes|None` (the same
    contract as EcVolume.remote_fetch) lets a node holding fewer than
    DATA_SHARDS local shards stream missing source intervals from peers
    instead of failing; `shard_size` must be given when no local shard
    exists to size the stream from (a partial client's probe can answer
    it too).

    `partial` (a storage.ec.partial.PartialRepairClient) switches remote
    sourcing to the partial-sum protocol: remote sources multiply their
    intervals by their decode-plan columns locally and this node pulls
    ONE aggregated (missing x width) partial per rack instead of every
    raw interval — the local shards' plan columns are applied here and
    XOR'd in, so output bytes are identical by GF linearity.  Any
    partial failure (source death mid-stream, stale holder) degrades
    permanently to the full-fetch path for the rest of the rebuild
    (seaweedfs_ec_partial_fallback_total{path="rebuild"}).

    On any error the partial .ecNN outputs are REMOVED — a failed
    rebuild leaves no truncated shard for a later mount to trust.
    Returns rebuilt ids; `progress(shard_bytes_done)` fires after each
    reconstructed slice hits the output files.
    """
    import queue
    from concurrent.futures import ThreadPoolExecutor

    from ...util.executors import MeteredThreadPoolExecutor

    codec = get_codec(codec_name)
    impl = getattr(codec, "_impl", codec_name)
    local = [i for i in range(TOTAL_SHARDS)
             if os.path.exists(base_name + to_ext(i))]
    if len(local) == TOTAL_SHARDS:
        return []
    picked = None
    if partial is not None:
        try:
            picked = _pick_rebuild_sources(
                base_name, local, remote_fetch, partial)
        except ValueError:
            # the holder map cannot supply 10 sources (stale locations):
            # let the probing path have a try before giving up
            EC_PARTIAL_FALLBACK.labels("rebuild").inc()
            partial = None
    if picked is None:
        picked = _pick_rebuild_sources(base_name, local, remote_fetch)
    sources, remote, remote_available = picked
    # rebuild only GLOBALLY missing shards: a shard healthy on a peer
    # needs a copy rpc, not a decode (see _pick_rebuild_sources)
    missing = [i for i in range(TOTAL_SHARDS)
               if i not in local and i not in remote_available]
    if not missing:
        return []
    if local:
        shard_size = os.path.getsize(base_name + to_ext(local[0]))
    elif shard_size is None:
        if partial is not None:
            shard_size = partial.shard_size() or None
        if shard_size is None:
            raise ValueError(
                "cannot rebuild: no local shard and no shard_size given")

    # the whole decode program for this loss pattern, from the shared
    # plan cache: one 10x10 inversion per survivor set, not per slice
    rows = gf256.decode_plan_for(
        codec.matrix, DATA_SHARDS, sources, tuple(missing))

    # partial mode: split the plan by source locality — columns for
    # local sources are applied HERE, columns for remote sources ship to
    # them as coefficient rows and come back pre-multiplied + pre-XOR'd
    local_srcs = [s for s in sources if s not in remote]
    n_local = len(local_srcs)
    use_partial = partial is not None and bool(remote)
    if use_partial and remote_fetch is not None:
        # the protocol pulls racks x missing x width; when that exceeds
        # the plain sources x width (many lost shards, few remote
        # sources), full fetch IS the bandwidth-optimal path.  Without
        # a full-fetch transport the partial path stays on regardless —
        # it is the only remote sourcing available.
        try:
            use_partial = partial.ingress_advantage(
                remote, len(missing)) >= 1.0
        except Exception:  # noqa: BLE001 — fetch failures fall back anyway
            pass
    local_plan = None
    coef_by_shard: dict[int, np.ndarray] = {}
    if use_partial:
        local_cols = [i for i, s in enumerate(sources) if s not in remote]
        if local_cols:
            local_plan = np.ascontiguousarray(rows[:, local_cols])
        coef_by_shard = {s: rows[:, i] for i, s in enumerate(sources)
                         if s in remote}
    # ingress locality labels for the full-fetch path (the partial
    # client labels its own aggregated pulls).  Evaluated per fetch, not
    # precomputed: the fetcher reports the holder it ACTUALLY read from,
    # which can shift cross-rack mid-rebuild when a same-rack peer dies.
    loc_of = getattr(remote_fetch, "locality_of", None)
    if loc_of is None and partial is not None:
        loc_of = partial.locality_of

    def _src_label(sid: int) -> str:
        try:
            return loc_of(sid) if loc_of is not None else "dc"
        except Exception:  # noqa: BLE001 — labels must never fail a read
            return "dc"

    label_child = {lab: EC_REBUILD_BYTES.labels(lab)
                   for lab in ("local", "rack", "dc")}
    if service is None:
        service = codec_service.service_for_codec(codec_name)
    is_device_codec = hasattr(codec, "apply_rows_device")

    # everything that creates on-disk or OS state is populated INSIDE the
    # guarded try below: the finally owns closing handles and removing
    # partial outputs, so no setup failure (buffer MemoryError, thread
    # spawn refusal) can leave a zero-length .ecNN for a mount to trust
    ins: dict[int, object] = {}
    outs: dict[int, object] = {}
    t_start = time.perf_counter()
    vid = os.path.basename(base_name)  # the spans' `vid`

    q: queue.Queue = queue.Queue(maxsize=2)
    stop = threading.Event()
    # slice buffers from the process's pool, as the encode's (_SlicePool)
    buffers = _PipelineBuffers("rebuild", slice_size, _REBUILD_SLICES, stop)

    def _put(item) -> bool:
        while not stop.is_set():
            try:
                q.put(item, timeout=0.1)
                return True
            except queue.Full:
                continue
        return False

    def _read_source(sid: int, off: int, dest: np.ndarray) -> int:
        """Fill one source row; returns the bytes fetched remotely."""
        width = len(dest)
        if sid in remote:
            buf = remote_fetch(sid, off, width)
            if buf is None or len(buf) != width:
                raise IOError(
                    f"remote shard {sid} unavailable during rebuild")
            dest[:] = np.frombuffer(buf, dtype=np.uint8)
            return width
        _pread_into(ins[sid].fileno(), dest, off)
        return 0

    part_on = [use_partial]  # sticky: one failure drops to full fetch

    def _fetch_partial(off: int, width: int) -> "np.ndarray | None":
        """-> (missing, width) aggregated remote partial, or None after
        a clean, PERMANENT fallback to the full-fetch path."""
        if not part_on[0]:
            return None
        try:
            return partial.fetch(coef_by_shard, len(missing), off, width)
        except Exception:
            if remote_fetch is None:
                raise  # no fallback transport: surface the clean error
            part_on[0] = False
            EC_PARTIAL_FALLBACK.labels("rebuild").inc()
            return None

    def reader(fetch_pool: ThreadPoolExecutor) -> None:
        try:
            for off in range(0, shard_size, slice_size):
                width = min(slice_size, shard_size - off)
                faultpoint.inject(FP_REBUILD_READ, ctx=base_name)
                buf, source = buffers.take()
                if buf is None:
                    return
                with trace.stage("ec.pipeline.prefetch", _STAGE_PREFETCH,
                                 vid=vid, offset=off, buffer=source):
                    part = _fetch_partial(off, width)
                    if part is not None:
                        # only the LOCAL source rows are read here; the
                        # remote contribution arrived pre-combined
                        view = buf[:n_local, :width]
                        for j, sid in enumerate(local_srcs):
                            _pread_into(ins[sid].fileno(), view[j], off)
                        label_child["local"].inc(n_local * width)
                    else:
                        view = buf[:, :width]
                        fetched = list(fetch_pool.map(
                            lambda j: _read_source(sources[j], off, view[j]),
                            range(DATA_SHARDS)))
                        for j, nb in enumerate(fetched):
                            if nb:
                                label_child[_src_label(sources[j])].inc(nb)
                        label_child["local"].inc(
                            DATA_SHARDS * width - sum(fetched))
                _BYTES_PREFETCH.inc(view.nbytes)
                if not _put((buf, view, off, width, part)):
                    return
        except Exception as e:  # surfaced by the consumer
            _put(e)
            return
        _put(None)

    wq: queue.Queue = queue.Queue(maxsize=2)
    write_err: list[Exception] = []

    def writer() -> None:
        while True:
            pending = wq.get()
            if pending is None:
                return
            if write_err:
                continue  # drain so producers never block
            try:
                buf, rebuilt, off, width = pending
                with trace.stage("ec.pipeline.write", _STAGE_WRITE,
                                 vid=vid, offset=off):
                    for row, sid in zip(rebuilt, missing):
                        outs[sid].write(row)
                _BYTES_WRITE.inc(len(missing) * width)
                buffers.give_back(buf)  # source slice fully consumed
                if progress is not None:
                    progress(off + width)
            except Exception as e:  # surfaced by the main thread
                write_err.append(e)

    fetch_pool: "ThreadPoolExecutor | None" = None
    rt = threading.Thread(target=lambda: reader(fetch_pool),
                          name="ec-rebuild-prefetch", daemon=True)
    wt = threading.Thread(target=writer, name="ec-rebuild-writer",
                          daemon=True)

    def drain(pending) -> None:
        buf, dev, off, width, part = pending
        with _STAGE_DECODE.time():  # readback/wait = decode completion
            if hasattr(dev, "result"):  # codec-service future -> row list
                rebuilt = dev.result()
            else:
                rebuilt = np.ascontiguousarray(
                    np.asarray(dev, dtype=np.uint8))
            if part is not None:  # GF addition completes the decode
                rebuilt = np.bitwise_xor(
                    np.asarray(rebuilt, dtype=np.uint8), part)
        wq.put((buf, rebuilt, off, width))
        if write_err:
            raise write_err[0]

    # service submits are queue hops, so two slices ride in flight (the
    # service double-buffers); direct device dispatch keeps one async
    async_mode = is_device_codec or service is not None
    max_pending = 2 if service is not None else 1
    pending_q: deque = deque()
    ok = False
    try:
        for i in sources:
            if i not in remote:
                ins[i] = open(base_name + to_ext(i), "rb")
        for i in missing:
            outs[i] = open(base_name + to_ext(i), "wb")
        fetch_pool = MeteredThreadPoolExecutor(
            max_workers=DATA_SHARDS, name="ec_rebuild_read",
            thread_name_prefix="ec-rebuild-read")
        rt.start()
        wt.start()
        while True:
            item = q.get()
            if isinstance(item, Exception):
                raise item
            if item is None:
                break
            buf, view, off, width, part = item
            if part is not None and n_local == 0:
                # every source was remote: the aggregated partial IS the
                # rebuilt rows — zero GF compute at the rebuilder
                wq.put((buf, list(part), off, width))
                if write_err:
                    raise write_err[0]
                continue
            plan_mtx = local_plan if part is not None else rows
            if not async_mode:
                # host codec: SIMD decode inline, overlap only the I/O
                with _STAGE_DECODE.time():
                    rebuilt = codec.apply_rows(plan_mtx, list(view))
                    if part is not None:
                        rebuilt = np.bitwise_xor(
                            np.asarray(rebuilt, dtype=np.uint8), part)
                wq.put((buf, rebuilt, off, width))
                if write_err:
                    raise write_err[0]
                continue
            if service is not None:
                # a full slice is the pooled buffer itself, which the
                # service can hand to the device as it is; `buf` is the
                # service's until drain() has the result, and only the
                # writer, after that, recycles it
                dev = service.submit_apply(plan_mtx, view, stream=base_name)
            else:
                dev = codec.apply_rows_device(plan_mtx, view)
            pending_q.append((buf, dev, off, width, part))
            if len(pending_q) > max_pending:
                drain(pending_q.popleft())  # k reads back while k+1 computes
        while pending_q:
            drain(pending_q.popleft())
        wq.put(None)
        wt.join()
        if write_err:
            raise write_err[0]
        ok = True
    finally:
        stop.set()
        while True:
            try:
                q.get_nowait()
            except queue.Empty:
                break
        if rt.ident is not None:  # never-started threads cannot be joined
            rt.join()
        if wt.ident is not None and wt.is_alive():
            while True:
                try:
                    wq.get_nowait()
                except queue.Empty:
                    break
            wq.put(None)
            wt.join()
        if fetch_pool is not None:
            fetch_pool.shutdown(wait=False)
        for h in ins.values():
            h.close()
        for h in outs.values():
            h.close()
        EC_REBUILD_SECONDS.labels(impl).observe(time.perf_counter() - t_start)
        EC_REBUILD_RESULT.labels("ok" if ok else "error").inc()
        if ok:
            EC_REBUILD_SHARDS.inc(len(missing))
        else:
            # clean-error contract: no truncated shard file survives a
            # failed rebuild for a later mount to trust
            for sid in missing:
                try:
                    os.remove(base_name + to_ext(sid))
                except FileNotFoundError:
                    pass
    return missing
