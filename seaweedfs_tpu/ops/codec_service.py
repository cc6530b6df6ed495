"""Pod-scale EC codec service: batched, double-buffered GF(2⁸) dispatch.

One bounded submission queue sits between every GF caller — the file
encoder, the rebuild pipeline, degraded reads, bench — and the compute
backend.  A scheduler thread drains it, coalesces jobs into one batch,
and dispatches the batch as a single compute call:

* **device mode**: a batch is the head job plus queued jobs of OTHER
  streams (slices of different volumes' encodes or rebuilds) that share
  its SHAPE — output and input rows, whatever their matrices — and its
  width bucket: a power of two of them, so that the compiled shapes stay
  few, up to what the devices' memory holds with two batches
  in flight (``_device_max_volumes``).  It runs as ONE device program
  from ``parallel.mesh`` over every device the process holds: each job's
  ``(S, W)`` array is an argument of its own, columns spread over all the
  devices, every job's matrix is an argument too (a rebuild's decode plan
  is one of up to fourteen after a node's loss, and none of them is a
  compile), and the ``(V, R, W)`` stack of results comes back as one
  array: on a mesh the devices gather it among themselves, so the host
  fetches it whole from one of them.  It sets out for the host when the
  batch is dispatched (``copy_to_host_async``), on the runtime's threads,
  and the scheduler's thread only picks up the finished copy: it makes no
  pass over result bytes of its own.  Both cross the bus as uint32 lane
  tiles, views of the host's bytes in the layout the device keeps, so
  neither way is anything re-laid on the host.  A job that is a whole
  block already (a contiguous ``(S, W)`` array whose width is its own
  bucket) goes in as it is; any other job is first copied into a reused
  staging buffer padded to the bucket.  Up to two batches stay in flight:
  while batch *k* computes, batch *k+1* is assembled and dispatched, and
  *k*'s readback overlaps *k+1*'s compute — replacing the encoder's
  one-async-slice rule with true H2D/compute/D2H double buffering.
  Jobs of class ``read`` (one lost interval of a degraded GET each, a
  byte to a MiB wide) batch the other way: the queued reads that share
  the head's decode plan go side by side into ONE staged block of one of
  four widths (``_READ_BUCKETS``) — a block is one matrix's columns — so
  a shape has four programs, compiled when a volume loses a shard
  (``warm_reads``), and reads of any lengths share a batch.

* **host mode**: the SAME scheduler runs on the C++ SIMD codec, so the
  batching and fairness properties hold on TPU-less hosts.  Small jobs
  coalesce column-wise into one reused slab and one native call; larger
  jobs run back to back through a prepared-pointer kernel entry
  (``native.gf_apply_fast``) that skips the ~15-20us of per-call Python
  the direct path pays.  On overhead-bound small-slice workloads this is
  where the aggregate win comes from: N producers' per-slice Python
  collapses into one worker's per-batch Python.

Callers that hold many independent jobs at once (the encoder has a whole
batch of stripe segments in hand) use the vectored ``submit_*_many``
entries: one lock acquisition and one wakeup for the group, which
matters more than any compute trick when jobs are tens of KB.

Fairness: batches always start from the queue HEAD (the oldest job), so
a saturating producer of one job class cannot starve another past one
batch's service time.  Byte identity with ``cpu_simd`` is structural:
host mode calls the same kernel, device mode runs the GF program of
ops/rs_jax.py pinned byte-identical to the reference in
tests/test_gf_program_args.py.  A host batch, whose small jobs share one
slab and one kernel call, takes jobs of one matrix only.

Env knobs (all ``SEAWEEDFS_TPU_EC_SERVICE_*``): ``QUEUE`` (bound, 64),
``BATCH`` (max jobs/batch, 16), ``BATCH_MB`` (host mode: max input
MB/batch, 64; a device batch is capped by the devices' memory instead),
``COALESCE_KB`` (host slab threshold per job, 16), and the top-level
``SEAWEEDFS_TPU_EC_SERVICE`` ("0" disables every default wiring).
"""

from __future__ import annotations

import contextlib
import os
import threading
import time
from collections import deque

import numpy as np

from ..stats.metrics import (
    EC_SERVICE_BATCH_BYTES,
    EC_SERVICE_BATCH_JOBS,
    EC_SERVICE_BATCH_PLANS,
    EC_SERVICE_BLOCK_BYTES,
    EC_SERVICE_FLUSH,
    EC_SERVICE_INFLIGHT,
    EC_SERVICE_INPUT_BYTES,
    EC_SERVICE_JOBS,
    EC_SERVICE_QUEUE_DEPTH,
    EC_SERVICE_READBACKS,
    EC_SERVICE_STAGE,
)
from ..telemetry import trace
from .codec import DEVICE_CODEC_NAMES as _DEVICE_CODECS
from .codec import resolve_codec_name
from .rs_cpu import ReedSolomon

DATA_SHARDS = 10
PARITY_SHARDS = 4

# A job's class: `read` is one lost interval of a degraded GET (KB to a
# MiB, a client waits on it), `pipeline` a slice of an encode or rebuild
# (and anything else).  A batch holds jobs of one class, and every
# observation of the batch carries it.
JOB_CLASSES = ("pipeline", "read")
# In device mode `compute` and `readback` are the coarser stages of before
# the split, observed with the extent they always had (`enqueue`, and
# `device_wait` + `d2h`): the benchmark's svc_devwait_s_per_GB.* reads
# them, and only a `benchmark` PR may repoint it.  `parked` and `handback`
# are the two waits of a job's round trip no span covers: a dispatched
# device batch's wait in `inflight` until the scheduler's thread comes back
# for it, and a delivered job's wait until its requester runs again.  With
# them, queue_wait + build + enqueue + parked + device_wait + d2h + deliver
# + handback is a job's submit -> result().
_STAGE = {c: {st: EC_SERVICE_STAGE.labels(st, c) for st in (
    "queue_wait", "build", "enqueue", "parked", "device_wait", "d2h",
    "deliver", "handback", "compute", "readback")} for c in JOB_CLASSES}
_INPUT_BYTES = {p: EC_SERVICE_INPUT_BYTES.labels(p)
                for p in ("direct", "staged")}
_BLOCK_BYTES = {c: EC_SERVICE_BLOCK_BYTES.labels(c) for c in JOB_CLASSES}
_BATCH_JOBS = {c: EC_SERVICE_BATCH_JOBS.labels(c) for c in JOB_CLASSES}
_BATCH_PLANS = {c: EC_SERVICE_BATCH_PLANS.labels(c) for c in JOB_CLASSES}
_BATCH_BYTES = {c: EC_SERVICE_BATCH_BYTES.labels(c) for c in JOB_CLASSES}
_READBACKS = {c: {st: EC_SERVICE_READBACKS.labels(st, c)
                  for st in ("ready", "waited")} for c in JOB_CLASSES}
# A device batch of read jobs is ONE block: the intervals of reads under
# one decode plan side by side in a staging buffer of one of these widths
# (the sum of theirs, up to the last), so that a shape has four programs
# whatever the interval lengths and however many reads meet in the queue —
# few enough to compile when a volume loses a shard (`warm_reads`).  The GF
# work is columnwise: whose column a byte is changes nothing.  Multiples of
# a lane tile on up to sixteen devices.
_READ_BUCKETS = (64 << 10, 256 << 10, 1 << 20, 4 << 20)

# What a device batch may occupy, from what the devices can hold, in bytes
# on the device per byte of one job's padded (S, w_pad) input.  Resident per
# job of a batch in flight: its input and its result.  Once per program,
# whatever V is: the XOR network's temporaries (the doubled multiples of
# every row); `memory_stats` does not count them, the compiler does, and
# refuses a program that does not fit.  Both constants are the uint8
# program's of before (2.01 and 12.4: PERF.md §6, PR 30), kept as upper
# bounds so that the cap stays the one measured then: as uint32 lane tiles
# nothing pads (ten rows in, four out: `peak_bytes_in_use` read 2.80 x the
# block's bytes with two batches in flight at V = 1, 2, 4 and 8 on a v5e,
# 1.40 a batch) and the temporaries are 7.1 x one job's bytes (PERF.md §6,
# PR 31; 3.2 since the matrix is an argument: PR 40, AOT).  Two batches are
# in flight and a batch may take _HBM_SHARE of each device's memory.  On a
# mesh of n devices the result is gathered (PR 38): a device holds its 1/n
# of a job's input and ALL of its result, 1 + 0.4 n in these units where
# the column-sharded result's was 1.4, and the gather keeps a second whole
# result as a temporary (the compiler's count for a described v5e:2x2,
# (10, 16 MiB) jobs: 104 MiB of arguments and output a job a chip,
# temporaries 160 / 513 / 1,026 MiB at V = 1 / 8 / 16):
# `_resident_per_job_byte` adds both, 2 x 0.4 for every device but one, so
# the cap still refuses what the compiler would (tests/test_tpu_compile.py).
_HBM_RESIDENT_PER_JOB_BYTE = 2.05
_HBM_TEMP_PER_JOB_BYTE = 12.5
_HBM_SHARE = 0.75
_HBM_BYTES_UNREPORTED = 16 << 30  # a backend without memory_stats (CPU)
# staging buffers kept for reuse per width bucket: three batches' worth
# (one being built, two in flight) of the eight streams a server holds
_STAGING_KEPT = 24
# what the spans of a batch's two bus crossings say of its form
_LAYOUT = "u32x128"


def _env_int(name: str, default: int) -> int:
    try:
        return int(os.environ.get(name, str(default)))
    except ValueError:
        return default


class _Job:
    __slots__ = ("kind", "key", "plan", "rows", "data", "width", "out",
                 "stream", "cls", "event", "result", "error", "t_submit",
                 "t_set")

    def __init__(self, kind, key, plan, rows, data, width, out, stream=None,
                 cls="pipeline"):
        self.kind = kind
        self.cls = cls
        # jobs of one stream (the consecutive slices of one volume's
        # pipeline) never share a device batch: see _collect_locked
        self.stream = stream
        # (class, R, S): jobs of one key share a device batch, each with
        # its own matrix; `plan` (the matrix's bytes) matters only where a
        # batch is ONE matrix's columns (read blocks, host slabs)
        self.key = key
        self.plan = plan
        self.rows = rows
        # (S, W) uint8 ndarray, or a list of S equal-length 1-D rows
        # (e.g. zero-copy views into an mmap'd .dat)
        self.data = data
        self.width = width
        self.out = out
        self.event = threading.Event()
        self.result = None  # (R, W) array-like of rows once delivered
        self.error: "Exception | None" = None
        self.t_submit = time.perf_counter()
        # perf_counter just before the scheduler sets `event` (_deliver,
        # _fail); 0.0 once the requester's handback is observed, or for a
        # job delivered inline
        self.t_set = 0.0


class CodecFuture:
    """Handle for a submitted job; ``result()`` blocks until delivery
    and returns an (R, W) array-like — iterate it for the output rows."""

    __slots__ = ("_job",)

    def __init__(self, job: _Job):
        self._job = job

    def done(self) -> bool:
        return self._job.event.is_set()

    def result(self, timeout: "float | None" = None):
        job = self._job
        if not job.event.wait(timeout):
            raise TimeoutError("codec service job not done")
        if job.t_set:
            # the first result() after delivery: the requester's wait from
            # the scheduler's event.set() until it runs again
            _STAGE[job.cls]["handback"].observe(
                time.perf_counter() - job.t_set)
            job.t_set = 0.0
        if job.error is not None:
            raise job.error
        return job.result


class CodecService:
    """Batched GF(2⁸) dispatch behind a bounded queue.

    ``mode``: ``host`` (SIMD), ``device`` (mesh-sharded jax), or ``auto``
    (device iff ``codec_name`` names a device codec).  A device-mode
    service whose jax backend cannot initialise fails its jobs — it never
    degrades to the host codec.

    A job's input belongs to the service until its future resolves: the
    caller keeps it alive and does not write to it before then.  The
    device may be handed the caller's own array, and its H2D transfer
    runs on the runtime's threads after dispatch has returned, so a
    buffer recycled early is read half-overwritten.
    """

    def __init__(self, mode: str = "auto", codec_name: str = "cpu",
                 data_shards: int = DATA_SHARDS,
                 parity_shards: int = PARITY_SHARDS,
                 max_batch: "int | None" = None,
                 max_queue: "int | None" = None,
                 max_batch_mb: "int | None" = None,
                 coalesce_kb: "int | None" = None,
                 mesh=None):
        if mode not in ("auto", "host", "device"):
            raise ValueError(f"unknown codec service mode {mode!r}")
        if mode == "auto":
            mode = "device" if codec_name in _DEVICE_CODECS else "host"
        self.mode = mode
        self.codec_name = codec_name
        self.data_shards = data_shards
        self.parity_shards = parity_shards
        self._rs = ReedSolomon(data_shards, parity_shards)
        self.matrix = self._rs.matrix
        self.parity_matrix = np.ascontiguousarray(
            self._rs.parity_matrix, dtype=np.uint8)
        self._parity_plan = self.parity_matrix.tobytes()
        self.max_batch = max_batch if max_batch is not None else _env_int(
            "SEAWEEDFS_TPU_EC_SERVICE_BATCH", 16)
        self.max_queue = max_queue if max_queue is not None else _env_int(
            "SEAWEEDFS_TPU_EC_SERVICE_QUEUE", 64)
        # host mode counts a batch's real input bytes against this; a
        # device batch is capped by _device_max_volumes instead
        self.max_batch_bytes = (
            max_batch_mb if max_batch_mb is not None else _env_int(
                "SEAWEEDFS_TPU_EC_SERVICE_BATCH_MB", 64)) << 20
        self._device_bytes: "int | None" = None  # _device_max_volumes
        self.coalesce_bytes = (
            coalesce_kb if coalesce_kb is not None else _env_int(
                "SEAWEEDFS_TPU_EC_SERVICE_COALESCE_KB", 16)) << 10
        self._mesh = mesh
        self._batch_seq = 0  # scheduler-thread-only: the spans' `batch`
        # (S, w_pad) staging buffers of finished device batches by w_pad,
        # for the next job that has to be staged (scheduler-thread-only):
        # a fresh 80 MiB buffer is 20,480 page faults, a reused one a memcpy
        self._staging: dict[int, list[np.ndarray]] = {}
        # open streams (stream()) by name -> how often opened
        self._streams: dict = {}
        self._streams_lock = threading.Lock()
        # (R, S, w_pad) -> the largest V whose program is compiled
        # (warm_pipeline, warm_reads)
        self._warmed: dict = {}
        self._warm_lock = threading.Lock()
        self._q: deque[_Job] = deque()
        self._cond = threading.Condition()
        self._open = True
        self._thread: "threading.Thread | None" = None
        self._thread_err: "Exception | None" = None
        # reused input slab for host coalescing (scheduler-thread-only):
        # a fresh np.empty per batch pays more in page faults than the
        # kernel call it feeds (measured 0.47s build vs 0.15s compute)
        self._slab_in: "np.ndarray | None" = None
        # metric children resolved once — the submit/deliver hot path
        # must not pay registry locks per job
        self._depth_child = EC_SERVICE_QUEUE_DEPTH.labels()
        self._inflight_child = EC_SERVICE_INFLIGHT.labels()
        self._job_ok = {k: EC_SERVICE_JOBS.labels(k, "ok")
                        for k in ("parity", "apply")}
        self._job_err = {k: EC_SERVICE_JOBS.labels(k, "error")
                         for k in ("parity", "apply")}
        self._flush_children = {r: EC_SERVICE_FLUSH.labels(r)
                                for r in ("full", "bytes", "ready", "drain")}

    # -- submission -------------------------------------------------------

    @contextlib.contextmanager
    def stream(self, name):
        """Holds ``name`` open as a stream: a pipeline that submits one
        volume's slices one after another under ``stream=name`` keeps it
        open for as long as it runs.  While several are open their jobs
        share device batches, and no batch holds more jobs than there are
        streams, so a job submitted under an open stream first finds every
        program such a batch can take compiled (``warm_pipeline``).  A
        stream that is open alone changes nothing."""
        with self._streams_lock:
            self._streams[name] = self._streams.get(name, 0) + 1
        try:
            yield
        finally:
            with self._streams_lock:
                self._streams[name] -= 1
                if not self._streams[name]:
                    del self._streams[name]

    def submit_parity(self, data, out=None, stream=None) -> CodecFuture:
        """(data_shards, W) -> future of the parity rows.  ``data`` is
        the service's until the future resolves (see the class).  A
        pipeline that submits one volume's slices one after another names
        itself as their ``stream`` (and holds it open, see ``stream()``):
        a device batch takes one job of a stream, so it fills with OTHER
        volumes' slices and a lone pipeline's slices keep going to the
        device one by one, as they are."""
        return self._submit_many(
            "parity", self.parity_matrix, self._parity_plan,
            (data,), (out,), stream)[0]

    def submit_parity_many(self, datas, outs=None) -> list[CodecFuture]:
        """Vectored submit: one lock/wakeup for a group of parity jobs —
        callers with a batch of independent segments in hand (the mmap
        encoder) pay the queue overhead once, not per segment."""
        if outs is None:
            outs = (None,) * len(datas)
        return self._submit_many(
            "parity", self.parity_matrix, self._parity_plan, datas, outs)

    def submit_apply(self, rows: np.ndarray, inputs, out=None,
                     stream=None, job_class="pipeline") -> CodecFuture:
        """Arbitrary (R, S) GF matrix x S input rows -> future of R rows.
        ``inputs`` is the service's until the future resolves (see the
        class); ``stream`` as in ``submit_parity``; ``job_class`` one of
        ``JOB_CLASSES``."""
        rows = np.ascontiguousarray(rows, dtype=np.uint8)
        if rows.ndim != 2:
            raise ValueError("rows must be a 2-D GF matrix")
        if job_class not in JOB_CLASSES:
            raise ValueError(f"unknown job class {job_class!r}")
        return self._submit_many(
            "apply", rows, rows.tobytes(), (inputs,), (out,),
            stream, job_class)[0]

    def submit_apply_many(self, rows: np.ndarray, inputs_list,
                          outs=None) -> list[CodecFuture]:
        rows = np.ascontiguousarray(rows, dtype=np.uint8)
        if rows.ndim != 2:
            raise ValueError("rows must be a 2-D GF matrix")
        if outs is None:
            outs = (None,) * len(inputs_list)
        return self._submit_many(
            "apply", rows, rows.tobytes(), inputs_list, outs)

    @staticmethod
    def _validate(data, s: int):
        """-> (data, width).  C-contiguous 2-D uint8 arrays pass through
        untouched (the fast path); anything else becomes a list of
        equal-length 1-D uint8 rows — a strided 2-D array (a column
        slice of a wider buffer) as views of its rows, uncopied."""
        if isinstance(data, np.ndarray) and data.ndim == 2:
            if data.shape[0] != s:
                raise ValueError(f"want {s} input rows, got {data.shape[0]}")
            if data.dtype != np.uint8:
                raise ValueError("inputs must be uint8")
            if data.flags["C_CONTIGUOUS"]:
                return data, data.shape[1]
        # ascontiguousarray, not asarray: the host fast path hands raw
        # row pointers to the native kernel, which reads stride-1 — a
        # strided view here would silently decode garbage
        data = [np.ascontiguousarray(r_, dtype=np.uint8) for r_ in data]
        if len(data) != s:
            raise ValueError(f"want {s} input rows, got {len(data)}")
        width = len(data[0])
        for r_ in data:
            if r_.ndim != 1 or len(r_) != width:
                raise ValueError("input rows must be equal-length 1-D")
        return data, width

    def _submit_many(self, kind, rows, plan, datas, outs,
                     stream=None, cls="pipeline") -> list[CodecFuture]:
        r, s = rows.shape
        key = (cls, r, s)
        jobs: list[_Job] = []
        futs: list[CodecFuture] = []
        for data, out in zip(datas, outs):
            data, width = self._validate(data, s)
            if out is not None:
                out = list(out) if not isinstance(out, np.ndarray) else out
                if len(out) != r:
                    raise ValueError(f"want {r} output rows, got {len(out)}")
                for o in out:
                    if len(o) != width:
                        raise ValueError("output rows must match input width")
            job = _Job(kind, key, plan, rows, data, width, out, stream, cls)
            futs.append(CodecFuture(job))
            if width == 0:  # nothing to compute: deliver inline
                job.result = (out if out is not None else
                              np.empty((r, 0), np.uint8))
                job.event.set()
            else:
                jobs.append(job)
        if jobs and stream is not None and self.mode == "device":
            with self._streams_lock:
                beside = len(self._streams) if stream in self._streams else 0
            if beside > 1:  # before the jobs are queued: see warm_pipeline
                self.warm_pipeline(r, s, {j.width for j in jobs}, beside)
        if jobs:
            with self._cond:
                if not self._open:
                    raise RuntimeError("codec service is closed")
                while len(self._q) >= self.max_queue:
                    self._cond.wait(0.1)
                    if not self._open:
                        raise RuntimeError("codec service is closed")
                self._q.extend(jobs)
                self._depth_child.set(len(self._q))
                if self._thread is None:
                    self._thread = threading.Thread(
                        target=self._run, name="ec-codec-service",
                        daemon=True)
                    self._thread.start()
                self._cond.notify_all()
        return futs

    # -- sync conveniences ------------------------------------------------

    def parity_into(self, inputs, outs) -> None:
        self.submit_parity(inputs, out=outs).result()

    def apply_rows(self, rows, inputs):
        return self.submit_apply(rows, inputs).result()

    # -- lifecycle --------------------------------------------------------

    def close(self, timeout: "float | None" = 30.0) -> None:
        """Stop accepting jobs, drain everything in flight, stop the
        scheduler.  Every already-submitted job still gets its result."""
        with self._cond:
            self._open = False
            self._cond.notify_all()
            t = self._thread
        if t is not None:
            t.join(timeout)

    @property
    def closed(self) -> bool:
        return not self._open

    # -- scheduler --------------------------------------------------------

    def _device_max_volumes(self, job_bytes: int) -> int:
        """How many jobs of `job_bytes` padded input each one device batch
        may hold: two batches resident and one program's temporaries
        within _HBM_SHARE of every device's memory (a job's columns are
        spread over all of them).  At least the head job, always."""
        if self._device_bytes is None:
            mesh = self._device_mesh()
            stats = mesh.devices.flat[0].memory_stats() or {}
            self._device_bytes = int(_HBM_SHARE * mesh.size * stats.get(
                "bytes_limit", _HBM_BYTES_UNREPORTED))
        room = int((self._device_bytes / job_bytes - _HBM_TEMP_PER_JOB_BYTE)
                   / (2 * self._resident_per_job_byte()))
        return max(1, min(room, self.max_batch))

    def _resident_per_job_byte(self) -> float:
        """_HBM_RESIDENT_PER_JOB_BYTE, and on a mesh what the gathered
        result adds over all its devices: each of the others holds the
        whole (R, w) of a job too, once as the program's output and once
        as the gather's own copy of it (a temporary that grows with V,
        counted for both batches in flight)."""
        others = self._device_mesh().size - 1
        return _HBM_RESIDENT_PER_JOB_BYTE + (
            2 * others * self.parity_shards / self.data_shards)

    def _collect_locked(self) -> "tuple[list[_Job], str]":
        """Pop the head job plus every queued job sharing its key — its
        class and shape — up to the job/byte caps; where the batch is one
        matrix's columns (a read block, a host batch) its matrix too.
        Head-of-queue start = oldest-first, so no job class can starve
        another."""
        head = self._q.popleft()
        batch = [head]
        s = head.rows.shape[1]
        nbytes = head.width * s
        # a device batch of pipeline jobs is one program over V jobs'
        # arrays: it takes jobs of the head's width bucket only (nothing
        # pads beyond its own bucket), as many as the devices' memory
        # holds, and one job of a stream — two slices of one volume in a
        # batch only delay the first, and a lone pipeline's slices keep
        # going one by one.  One of read jobs is a single block of their
        # intervals side by side, up to the widest read bucket.
        device = self.mode == "device"
        columns = device and head.cls == "read"
        one_plan = columns or not device
        if device and not columns:
            n_dev = self._device_mesh().size
            bucket = self._pad_width(head.width, n_dev)
            room = self._device_max_volumes(s * bucket)
            streams = {head.stream}
        reason = "ready"
        if self.max_batch > 1:
            for job in self._q:
                if job.key != head.key or (one_plan
                                           and job.plan != head.plan):
                    continue
                if device and not columns and (
                        self._pad_width(job.width, n_dev) != bucket
                        or (job.stream is not None and job.stream in streams)):
                    continue
                if len(batch) >= self.max_batch:
                    reason = "full"
                    break
                if columns:
                    over = nbytes + job.width * s > _READ_BUCKETS[-1] * s
                elif device:
                    over = len(batch) >= room
                else:
                    over = nbytes + job.width * s > self.max_batch_bytes
                if over:
                    reason = "bytes"
                    break
                batch.append(job)
                nbytes += job.width * s
                if device and not columns:
                    streams.add(job.stream)
            if device and not columns:
                # a power of two of volumes: every (V, width) is a compiled
                # program, and there must be few enough to warm them all
                # (warm_pipeline); the rest keep their places in the queue
                for job in batch[1 << (len(batch).bit_length() - 1):]:
                    batch.remove(job)
                    nbytes -= job.width * s
            if len(batch) > 1:
                taken = {id(j) for j in batch}
                self._q = deque(j for j in self._q if id(j) not in taken)
        self._depth_child.set(len(self._q))
        _BATCH_JOBS[head.cls].observe(len(batch))
        _BATCH_PLANS[head.cls].observe(len({j.plan for j in batch}))
        _BATCH_BYTES[head.cls].observe(nbytes)
        return batch, reason

    def _run(self) -> None:
        # device mode: (jobs, what _dispatch_device returned, tags)
        inflight: deque = deque()
        try:
            if self.mode == "device":
                # the backend starts here if nothing started it before, not
                # under the queue's lock
                self._device_mesh()
            while True:
                with self._cond:
                    while not self._q and self._open and not inflight:
                        self._cond.wait(0.2)
                    batch = reason = None
                    if self._q:
                        batch, reason = self._collect_locked()
                        if not self._open and not self._q:
                            reason = "drain"
                    elif not inflight and not self._open:
                        break
                    self._cond.notify_all()  # wake blocked submitters
                if batch is None:
                    if inflight:
                        self._complete_device(*inflight.popleft())
                        self._inflight_child.set(len(inflight))
                    continue
                self._flush_children[reason].inc()
                popped = time.perf_counter()
                for job in batch:
                    _STAGE[job.cls]["queue_wait"].observe(
                        popped - job.t_submit)
                # what every stage span of this batch carries, so one
                # batch can be followed through a trace by hand
                self._batch_seq += 1
                tags = {"batch": self._batch_seq, "jobs": len(batch),
                        "class": batch[0].cls,
                        "bytes": sum(j.width for j in batch)
                        * batch[0].rows.shape[1]}
                try:
                    if self.mode == "device":
                        dev = self._dispatch_device(batch, tags)
                        inflight.append((batch, dev, tags))
                        self._inflight_child.set(len(inflight))
                        if len(inflight) >= 2:
                            self._complete_device(*inflight.popleft())
                            self._inflight_child.set(len(inflight))
                    else:
                        self._compute_host(batch, tags)
                except Exception as e:  # noqa: BLE001 — the jobs carry it
                    # the collected batch is in neither queue nor inflight:
                    # fail it here or its waiters hang forever.  As with a
                    # result that fails to come back (_complete_device), it
                    # is this batch's failure, and the next one still runs
                    for job in batch:
                        self._fail(job, e)
            while inflight:
                self._complete_device(*inflight.popleft())
                self._inflight_child.set(len(inflight))
        except Exception as e:  # scheduler death must not strand waiters
            self._thread_err = e
            for jobs, _dev, _tags in inflight:
                for job in jobs:
                    self._fail(job, e)
            with self._cond:
                pending = list(self._q)
                self._q.clear()
                self._open = False
                self._cond.notify_all()
            for job in pending:
                self._fail(job, e)

    # -- delivery ---------------------------------------------------------

    def _deliver(self, job: _Job, result, direct: bool = False) -> None:
        """``result`` is (R, W) array-like; ``direct`` means the compute
        already wrote the caller's ``out`` buffers."""
        if job.out is not None and not direct:
            for dst, src in zip(job.out, result):
                np.copyto(np.asarray(dst), src, casting="no")
            job.result = job.out
        else:
            job.result = result
        job.t_set = time.perf_counter()
        job.event.set()
        self._job_ok[job.kind].inc()

    def _fail(self, job: _Job, err: Exception) -> None:
        if job.event.is_set():
            return
        job.error = err
        job.t_set = time.perf_counter()
        job.event.set()
        self._job_err[job.kind].inc()

    # -- host backend -----------------------------------------------------

    @staticmethod
    def _rows_of(data, s: int) -> list:
        return [data[i] for i in range(s)] if isinstance(
            data, np.ndarray) else data

    def _compute_host(self, batch: list[_Job], tags: dict) -> None:
        from ..native import lib as native

        rows = batch[0].rows
        r, s = rows.shape
        stage = _STAGE[batch[0].cls]
        use_native = native.available()
        mbytes = rows.tobytes()
        try:
            small = (len(batch) > 1
                     and all(j.width <= self.coalesce_bytes for j in batch))
            if small and use_native:
                # column-concatenate into the reused input slab -> ONE
                # kernel call for the whole batch; per-job results are
                # views of one output slab
                with trace.stage("ec.svc.build", stage["build"], **tags):
                    total = sum(j.width for j in batch)
                    slab = self._slab_in
                    if (slab is None or slab.shape[0] != s
                            or slab.shape[1] < total):
                        slab = np.empty(
                            (s, max(total, 1 << 20)), dtype=np.uint8)
                        self._slab_in = slab
                    at = 0
                    for j in batch:
                        w = j.width
                        if isinstance(j.data, np.ndarray):
                            slab[:, at:at + w] = j.data
                        else:
                            for ri in range(s):
                                slab[ri, at:at + w] = j.data[ri]
                        at += w
                with trace.stage("ec.svc.compute", stage["compute"], **tags):
                    out_slab = np.empty((r, total), dtype=np.uint8)
                    # row pointers: slab rows are strided by capacity, so
                    # pass each row's view; the kernel reads `total` bytes
                    native.gf_apply_fast(
                        mbytes, r, s,
                        [slab[i] for i in range(s)],
                        [out_slab[i] for i in range(r)], total)
                at = 0
                for j in batch:
                    self._deliver(j, out_slab[:, at:at + j.width])
                    at += j.width
                return
            with trace.stage("ec.svc.compute", stage["compute"], **tags):
                for j in batch:
                    w = j.width
                    rows_in = self._rows_of(j.data, s)
                    direct = False
                    if not use_native:
                        out_arr = self._rs._apply(j.rows, [
                            np.ascontiguousarray(x) for x in rows_in])
                    else:
                        if (j.out is not None
                                and all(isinstance(o, np.ndarray)
                                        and o.dtype == np.uint8
                                        and o.flags["C_CONTIGUOUS"]
                                        for o in j.out)):
                            out_rows = list(j.out)
                            direct = True
                        else:
                            out_arr = np.empty((r, w), dtype=np.uint8)
                            out_rows = [out_arr[i] for i in range(r)]
                        native.gf_apply_fast(
                            mbytes, r, s, rows_in, out_rows, w)
                        if direct:
                            out_arr = out_rows
                    self._deliver(j, out_arr, direct=direct)
        except Exception as e:
            for j in batch:
                self._fail(j, e)

    # -- device backend ---------------------------------------------------

    def _device_mesh(self):
        if self._mesh is None:
            from ..parallel.mesh import make_mesh

            # every device on the column axis: a lone slice stays a whole
            # block on any number of chips, and no batch needs a padding
            # volume (measured against dp = 2 and one service per chip on
            # four chips: PERF.md §6, PR 30)
            self._mesh = make_mesh(dp=1)
        return self._mesh

    def mesh_shape(self) -> str:
        """"<dp>x<sp>" of the mesh device batches run over (`/status`,
        the spans' `mesh`)."""
        mesh = self._device_mesh()
        return f"{mesh.shape['dp']}x{mesh.shape['sp']}"

    @staticmethod
    def _pad_width(width: int, sp: int) -> int:
        """Bucket widths to a power of two of whole lane tiles on each of
        the sp devices, so the jitted sharded program compiles once per
        bucket, not once per slice, and every bucket is one the program
        takes (``parallel.mesh.jobs_apply_sharded``)."""
        from ..parallel.mesh import TILE_BYTES

        w = TILE_BYTES * sp
        while w < width:
            w <<= 1
        return w

    def warm_pipeline(self, r: int, s: int, widths, streams: int) -> None:
        """Compile every device program that batches of up to ``streams``
        pipeline jobs of R x S matrices at these widths can take — each
        power of two of volumes the devices hold, at each width bucket,
        whatever the matrices.  The submitter of a job of an open stream
        runs it BEFORE the job is queued, with the number of streams
        open: of the jobs a batch takes, one of a stream, the one
        submitted last saw at least as many streams open as the batch has
        jobs, so no batch of open streams' jobs forms before its program
        is compiled.  A caller that will run ``streams`` pipelines side by
        side (a mass rebuild's workers) runs it before the first, so that
        the shapes, and not the order of the losses, decide what is
        compiled.  A shape already compiled costs a dict lookup; a
        host-mode service has none."""
        if self.mode != "device":
            return
        from ..parallel.mesh import compile_jobs_apply

        mesh = self._device_mesh()
        for w_pad in sorted({self._pad_width(w, mesh.size) for w in widths}):
            room = min(self._device_max_volumes(s * w_pad), streams)
            if self._warmed.get((r, s, w_pad), 0) * 2 > room:
                continue
            with self._warm_lock:
                v = self._warmed.get((r, s, w_pad), 0) * 2 or 1
                while v <= room:
                    compile_jobs_apply(mesh, r, v, (s, w_pad))
                    self._warmed[r, s, w_pad] = v
                    v *= 2

    def warm_reads(self, r: int, s: int = DATA_SHARDS) -> None:
        """Compile the device programs a batch of read jobs of R x S
        matrices can take: one per read bucket, whatever the plan.
        Whoever learns that reads of that shape are coming (a volume that
        has just lost a shard) calls it before they come; a program
        already compiled costs a dict lookup, and a host-mode service has
        none."""
        if self.mode != "device":
            return
        from ..parallel.mesh import compile_jobs_apply

        mesh = self._device_mesh()
        with self._warm_lock:
            for w_pad in _READ_BUCKETS:
                if not self._warmed.get((r, s, w_pad)):
                    compile_jobs_apply(mesh, r, 1, (s, w_pad))
                    self._warmed[r, s, w_pad] = 1

    def _staging_block(self, s: int, w_pad: int) -> np.ndarray:
        free = self._staging.get(w_pad)
        return free.pop() if free else np.empty((s, w_pad), dtype=np.uint8)

    def _dispatch_device(self, batch: list[_Job], tags: dict):
        """-> (device array, staging buffers used, where each job's
        result lies in it: (block, first column), perf_counter at the end
        of its `enqueue`, where its `parked` begins)."""
        from ..parallel.mesh import jobs_apply_sharded

        mesh = self._device_mesh()
        head = batch[0]
        s = head.rows.shape[1]
        stage = _STAGE[head.cls]
        columns = head.cls == "read"
        blocks, staged, places = [], [], []
        if columns:
            # one block: the jobs' intervals side by side
            total = sum(j.width for j in batch)
            w_pad = next((w for w in _READ_BUCKETS if total <= w), None) \
                or self._pad_width(total, mesh.size)
            path = "staged"
        else:
            w_pad = self._pad_width(head.width, mesh.size)  # the bucket
            # a job that fills its bucket IS its block (an ndarray job is
            # C-contiguous (S, W) uint8, by _validate) and goes in as it
            # is; any other is copied into a staging buffer of the
            # bucket's width
            whole = [isinstance(j.data, np.ndarray) and j.width == w_pad
                     for j in batch]
            path = ("direct" if all(whole) else
                    "staged" if not any(whole) else "mixed")
        n_blocks = 1 if columns else len(batch)
        matrices = [head.rows] if columns else [j.rows for j in batch]
        tags.update(volumes=len(batch), v_pad=n_blocks,
                    mesh=self.mesh_shape())
        with trace.stage("ec.svc.build", stage["build"], path=path, **tags):
            if columns:
                block = self._staging_block(s, w_pad)
                at = 0
                for j in batch:
                    for ri, row in enumerate(self._rows_of(j.data, s)):
                        block[ri, at:at + j.width] = row
                    places.append((0, at))
                    at += j.width
                # the columns past `at` hold what the buffer held before:
                # they are computed and never read
                _INPUT_BYTES["staged"].inc(total * s)
                blocks.append(block)
                staged.append(block)
            else:
                for vi, (j, as_it_is) in enumerate(zip(batch, whole)):
                    places.append((vi, 0))
                    _INPUT_BYTES["direct" if as_it_is else "staged"].inc(
                        j.width * s)
                    if as_it_is:
                        blocks.append(j.data)
                        continue
                    block = self._staging_block(s, w_pad)
                    for ri, row in enumerate(self._rows_of(j.data, s)):
                        block[ri, :j.width] = row
                    block[:, j.width:] = 0
                    blocks.append(block)
                    staged.append(block)
            _BLOCK_BYTES[head.cls].inc(n_blocks * s * w_pad)
        # H2D dispatch of every block (async), a compile on a miss
        with trace.stage("ec.svc.enqueue", stage["enqueue"], layout=_LAYOUT,
                         plans=len({j.plan for j in batch}), **tags) as st:
            dev = jobs_apply_sharded(mesh, matrices, blocks)
        t_enqueued = time.perf_counter()
        stage["compute"].observe(st.seconds)
        # the result sets out for the host as soon as the device has it, on
        # the runtime's threads: _complete_device picks up a finished copy
        dev.copy_to_host_async()
        return dev, staged, places, t_enqueued

    def _complete_device(self, batch: list[_Job], sent, tags: dict) -> None:
        dev, staged, places, t_enqueued = sent
        stage = _STAGE[batch[0].cls]
        stage["parked"].observe(time.perf_counter() - t_enqueued)
        try:
            # the copy out began at dispatch: was the program done by now?
            state = "ready" if dev.is_ready() else "waited"
            _READBACKS[batch[0].cls][state].inc()
            # np.asarray alone would wait just the same: the split only
            # says how much of it is the device and how much the copy out
            with trace.stage("ec.svc.device_wait", stage["device_wait"],
                             **tags) as wait:
                dev.block_until_ready()
            with trace.stage("ec.svc.d2h", stage["d2h"], layout=_LAYOUT,
                             state=state, **tags) as copy:
                # the pick-up of (V, R, w_pad) bytes the runtime has copied,
                # or the wait for the rest of that copy
                out = np.asarray(dev)
            stage["readback"].observe(wait.seconds + copy.seconds)
            # the result is here, so the device has read the staged jobs
            for block in staged:
                free = self._staging.setdefault(block.shape[1], [])
                if len(free) < _STAGING_KEPT:
                    free.append(block)
            with trace.stage("ec.svc.deliver", stage["deliver"], **tags):
                for j, (vi, at) in zip(batch, places):
                    self._deliver(j, out[vi, :, at:at + j.width])
        except Exception as e:
            for j in batch:
                self._fail(j, e)


# ---------------------------------------------------------------------------
# Process-wide singletons: every caller of the same backend shares one
# queue, which is the whole point — concurrency ACROSS volumes is what
# the scheduler turns into batch occupancy.
# ---------------------------------------------------------------------------

_SERVICES: dict[str, CodecService] = {}
_SERVICES_LOCK = threading.Lock()


def enabled() -> bool:
    return os.environ.get("SEAWEEDFS_TPU_EC_SERVICE", "1").lower() not in (
        "0", "false", "off", "no")


def get_service(codec_name: str = "cpu") -> "CodecService | None":
    """The shared service for a codec backend, or None when disabled."""
    if not enabled():
        return None
    key = "device" if codec_name in _DEVICE_CODECS else "host"
    with _SERVICES_LOCK:
        svc = _SERVICES.get(key)
        if svc is None or svc.closed:
            svc = CodecService(mode="auto", codec_name=(
                codec_name if key == "device" else "cpu"))
            _SERVICES[key] = svc
        return svc


def service_for_codec(codec_name: str) -> "CodecService | None":
    """Default routing for the bulk encode/rebuild pipelines and for a
    degraded read's interval decode (storage/ec/volume.py): a device
    codec goes through the (device-mode) service when THIS process's jax
    holds an accelerator; on a CPU backend the per-volume device path
    keeps its direct dispatch, and host codecs their mmap/inline-SIMD
    paths.  The backend is asked in process (ops.device.held_device): one
    that cannot initialise raises here and the rpc fails.  Callers that
    KNOW they are concurrent (bench --service, batch flows) pass an
    explicit service instead."""
    codec_name = resolve_codec_name(codec_name)
    if not enabled() or codec_name not in _DEVICE_CODECS:
        return None
    from .device import held_device

    if held_device()["platform"] == "cpu":
        return None
    return get_service(codec_name)


def shutdown_all(timeout: "float | None" = 30.0) -> None:
    """Drain and close every shared service (server shutdown, tests).
    Safe to call repeatedly; a later get_service starts a fresh one."""
    with _SERVICES_LOCK:
        svcs = list(_SERVICES.values())
        _SERVICES.clear()
    for svc in svcs:
        svc.close(timeout)
