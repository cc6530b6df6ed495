#!/usr/bin/env python
"""Headline benchmark: EC encode GB/s per chip (RS 10+4, GF(2^8) on TPU).

Prints ONE JSON line:
  {"metric": "ec_encode_GBps", "value": <TPU pallas-kernel encode rate>,
   "unit": "GB/s", "vs_baseline": <ratio vs the CPU SIMD codec on this host>,
   ...details}

Methodology notes:
  * repeated dispatch of the same computation invites CSE, so the timed
    kernel workload is ONE device-side pallas_call with a (K, G) grid whose
    input index_map shifts by the sweep index k — K full encode sweeps over
    distinct HBM windows in a single dispatch, ended by a host readback.
  * one process per chip: the parent never touches a jax backend; every
    device stage is a child that runs alone and exits.  A stage that finds
    no accelerator, or raises, FAILS the run — the host codec is never
    written under the device metric's name.
  * Rate convention matches the reference workload accounting (BASELINE.md):
    encode throughput = volume bytes consumed per second.
  * The CPU baseline is our C++ SSSE3 nibble-table codec — the same
    algorithm class as the reference's SIMD assembly — on this host.
"""

from __future__ import annotations

import functools
import json
import time

import numpy as np


def _tpu_pallas_rate(tile: int = 256) -> dict:
    """Escalating-sweep kernel benchmark with a salvage contract.

    The old single-shot version device_put a ~660MB buffer and printed
    NOTHING until the final readback, so a killed stage recorded no number
    at all.  Contract now:
      * stage 0 is a small probe (4MB/shard, ~46MB upload) that emits a
        measured partial JSON rate as soon as it completes;
      * each later stage (16 -> 64 -> 256 MB/shard) re-emits the best rate
        so far after the upload, after compile, and after EVERY timing rep,
        so a killed process always leaves the latest measurement on stdout;
      * a stage only starts if the previous stage's observed device_put
        rate projects it to fit in the remaining time budget;
      * SEAWEEDFS_TPU_BENCH_KERNEL_MB caps the largest stage — the retry
        loop halves it on timeout instead of re-running the same shape.
    """
    import os

    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    from seaweedfs_tpu.ops import gf256
    from seaweedfs_tpu.ops.rs_pallas import LANES, _kernel_body

    rows = tuple(tuple(int(c) for c in r) for r in gf256.rs_parity_matrix(10, 4))
    kernel = functools.partial(_kernel_body, rows)
    max_mb = int(os.environ.get("SEAWEEDFS_TPU_BENCH_KERNEL_MB", "256"))
    budget = float(os.environ.get("SEAWEEDFS_TPU_BENCH_KERNEL_BUDGET_S", "250"))
    from seaweedfs_tpu.ops.device import enable_compile_cache, held_device

    enable_compile_cache()
    device = held_device()
    if device["platform"] == "cpu":
        raise RuntimeError(
            "kernel stage found only a CPU backend; the Mosaic kernel "
            "needs a TPU and the host is never timed in its place")
    t_start = time.perf_counter()
    result: dict = {"device": device}

    def emit(**kv) -> None:
        result.update(kv)
        print(json.dumps({"partial": True, **result}), flush=True)

    # (mb_per_shard, sweeps): upload is 10*(g+k) blocks, compute is k full
    # sweeps over g blocks — later stages amortise upload over more compute
    stages = [(4, 8), (16, 32), (64, 16), (256, 8)]
    put_rate = None  # bytes/s observed for device_put, drives stage gating
    compile_dt = 20.0  # refined from each stage's observed compile time
    for mb, k in stages:
        if mb > max_mb and mb != stages[0][0]:
            continue
        g = (mb << 20) // (tile * LANES * 4)
        upload_bytes = 10 * (g + k) * tile * LANES * 4
        remaining = budget - (time.perf_counter() - t_start)
        # each stage is a fresh XLA/Mosaic program (the grid changes), so
        # the projection budgets a compile alongside the upload
        if put_rate and (upload_bytes / put_rate * 1.3
                         + compile_dt * 1.5 + 10 > remaining):
            emit(skipped_stage_mb=mb, skip_reason="projected over budget")
            break
        rng = np.random.default_rng(0)
        host = rng.integers(
            0, 2**32, (10, (g + k) * tile * LANES), dtype=np.uint32
        ).reshape(10, (g + k) * tile, LANES)
        t0 = time.perf_counter()
        buf = jax.device_put(host)
        np.asarray(buf[0, 0, :2])  # fence via readback
        put_dt = time.perf_counter() - t0
        put_rate = upload_bytes / max(put_dt, 1e-6)
        emit(stage_mb=mb, put_seconds=round(put_dt, 2),
             put_GBps=round(put_rate / 1e9, 3))
        fn = jax.jit(
            pl.pallas_call(
                kernel,
                out_shape=jax.ShapeDtypeStruct((4, g * tile, LANES), jnp.uint32),
                grid=(k, g),
                in_specs=[
                    pl.BlockSpec(
                        (10, tile, LANES), lambda kk, gg: (0, gg + kk, 0),
                        memory_space=pltpu.VMEM,
                    )
                ],
                out_specs=pl.BlockSpec(
                    (4, tile, LANES), lambda kk, gg: (0, gg, 0),
                    memory_space=pltpu.VMEM,
                ),
            )
        )
        t0 = time.perf_counter()
        out = fn(buf)
        np.asarray(out[0, 0, :2])  # compile + warm
        compile_dt = time.perf_counter() - t0
        emit(compile_seconds=round(compile_dt, 2))
        bytes_encoded = 10 * g * tile * LANES * 4 * k
        for rep in range(3):
            t0 = time.perf_counter()
            out = fn(buf)
            np.asarray(out[0, 0, :2])  # fence via readback
            dt = time.perf_counter() - t0
            rate = bytes_encoded / dt / 1e9
            if rate > result.get("rate", 0.0):
                result.update(rate=rate, sweeps=k, bytes=bytes_encoded,
                              seconds=dt, sweep_mb_per_shard=mb)
            emit(rep=rep)
        del buf, out
    if "rate" not in result:
        return {"error": "no kernel stage completed"}
    return result


def _e2e_rates(volume_mb: int | None = None, slice_mb: int = 8,
               codec_name: str = "tpu") -> dict:
    """End-to-end file pipeline (BASELINE configs 2+3).

    Writes a synthetic .dat, times the full disk->HBM->shards encode
    (storage.ec.encoder pipelined path), then deletes the 4 FIRST data
    shards (worst case: full decode-matrix inversion) and times the rebuild.
    Rates follow the reference accounting: volume/input bytes per second.

    Robustness contract (this stage produced nothing for 3 rounds): it
    EMITS PARTIAL JSON LINES as it goes — after warmup, every ~2s of
    encode/rebuild progress, and after the encode stage — so if the parent
    has to kill us mid-transfer, the captured stdout still carries a
    measured rate for every stage that ran.  The
    volume is deliberately small (default 256MB, SEAWEEDFS_TPU_BENCH_E2E_MB
    to override) so a healthy run finishes in well under a minute and the
    parent timeout is never the thing that ends it.
    """
    import os
    import shutil
    import sys
    import tempfile

    from seaweedfs_tpu.storage.ec.constants import DATA_SHARDS, to_ext
    from seaweedfs_tpu.storage.ec.encoder import (
        generate_ec_files,
        rebuild_ec_files,
    )

    if volume_mb is None:
        # host codecs sustain ~0.35 GB/s through this pipeline, so a 1GB
        # volume keeps the stage under ~15s while exercising 100 small-row
        # stripes; the device codec stays at 256MB until the benchmark
        # PR sizes it from a chip run
        default = "256" if codec_name != "cpu" else "1024"
        volume_mb = int(os.environ.get("SEAWEEDFS_TPU_BENCH_E2E_MB", default))
    slice_bytes = slice_mb << 20
    dat_size = max(64, volume_mb) << 20
    result = {"impl": codec_name, "e2e_bytes": dat_size}

    def emit(**kv) -> None:
        result.update(kv)
        print(json.dumps({"partial": True, **result}), flush=True)

    if codec_name != "cpu":
        # warm the device with a TINY buffer first: the first partial
        # prints before any device call, and the warm buffer is ~1.3MB
        # (the real slice shape compiles inside the timed region instead;
        # its one-time cost shows up in the first progress line)
        from seaweedfs_tpu.ops.codec import get_codec
        from seaweedfs_tpu.ops.device import held_device

        codec = get_codec(codec_name)  # also enables the compile cache
        result["device"] = held_device()
        if result["device"]["platform"] == "cpu":
            raise RuntimeError(
                f"device stage for codec {codec_name!r} found only a CPU "
                "backend; refusing to time it under a device metric")
        emit(warm_stage="starting")  # before the first device round trip
        t0 = time.perf_counter()
        warm = np.zeros((10, 256 * 512), dtype=np.uint8)  # 1.3MB total
        np.asarray(codec.encode_device(warm))
        emit(warm_seconds=round(time.perf_counter() - t0, 2))

    tmp = tempfile.mkdtemp(prefix="swfs-bench-")
    base = os.path.join(tmp, "1")
    try:
        # content doesn't affect GF timing: tile one random block
        rng = np.random.default_rng(7)
        block = rng.integers(0, 256, 32 << 20, dtype=np.uint8).tobytes()
        # the timed write+sync of the .dat doubles as the raw-disk write
        # baseline: encode writes 1.4x the volume, so an e2e rate near
        # disk_write_GBps/1.4 means the pipeline runs at the disk's write
        # bandwidth and the codec is fully hidden behind I/O.  Syncing
        # here also keeps the timed encode from competing with its own
        # input's writeback (the read side stays page-cache warm — the
        # "warm volume" of BASELINE config 2).
        t0 = time.perf_counter()
        with open(base + ".dat", "wb") as f:
            left = dat_size
            while left > 0:
                n = min(len(block), left)
                f.write(block[:n])
                left -= n
            f.flush()
            os.fsync(f.fileno())  # time THIS file's writeback only
        result["disk_write_GBps"] = round(
            dat_size / (time.perf_counter() - t0) / 1e9, 3)
        os.sync()  # untimed: clear any other dirty pages before the encode

        last_emit = time.perf_counter()

        def progress(tag: str, start: float, total: int, scale: int = 1):
            # `scale` keeps partial rates on the same accounting as the
            # completed-stage rate (rebuild counts DATA_SHARDS x shard
            # bytes, but the callback reports single-shard column offsets).
            # The emitted {tag}_rate never regresses: a throttled trial's
            # in-flight rate must not overwrite an earlier COMPLETED
            # trial's best-of in the salvage stream (the last partial line
            # is what a timeout kill records).
            def cb(done: int) -> None:
                nonlocal last_emit
                now = time.perf_counter()
                rate = done * scale / (now - start) / 1e9
                print(f"{tag}: {done >> 20}/{total >> 20} MB "
                      f"{rate:.3f} GB/s", file=sys.stderr, flush=True)
                if now - last_emit > 2.0:
                    last_emit = now
                    emit(**{f"{tag}_rate": max(
                            rate, result.get(f"{tag}_rate", 0.0)),
                            f"{tag}_partial_bytes": done})
            return cb

        # three timed trials for host codecs, best-of (mirrors the kernel
        # stage's min-of-3).  Why best-of and not mean: r05 profiling
        # showed the e2e wall time is 96% kernel buffered-write path
        # whose throughput swings 0.2-4.5 GB/s with dirty-page/writeback
        # state on this 1-core VM (codec compute is 0.2s/GB; the
        # user-space gather/syscall costs were eliminated by the mmap+
        # writev encode path) — best-of measures the pipeline, not the
        # writeback lottery.  Trial 1 additionally pays first-allocation
        # of the 1.4x shard extents.  Device codecs run once.
        trials = 1 if codec_name != "cpu" else 3
        encode_dt = None
        for trial in range(trials):
            t0 = time.perf_counter()
            generate_ec_files(base, codec_name=codec_name,
                              slice_size=slice_bytes,
                              progress=progress("e2e", time.perf_counter(),
                                                dat_size))
            dt = time.perf_counter() - t0
            encode_dt = dt if encode_dt is None else min(encode_dt, dt)
            emit(e2e_rate=dat_size / encode_dt / 1e9,
                 e2e_seconds=round(encode_dt, 2), e2e_trials=trial + 1)
        if codec_name == "cpu":
            # durability-matched variant: shard files fsync'd inside the
            # timed region, so this rate shares semantics with
            # disk_write_GBps (which times an fsync'd raw write) — the
            # warm-cache e2e_rate above deliberately excludes writeback,
            # mirroring the reference encode which never syncs shards
            # (ec_encoder.go:194-231)
            t0 = time.perf_counter()
            generate_ec_files(base, codec_name=codec_name,
                              slice_size=slice_bytes, sync=True)
            emit(e2e_fsync_rate=round(
                dat_size / (time.perf_counter() - t0) / 1e9, 3))

        shard_size = os.path.getsize(base + to_ext(0))
        for i in range(4):  # lose 4 data shards — worst case
            os.remove(base + to_ext(i))
        t0 = time.perf_counter()
        rebuilt = rebuild_ec_files(
            base, codec_name=codec_name, slice_size=slice_bytes,
            progress=progress("rebuild", time.perf_counter(), shard_size,
                              scale=DATA_SHARDS))
        rebuild_dt = time.perf_counter() - t0
        assert rebuilt == [0, 1, 2, 3]
        result.update(
            rebuild_rate=shard_size * DATA_SHARDS / rebuild_dt / 1e9,
            rebuild_seconds=round(rebuild_dt, 2),
        )
        for k in list(result):
            if k.endswith("_partial_bytes"):
                del result[k]
        return result
    finally:
        shutil.rmtree(tmp, ignore_errors=True)


def _parse_lose_env(var: str, default: str) -> list[int]:
    """Loss-pattern knob: a csv of shard ids to delete (e.g. "0,1,2,3"
    for the worst-case first-4-data pattern, "10,11,12,13" for parity,
    "2,7,11,13" for mixed)."""
    import os

    raw = os.environ.get(var, default)
    ids = sorted({int(x) for x in raw.split(",") if x.strip() != ""})
    if any(i < 0 or i > 13 for i in ids) or len(ids) > 4:
        raise ValueError(f"{var}={raw!r}: want <=4 shard ids in 0..13")
    return ids


def _rebuild_only_rates(codec_name: str | None = None) -> dict:
    """BASELINE config 3 in isolation: encode a synthetic volume
    (untimed), delete the configured loss pattern
    (SEAWEEDFS_TPU_BENCH_LOSE, default the worst-case first 4 data
    shards), and time rebuild_ec_files alone — the repair-plane headline
    without the encode stage's accounting in the way.  Asserts the
    rebuilt shards byte-identical to the originals.  Volume size via
    SEAWEEDFS_TPU_BENCH_E2E_MB (default 1024), codec via
    SEAWEEDFS_TPU_BENCH_REBUILD_CODEC (default cpu)."""
    import hashlib
    import os
    import shutil
    import tempfile

    from seaweedfs_tpu.storage.ec.constants import DATA_SHARDS, to_ext
    from seaweedfs_tpu.storage.ec.encoder import (
        generate_ec_files,
        rebuild_ec_files,
    )

    if codec_name is None:
        codec_name = os.environ.get("SEAWEEDFS_TPU_BENCH_REBUILD_CODEC", "cpu")
    lose = _parse_lose_env("SEAWEEDFS_TPU_BENCH_LOSE", "0,1,2,3")
    volume_mb = int(os.environ.get("SEAWEEDFS_TPU_BENCH_E2E_MB", "1024"))
    dat_size = max(64, volume_mb) << 20
    slice_bytes = 8 << 20
    result = {"impl": codec_name, "rebuild_lost_shards": lose,
              "rebuild_bytes": dat_size}

    def emit(**kv) -> None:
        result.update(kv)
        print(json.dumps({"partial": True, **result}), flush=True)

    tmp = tempfile.mkdtemp(prefix="swfs-rebuild-")
    base = os.path.join(tmp, "1")
    try:
        rng = np.random.default_rng(7)
        block = rng.integers(0, 256, 32 << 20, dtype=np.uint8).tobytes()
        with open(base + ".dat", "wb") as f:
            left = dat_size
            while left > 0:
                n = min(len(block), left)
                f.write(block[:n])
                left -= n
        generate_ec_files(base, codec_name=codec_name,
                          slice_size=slice_bytes)
        os.sync()  # the timed rebuild must not compete with encode writeback
        digests = {}
        for sid in lose:
            h = hashlib.sha256()
            with open(base + to_ext(sid), "rb") as f:
                for chunk in iter(lambda: f.read(8 << 20), b""):
                    h.update(chunk)
            digests[sid] = h.hexdigest()
            os.remove(base + to_ext(sid))
        shard_size = os.path.getsize(
            base + to_ext(next(i for i in range(14) if i not in lose)))
        emit(encode_done=True)

        # best-of-2: same writeback-lottery reasoning as the e2e stage
        rebuild_dt = None
        for trial in range(2):
            if trial:
                for sid in lose:
                    os.remove(base + to_ext(sid))
            t0 = time.perf_counter()
            rebuilt = rebuild_ec_files(base, codec_name=codec_name,
                                       slice_size=slice_bytes)
            dt = time.perf_counter() - t0
            assert sorted(rebuilt) == lose
            rebuild_dt = dt if rebuild_dt is None else min(rebuild_dt, dt)
            emit(rebuild_rate=shard_size * DATA_SHARDS / rebuild_dt / 1e9,
                 rebuild_seconds=round(rebuild_dt, 2),
                 rebuild_trials=trial + 1)
        for sid in lose:
            h = hashlib.sha256()
            with open(base + to_ext(sid), "rb") as f:
                for chunk in iter(lambda: f.read(8 << 20), b""):
                    h.update(chunk)
            if h.hexdigest() != digests[sid]:
                return {"error": f"rebuilt shard {sid} not byte-identical"}
        result["rebuild_byte_identical"] = True

        # ISSUE 10: partial-sum vs full-fetch A/B on ONE lost shard with
        # all 10 sources remote — the wire-reduction headline, measured
        # by the locality-labeled rebuild-ingress counters
        ab = _rebuild_ab_rates(base, tmp, codec_name, slice_bytes,
                               lose[0], digests[lose[0]])
        result["rebuild_ab"] = ab
        emit()
        if not ab.get("byte_identical"):
            result["error"] = "partial-sum A/B not byte-identical"
        return result
    finally:
        shutil.rmtree(tmp, ignore_errors=True)


def _rebuild_ab_rates(src_base: str, tmp: str, codec_name: str,
                      slice_bytes: int, lost: int, want_digest: str) -> dict:
    """Rebuild one lost shard twice with ALL 10 sources remote — once
    streaming full shard intervals, once through the partial-sum
    protocol (10 sources on 10 fake nodes across 2 racks; one rack is
    the rebuilder's, so exactly one combined partial crosses each rack
    boundary and one arrives rack-locally).  Network-in per leg comes
    from the seaweedfs_ec_rebuild_bytes_total{source=rack|dc} deltas;
    byte-identity against the original shard digest gates the result."""
    import hashlib
    import os

    from seaweedfs_tpu.stats.metrics import REGISTRY
    from seaweedfs_tpu.storage.ec import partial as P
    from seaweedfs_tpu.storage.ec.constants import TOTAL_SHARDS, to_ext
    from seaweedfs_tpu.storage.ec.encoder import rebuild_ec_files

    shard_size = os.path.getsize(src_base + to_ext(lost))

    def counters() -> dict:
        return {k: v for k, v in REGISTRY.snapshot_samples(max_samples=1 << 20)
                if "ec_rebuild_bytes" in k or "ec_partial" in k}

    def delta(before: dict, after: dict, name: str) -> float:
        return sum(after.get(k, 0.0) - before.get(k, 0.0)
                   for k in after if k.startswith(name))

    nodes, holders = {}, {}
    for sid in range(TOTAL_SHARDS):
        if sid == lost:
            continue
        addr = f"bench-src-{sid}:0"
        nodes[addr] = (src_base, [sid])
        # rack0 == the rebuilder's rack, rack1 crosses the boundary
        holders[sid] = [(addr, f"rack{sid % 2}", "dc1")]

    def remote_fetch(sid, off, length):
        if sid == lost:
            return None
        with open(src_base + to_ext(sid), "rb") as f:
            f.seek(off)
            return f.read(length)

    remote_fetch.locality_of = (
        lambda sid: "rack" if sid % 2 == 0 else "dc")

    out: dict = {"lost_shard": lost, "shard_size": shard_size}
    legs = {
        "full": dict(remote_fetch=remote_fetch, shard_size=shard_size),
        "partial": dict(
            remote_fetch=remote_fetch,
            partial=P.PartialRepairClient(
                1, "", lambda: holders, P.local_source_network(nodes),
                my_rack="rack0", my_dc="dc1")),
    }
    for leg, kw in legs.items():
        rdir = os.path.join(tmp, f"ab-{leg}")
        os.makedirs(rdir, exist_ok=True)
        rbase = os.path.join(rdir, "1")
        before = counters()
        t0 = time.perf_counter()
        rebuilt = rebuild_ec_files(rbase, codec_name=codec_name,
                                   slice_size=slice_bytes, **kw)
        dt = time.perf_counter() - t0
        after = counters()
        if rebuilt != [lost]:
            return {"error": f"{leg} leg rebuilt {rebuilt}, want [{lost}]"}
        h = hashlib.sha256()
        with open(rbase + to_ext(lost), "rb") as f:
            for chunk in iter(lambda: f.read(8 << 20), b""):
                h.update(chunk)
        if h.hexdigest() != want_digest:
            return {"error": f"{leg} leg not byte-identical"}
        rack_in = delta(before, after,
                        'seaweedfs_ec_rebuild_bytes_total{source="rack"}')
        dc_in = delta(before, after,
                      'seaweedfs_ec_rebuild_bytes_total{source="dc"}')
        out[leg] = {
            "seconds": round(dt, 3),
            "bytes_in": int(rack_in + dc_in),
            "bytes_in_rack": int(rack_in),
            "bytes_in_dc": int(dc_in),
            "fallbacks": int(delta(
                before, after, "seaweedfs_ec_partial_fallback_total")),
        }
    full_in = out["full"]["bytes_in"]
    part_in = out["partial"]["bytes_in"]
    out["wire_reduction"] = round(full_in / part_in, 2) if part_in else 0.0
    out["bytes_in_per_rebuilt_shard"] = {
        "full": full_in, "partial": part_in}
    out["byte_identical"] = True
    return out


def _mass_repair_rates() -> dict:
    """ISSUE 11 A/B over a LIVE loopback cluster (real gRPC sockets,
    the shipped code path end to end): one dead node's worth of EC
    volumes (default 32, SEAWEEDFS_TPU_BENCH_MASS_VOLUMES) each missing
    one shard, rebuilt twice on the same planned targets —

      * per_volume: the PR 10 status quo — one VolumeEcShardsRebuild +
        Mount rpc pair per volume IN SEQUENCE, each rebuild doing its
        own holder lookup, liveness probes and per-rack partial rpcs;
      * batched: the mass-repair transport — one
        VolumeEcShardsBatchRebuild rpc per target node (fired
        concurrently), every volume sourcing remote columns through one
        cross-volume MassPartialSession with plan-supplied size hints.

    Reported per leg: wall seconds, gRPC rpcs served (request_total
    deltas over the EC repair surface), and rebuilder-boundary wire
    bytes (partial request + received-partial counters).  Byte-identity
    against the staged shard digests gates the result; interleaved
    best-of-2 per the noisy-host discipline.  Per-volume .dat MB via
    SEAWEEDFS_TPU_BENCH_MASS_MB (default 2); EC block sizes are scaled
    down (SMALL=64KB) so shards carry real data instead of 1MB padding.
    """
    import hashlib
    import os
    import shutil
    import tempfile
    from concurrent.futures import ThreadPoolExecutor

    from seaweedfs_tpu.master.server import MasterServer
    from seaweedfs_tpu.pb import rpc as rpclib
    from seaweedfs_tpu.pb import volume_server_pb2 as vs
    from seaweedfs_tpu.stats.metrics import REGISTRY
    from seaweedfs_tpu.storage.ec.constants import (
        DATA_SHARDS,
        TOTAL_SHARDS,
        to_ext,
    )
    from seaweedfs_tpu.storage.ec.encoder import generate_ec_files
    from seaweedfs_tpu.volume.server import VolumeServer

    n_vols = int(os.environ.get("SEAWEEDFS_TPU_BENCH_MASS_VOLUMES", "32"))
    vol_mb = float(os.environ.get("SEAWEEDFS_TPU_BENCH_MASS_MB", "2"))
    n_srv = 5
    large, small = 1 << 20, 64 << 10
    dat_size = max(small * DATA_SHARDS, int(vol_mb * (1 << 20)))
    result: dict = {"mass_volumes": n_vols, "volume_bytes": dat_size}

    def emit(**kv) -> None:
        result.update(kv)
        print(json.dumps({"partial": True, **result}), flush=True)

    def free_port() -> int:
        import socket

        with socket.socket() as sk:
            sk.bind(("127.0.0.1", 0))
            return sk.getsockname()[1]

    tmp = tempfile.mkdtemp(prefix="swfs-mass-")
    master = None
    servers: list = []
    try:
        master = MasterServer(ip="127.0.0.1", port=free_port(),
                              volume_size_limit_mb=64, pulse_seconds=1.0)
        # the A/B drives the repair transport by hand; the autonomous
        # orchestrator would race it and heal the staged volumes first
        master.mass_repair.enabled = False
        master.start()
        for i in range(n_srv):
            d = os.path.join(tmp, f"vol{i}")
            os.makedirs(d)
            srv = VolumeServer(
                directories=[d],
                master_addresses=[f"127.0.0.1:{master.grpc_port}"],
                ip="127.0.0.1", port=free_port(), pulse_seconds=1.0,
                rack=f"rack{i % 2}", data_center="dc1",
                max_volume_count=max(64, n_vols))
            srv.start()
            servers.append(srv)
        deadline = time.time() + 30
        while time.time() < deadline and len(master.topo.nodes) < n_srv:
            time.sleep(0.1)
        if len(master.topo.nodes) < n_srv:
            return {**result, "error": "cluster never formed"}

        # stage: every volume misses shard (vid % 14) cluster-wide (the
        # dead node is already gone); survivors spread over all servers
        rng = np.random.default_rng(23)
        block = rng.integers(0, 256, min(dat_size, 8 << 20),
                             dtype=np.uint8).tobytes()
        digests: dict = {}
        lost_of: dict = {}
        stage = os.path.join(tmp, "stage")
        for v in range(1, n_vols + 1):
            d = os.path.join(stage, str(v))
            os.makedirs(d)
            base = os.path.join(d, str(v))
            with open(base + ".dat", "wb") as f:
                left = dat_size
                while left > 0:
                    n = min(len(block), left)
                    f.write(block[:n])
                    left -= n
            generate_ec_files(base, codec_name="cpu",
                              large_block_size=large,
                              small_block_size=small,
                              slice_size=4 << 20)
            lost = v % TOTAL_SHARDS
            lost_of[v] = lost
            h = hashlib.sha256()
            with open(base + to_ext(lost), "rb") as f:
                for chunk in iter(lambda: f.read(8 << 20), b""):
                    h.update(chunk)
            digests[v] = h.hexdigest()
            assign: dict = {j: [] for j in range(n_srv)}
            for k, sid in enumerate(
                    s for s in range(TOTAL_SHARDS) if s != lost):
                assign[k % n_srv].append(sid)
            for j, sids in assign.items():
                tbase = servers[j].store.locations[0].base_name(v, "")
                # synthetic volume: no needle index exists (the bench
                # never reads needles) — mount only requires the file
                open(tbase + ".ecx", "ab").close()
                for sid in sids:
                    shutil.copy(base + to_ext(sid), tbase + to_ext(sid))
                servers[j].store.mount_ec_shards(v, "", sids)
        deadline = time.time() + 60
        while time.time() < deadline and any(
                len(master.topo.lookup_ec_shards(v)) < 13
                for v in range(1, n_vols + 1)):
            time.sleep(0.3)
        shard_size = os.path.getsize(
            os.path.join(stage, "1", "1" + to_ext(lost_of[1] or 1)))
        result["shard_bytes"] = shard_size
        emit(setup_done=True)

        # one plan, both legs: identical targets (the orchestrator's
        # exposure-ranked, cap-spread assignment)
        plans = master.mass_repair.plan()
        if len(plans) != n_vols:
            return {**result,
                    "error": f"planned {len(plans)} of {n_vols}"}
        by_node = {s.store.public_url: s for s in servers}

        def stub_of(node_id):
            host, port = node_id.rsplit(":", 1)
            return rpclib.volume_server_stub(
                f"{host}:{int(port) + 10000}", timeout=600)

        RPC_OPS = ("VolumeEcShardPartialApply", "VolumeEcShardRead",
                   "VolumeEcShardsRebuild", "VolumeEcShardsBatchRebuild",
                   "VolumeEcShardsMount", "LookupEcVolume")
        # background chatter present in both legs but not repair traffic
        BG_OPS = ("SendHeartbeat", "KeepConnected")

        def counters() -> dict:
            """Total wire = every serialized gRPC byte the cluster moved
            (seaweedfs_grpc_bytes_total, counted at the codec boundary),
            heartbeat/keepalive chatter excluded; rpcs = repair-surface
            request counts."""
            out: dict = {"wire": 0.0, "rpcs": 0.0}
            for k, val in REGISTRY.snapshot_samples(max_samples=1 << 20):
                if (k.startswith("seaweedfs_grpc_bytes_total")
                        and not any(f'op="{op}"' in k for op in BG_OPS)):
                    out["wire"] += val
                if k.startswith("seaweedfs_request_total") and any(
                        f'op="{op}"' in k for op in RPC_OPS):
                    out["rpcs"] += val
            return out

        def verify() -> bool:
            for p in plans:
                v = p["volume_id"]
                srv = by_node[p["node"]]
                path = srv.store._ec_base(v, "") + to_ext(lost_of[v])
                h = hashlib.sha256()
                with open(path, "rb") as f:
                    for chunk in iter(lambda: f.read(8 << 20), b""):
                        h.update(chunk)
                if h.hexdigest() != digests[v]:
                    return False
            return True

        def reset() -> None:
            """Drop the rebuilt shards so the next leg starts degraded,
            and wait for the deletion deltas to reach the master — the
            next leg's holder lookups must not see the dead shard as
            alive (rebuild would no-op)."""
            for p in plans:
                v = p["volume_id"]
                stub_of(p["node"]).VolumeEcShardsDelete(
                    vs.VolumeEcShardsDeleteRequest(
                        volume_id=v, collection="",
                        shard_ids=[lost_of[v]]))
            deadline = time.time() + 30
            while time.time() < deadline and any(
                    lost_of[p["volume_id"]] in master.topo.lookup_ec_shards(
                        p["volume_id"])
                    for p in plans):
                time.sleep(0.2)

        def leg_per_volume() -> dict:
            before = counters()
            t0 = time.perf_counter()
            for p in plans:
                v = p["volume_id"]
                stub = stub_of(p["node"])
                resp = stub.VolumeEcShardsRebuild(
                    vs.VolumeEcShardsRebuildRequest(
                        volume_id=v, collection=""))
                rebuilt = list(resp.rebuilt_shard_ids)
                assert rebuilt == [lost_of[v]], (v, rebuilt)
                stub.VolumeEcShardsMount(
                    vs.VolumeEcShardsMountRequest(
                        volume_id=v, collection="", shard_ids=rebuilt))
            dt = time.perf_counter() - t0
            after = counters()
            return {"seconds": round(dt, 3),
                    "rpcs": int(after.get("rpcs", 0)
                                - before.get("rpcs", 0)),
                    "wire_bytes": int(after.get("wire", 0)
                                      - before.get("wire", 0))}

        def leg_batched() -> dict:
            groups: dict = {}
            for p in plans:
                groups.setdefault(p["node"], []).append(p)
            before = counters()
            t0 = time.perf_counter()

            def run_target(item):
                node, tjobs = item
                resp = stub_of(node).VolumeEcShardsBatchRebuild(
                    vs.VolumeEcShardsBatchRebuildRequest(
                        jobs=[vs.BatchRebuildJob(
                            volume_id=p["volume_id"], collection="",
                            shard_size=p["shard_size"]) for p in tjobs]))
                for r in resp.results:
                    assert not r.error, (r.volume_id, r.error)

            with ThreadPoolExecutor(max_workers=len(groups)) as pool:
                list(pool.map(run_target, groups.items()))
            dt = time.perf_counter() - t0
            after = counters()
            return {"seconds": round(dt, 3),
                    "rpcs": int(after.get("rpcs", 0)
                                - before.get("rpcs", 0)),
                    "wire_bytes": int(after.get("wire", 0)
                                      - before.get("wire", 0))}

        # interleaved best-of-2: each leg's best trial faces the same
        # background-interference lottery on a noisy host
        legs: dict = {}
        order = (("per_volume", leg_per_volume),
                 ("batched", leg_batched))
        for trial in range(2):
            for name, fn in order:
                r = fn()
                if not verify():
                    return {**result,
                            "error": f"{name} leg not byte-identical"}
                reset()
                if (name not in legs
                        or r["seconds"] < legs[name]["seconds"]):
                    legs[name] = r
                emit(**{name: legs[name], "trials": trial + 1})
        pv, bt = legs["per_volume"], legs["batched"]
        rebuilt_bytes = n_vols * shard_size
        result.update(
            per_volume=pv, batched=bt, byte_identical=True,
            speedup=round(pv["seconds"] / bt["seconds"], 2)
            if bt["seconds"] else 0.0,
            rpc_reduction=round(pv["rpcs"] / bt["rpcs"], 2)
            if bt["rpcs"] else 0.0,
            wire_bytes_saved=pv["wire_bytes"] - bt["wire_bytes"],
            # reconstructed shard bytes / wall time: the same quantity
            # seaweedfs_repair_batch_bytes_total over _seconds measures,
            # so bench and Prometheus rates compare 1:1
            aggregate_repair_GBps=round(
                rebuilt_bytes / bt["seconds"] / 1e9, 3)
            if bt["seconds"] else 0.0,
            batch_faster=bt["seconds"] < pv["seconds"],
            batch_fewer_wire_bytes=bt["wire_bytes"] < pv["wire_bytes"],
        )
        emit()
        return result
    finally:
        for srv in servers:
            srv.stop()
        if master is not None:
            master.stop()
        shutil.rmtree(tmp, ignore_errors=True)


def _degraded_read_rate(n_needles: int = 600, needle_kb: int = 64,
                        concurrency: int = 16,
                        lose_shards: "list[int] | None" = None,
                        duration_s: float = 4.0) -> dict:
    """BASELINE config 5: streaming EC reads reconstructing needles from
    10-of-14 shards under concurrent load (the reference drives this with
    `weed benchmark` against a degraded volume; here the same read path —
    EcVolume.read_needle -> interval reconstruct on the CPU codec, as
    per-needle reads must never pay device dispatch — runs in-process
    with the reference benchmark's c=16).

    Loses the 4 FIRST data shards, so every needle whose intervals land in
    shards 0-3 pays a full decode-matrix reconstruction from the 10
    survivors; needles on surviving shards measure the undegraded path.
    Reports needles/s and payload GB/s over a fixed wall budget.

    Floor analysis (r05): this host exposes ONE vCPU, so c=16 cannot
    exceed a single core's throughput.  After the r05 optimisation pass
    (single-row decode instead of all-lost reconstruct, .ecx key-column
    searchsorted replacing the pread binary search, and void*-address
    ctypes marshalling) the per-read CPU cost is ~150us — needle parse +
    64KB native CRC32C, the 10-way survivor pread gather, and one GF row
    decode — bounding this host at ~6.5-8k reads/s (r04: 5.0k).  Shard
    reads stay pread, NOT mmap: a truncating racer turns mapped reads
    into process-killing SIGBUS (observed; see EcVolumeShard.read_at).
    The reference's ~47k figure (README.md:545) is an UNdegraded
    1KB-needle run on a multi-core laptop; its shape needs cores.
    """
    import os
    import shutil
    import tempfile
    from concurrent.futures import ThreadPoolExecutor

    from seaweedfs_tpu.storage.ec.constants import to_ext
    from seaweedfs_tpu.storage.ec.encoder import (
        generate_ec_files,
        write_sorted_file_from_idx,
    )
    from seaweedfs_tpu.storage.ec.volume import EcVolume
    from seaweedfs_tpu.storage.needle import FLAG_HAS_NAME, Needle
    from seaweedfs_tpu.storage.super_block import SuperBlock
    from seaweedfs_tpu.storage.volume import Volume

    if lose_shards is None:
        lose_shards = _parse_lose_env(
            "SEAWEEDFS_TPU_BENCH_DEGRADED_LOSE", "0,1,2,3")
    rng = np.random.default_rng(11)
    tmp = tempfile.mkdtemp(prefix="swfs-degraded-")
    try:
        vol = Volume(tmp, "", 1, super_block=SuperBlock())
        payload = needle_kb << 10
        for i in range(1, n_needles + 1):
            n = Needle(cookie=int(rng.integers(0, 2**32)), id=i,
                       data=rng.integers(0, 256, payload)
                       .astype(np.uint8).tobytes())
            n.set(FLAG_HAS_NAME)
            n.name = f"bench-{i}.bin".encode()
            vol.append_needle(n)
        base = vol.file_name()
        vol.close()
        generate_ec_files(base, codec_name="cpu")
        write_sorted_file_from_idx(base)
        for sid in lose_shards:
            os.remove(base + to_ext(sid))

        ev = EcVolume(base, volume_id=1)
        stop_at = time.perf_counter() + duration_s
        t0 = time.perf_counter()

        def worker(seed: int) -> tuple[int, int]:
            r = np.random.default_rng(seed)
            reads = bytes_read = 0
            while time.perf_counter() < stop_at:
                nid = int(r.integers(1, n_needles + 1))
                needle = ev.read_needle(nid)
                assert needle.id == nid
                reads += 1
                bytes_read += len(needle.data)
            return reads, bytes_read

        with ThreadPoolExecutor(max_workers=concurrency) as pool:
            results = list(pool.map(worker, range(concurrency)))
        dt = time.perf_counter() - t0
        ev.close()
        reads = sum(r for r, _ in results)
        payload_bytes = sum(b for _, b in results)
        return {
            "degraded_reads_per_s": round(reads / dt, 1),
            "degraded_read_GBps": round(payload_bytes / dt / 1e9, 4),
            "degraded_concurrency": concurrency,
            "degraded_lost_shards": lose_shards,
        }
    finally:
        shutil.rmtree(tmp, ignore_errors=True)


def _scrape_metrics(url: str) -> dict:
    """-> {sample_name_with_labels: float} for counter/gauge samples."""
    import urllib.request

    from seaweedfs_tpu.telemetry.federation import parse_exposition

    with urllib.request.urlopen(url, timeout=10) as r:
        families, samples = parse_exposition(r.read().decode())
    out = {}
    for family, sample_name, value in samples:
        if families.get(family, ("",))[0] in ("counter", "gauge"):
            try:
                out[sample_name] = float(value)
            except ValueError:
                continue
    return out


# counter families worth folding into bench JSON: cache effectiveness,
# connection reuse, and retry pressure explain a rate delta between runs
_SNAPSHOT_PREFIXES = (
    "seaweedfs_needle_cache_", "seaweedfs_chunk_cache_total",
    "seaweedfs_connpool_reuse_total", "seaweedfs_connpool_dial_total",
    "seaweedfs_connpool_evict_total", "seaweedfs_retry_total",
    "seaweedfs_replication_error_total", "seaweedfs_request_total",
    "seaweedfs_ec_service_jobs_total", "seaweedfs_ec_service_flush_total",
    "seaweedfs_fsync_batch_", "seaweedfs_sendfile_",
    "seaweedfs_ec_preadv_batches_total",
)


def _metrics_delta(before: dict, after: dict) -> dict:
    """Counter deltas over a bench run, filtered to the families above,
    zero deltas dropped; plus derived hit/reuse rates."""
    delta = {}
    for name, v in after.items():
        if not name.startswith(_SNAPSHOT_PREFIXES):
            continue
        d = v - before.get(name, 0.0)
        if d:
            delta[name] = round(d, 3)

    def d(name: str) -> float:
        return delta.get(name, 0.0)

    out = {"metrics_delta": delta}
    hits, misses = d("seaweedfs_needle_cache_hit_total"), d(
        "seaweedfs_needle_cache_miss_total")
    if hits + misses > 0:
        out["needle_cache_hit_rate"] = round(hits / (hits + misses), 4)
    reuse, dial = d("seaweedfs_connpool_reuse_total"), d(
        "seaweedfs_connpool_dial_total")
    if reuse + dial > 0:
        out["connpool_reuse_rate"] = round(reuse / (reuse + dial), 4)
    retries = sum(v for k, v in delta.items()
                  if k.startswith("seaweedfs_retry_total"))
    if retries:
        out["retries_during_run"] = round(retries, 1)
    return out


def _smallfile_rates(n: int = 20000, concurrency: int = 16,
                     payload_bytes: int = 1024,
                     metrics_snapshot: bool = False,
                     verify_bytes: bool = False) -> dict:
    """The reference's ONLY published benchmark: random write then read
    of 1KB files at c=16 through the full HTTP data path (README.md:
    514-567, `weed benchmark` defaults benchmark.go:57-59).  Runs an
    in-process master + volume server and drives keep-alive HTTP
    connections exactly like the reference harness.  n is scaled down
    from the reference's 1,048,576 to keep the stage bounded; rates are
    per-second so the comparison holds."""
    import http.client
    import os
    import shutil
    import tempfile
    import threading
    import urllib.request
    from concurrent.futures import ThreadPoolExecutor

    from seaweedfs_tpu.master.server import MasterServer
    from seaweedfs_tpu.volume.server import VolumeServer

    reserved_ports: set[int] = set()

    def _port() -> int:
        # mirrors tests/helpers.free_port: servers derive grpc_port as
        # port+10000, so anything above 55535 would overflow the port
        # space, and BOTH the http port and its derived grpc sibling
        # must stay clear of every previously reserved pair
        import socket

        while True:
            with socket.socket() as s:
                s.bind(("127.0.0.1", 0))
                p = s.getsockname()[1]
            if (p <= 55000 and p not in reserved_ports
                    and p + 10000 not in reserved_ports):
                reserved_ports.update((p, p + 10000))
                return p

    tmp = tempfile.mkdtemp(prefix="swfs-smallfile-")
    # --metrics-snapshot also runs the judgment plane during the bench:
    # canary probes every second + the SLO engine on its burn-rate
    # rules, so the emitted JSON carries its own SLO verdict (probe
    # p50/p99 + any alerts that fired during the run)
    master = MasterServer(ip="127.0.0.1", port=_port(),
                          volume_size_limit_mb=1024,
                          canary_interval=1.0 if metrics_snapshot else 0.0,
                          slo_interval=1.0 if metrics_snapshot else 0.0)
    master.start()
    vs_ = VolumeServer(directories=[tmp], ip="127.0.0.1", port=_port(),
                       master_addresses=[f"127.0.0.1:{master.grpc_port}"],
                       pulse_seconds=0.5, max_volume_count=16)
    vs_.start()
    try:
        deadline = time.time() + 15
        while time.time() < deadline and len(master.topo.nodes) < 1:
            time.sleep(0.1)
        # --metrics-snapshot: counter state before the run; the delta at
        # the end explains the measured rates (cache hit rates, connpool
        # reuse vs dial, retry pressure) in the emitted JSON
        m_before = (_scrape_metrics(f"http://127.0.0.1:{vs_.port}/metrics")
                    if metrics_snapshot else None)
        # pre-assign fids in bulk through the master (the reference
        # assigns per write; bulk keeps the master out of the hot loop
        # measurement the same way its writeBenchmark reuses assigns)
        fids: list[tuple[str, str]] = []
        with urllib.request.urlopen(
            f"http://127.0.0.1:{master.port}/dir/assign?count={n}",
            timeout=20,
        ) as r:
            first = json.loads(r.read())
        base_fid, url = first["fid"], first["url"]
        vid, _, rest = base_fid.partition(",")
        key_hex, cookie = rest[:-8], rest[-8:]
        base_key = int(key_hex, 16)
        fids = [(f"{vid},{base_key + i:x}{cookie}", url)
                for i in range(n)]
        payload = os.urandom(payload_bytes)
        local = threading.local()

        def conn() -> http.client.HTTPConnection:
            c = getattr(local, "c", None)
            if c is None:
                c = http.client.HTTPConnection("127.0.0.1", vs_.port,
                                               timeout=20)
                c.connect()
                import socket as _socket

                c.sock.setsockopt(_socket.IPPROTO_TCP,
                                  _socket.TCP_NODELAY, 1)
                local.c = c
            return c

        lat: list[float] = []
        lat_lock = threading.Lock()

        def write_one(i: int) -> None:
            fid, _ = fids[i]
            body = (b"--bb\r\nContent-Disposition: form-data; "
                    b'name="file"; filename="b.bin"\r\n\r\n'
                    + payload + b"\r\n--bb--\r\n")
            t0 = time.perf_counter()
            c = conn()
            try:
                c.request("POST", f"/{fid}", body, {
                    "Content-Type": "multipart/form-data; boundary=bb"})
                resp = c.getresponse()
                resp.read()
                if resp.status >= 300:
                    return  # counted as failed, not timed as a success
            except (http.client.HTTPException, OSError):
                c.close()
                local.c = None
                return
            with lat_lock:
                lat.append(time.perf_counter() - t0)

        t0 = time.perf_counter()
        with ThreadPoolExecutor(concurrency) as pool:
            list(pool.map(write_one, range(n)))
        write_dt = time.perf_counter() - t0
        lat.sort()
        out = {
            "smallfile_write_reqs_per_s": round(len(lat) / write_dt, 1),
            "smallfile_write_avg_ms": round(
                sum(lat) / max(len(lat), 1) * 1000, 2),
            "smallfile_write_p99_ms": round(
                lat[int(len(lat) * 0.99) - 1] * 1000, 2) if lat else None,
            "smallfile_n": n,
            "smallfile_concurrency": concurrency,
            "smallfile_failed": n - len(lat),
        }

        lat.clear()
        mismatches = [0]

        def read_one(i: int) -> None:
            # Weyl-sequence index scramble: "random" reads without
            # sharing a numpy Generator across threads (not thread-safe)
            fid, _ = fids[(i * 2654435761) % n]
            t0 = time.perf_counter()
            c = conn()
            try:
                c.request("GET", f"/{fid}")
                resp = c.getresponse()
                body = resp.read()
                if resp.status >= 300:
                    return
                if verify_bytes and body != payload:
                    with lat_lock:
                        mismatches[0] += 1
                    return
            except (http.client.HTTPException, OSError):
                c.close()
                local.c = None
                return
            with lat_lock:
                lat.append(time.perf_counter() - t0)

        t0 = time.perf_counter()
        with ThreadPoolExecutor(concurrency) as pool:
            list(pool.map(read_one, range(n)))
        read_dt = time.perf_counter() - t0
        lat.sort()
        out.update({
            "smallfile_read_reqs_per_s": round(len(lat) / read_dt, 1),
            "smallfile_read_avg_ms": round(
                sum(lat) / max(len(lat), 1) * 1000, 2),
            "smallfile_read_p99_ms": round(
                lat[int(len(lat) * 0.99) - 1] * 1000, 2) if lat else None,
            "smallfile_read_failed": n - len(lat),
        })
        if verify_bytes:
            out["smallfile_byte_mismatches"] = mismatches[0]
        if m_before is not None:
            out.update(_metrics_delta(
                m_before,
                _scrape_metrics(f"http://127.0.0.1:{vs_.port}/metrics")))
            out.update(_slo_verdict(master))
        return out
    finally:
        vs_.stop()
        master.stop()
        shutil.rmtree(tmp, ignore_errors=True)


def _slo_verdict(master) -> dict:
    """Canary probe p50/p99 + alerts that fired during a bench run —
    the run's own SLO verdict, folded into the smallfile JSON so a
    bench regression carries its judgment with it."""
    from seaweedfs_tpu.stats.metrics import CANARY_PROBE_SECONDS

    out: dict = {}
    canary = master.canary.status()
    out["canary_probe_ticks"] = canary["tick"]
    out["canary_byte_mismatches"] = canary["byteMismatches"]
    counts, count, _total = _hist_child_snapshot(
        CANARY_PROBE_SECONDS, "volume_rt")
    if count:
        buckets = CANARY_PROBE_SECONDS.buckets
        out["canary_probe_p50_ms"] = round(
            _hist_quantile(buckets, counts, count, 0.5) * 1e3, 3)
        out["canary_probe_p99_ms"] = round(
            _hist_quantile(buckets, counts, count, 0.99) * 1e3, 3)
    fired = [h for h in master.slo.status(evaluate_if_idle=False)["history"]
             if h["state"] == "firing"]
    out["slo_alerts_fired"] = [
        {"slo": h["slo"], "severity": h["severity"],
         "burnShort": h.get("burnShort")} for h in fired]
    out["slo_clean"] = not any(
        h["severity"] == "page" for h in fired)
    return out


def _flight_overhead(n: int = 8000, concurrency: int = 16) -> dict:
    """ISSUE 20 acceptance gate: the always-on flight-recorder planes
    (continuous profiler + hot-key sketch) must cost under
    SEAWEEDFS_TPU_BENCH_FLIGHT_MAX_PCT (default 3%) of smallfile req/s.

    Same-host A/B: one smallfile leg with both planes disabled, one
    with production defaults.  A throwaway warmup leg runs first so the
    OFF leg does not pocket the process's import/allocator warmup and
    overstate the ON leg's cost."""
    import os

    from seaweedfs_tpu.telemetry import hotkeys
    from seaweedfs_tpu.util import profiler

    def leg(on: bool, leg_n: int) -> dict:
        override = ({} if on else
                    {profiler.DISABLE_VAR: "1", hotkeys.DISABLE_VAR: "0"})
        saved = {k: os.environ.get(k)
                 for k in (profiler.DISABLE_VAR, hotkeys.DISABLE_VAR)}
        for k in saved:
            os.environ.pop(k, None)
        os.environ.update(override)
        profiler.stop_continuous()
        hotkeys.reset()
        try:
            return _smallfile_rates(n=leg_n, concurrency=concurrency)
        finally:
            for k, v in saved.items():
                if v is None:
                    os.environ.pop(k, None)
                else:
                    os.environ[k] = v
            profiler.stop_continuous()
            hotkeys.reset()

    leg(True, max(n // 8, 500))  # warmup, discarded
    # interleaved off/on pairs, judged by the MEDIAN per-pair ratio:
    # adjacent legs share the host's load drift, so their ratio cancels
    # it — a global off-vs-on comparison on a shared box confuses
    # minutes-scale drift (observed at 20%+) with the planes' real cost
    offs, ons = [], []
    for _ in range(3):
        offs.append(leg(False, n))
        ons.append(leg(True, n))

    def med(vals: list[float]) -> float:
        vals = sorted(vals)
        return vals[len(vals) // 2]

    out: dict = {"flight_overhead_n": n}
    worst = 0.0
    for op in ("write", "read"):
        key = f"smallfile_{op}_reqs_per_s"
        out[f"flight_off_{op}_reqs_per_s"] = med([r[key] for r in offs])
        out[f"flight_on_{op}_reqs_per_s"] = med([r[key] for r in ons])
        ratios = [on[key] / off[key]
                  for off, on in zip(offs, ons) if off[key]]
        if ratios:
            worst = max(worst, (1.0 - med(ratios)) * 100.0)
    out["flight_overhead_pct"] = round(worst, 2)
    max_pct = float(os.environ.get(
        "SEAWEEDFS_TPU_BENCH_FLIGHT_MAX_PCT", "3.0"))
    out["flight_overhead_max_pct"] = max_pct
    out["flight_overhead_ok"] = worst <= max_pct
    return out


def _hist_child_snapshot(hist, *labels):
    """(cumulative counts[], count, total) for one histogram child —
    bench-side delta arithmetic over the in-process registry."""
    return hist.labels(*labels).cumulative()


def _hist_quantile(buckets, counts, count, q: float) -> float:
    """Linear-interpolated quantile from cumulative bucket counts (the
    usual Prometheus histogram_quantile estimate)."""
    if count <= 0:
        return 0.0
    rank = q * count
    prev_cum, prev_bound = 0, 0.0
    for bound, cum in zip(buckets, counts):
        if cum >= rank:
            if cum == prev_cum:
                return bound
            return prev_bound + (bound - prev_bound) * (
                (rank - prev_cum) / (cum - prev_cum))
        prev_cum, prev_bound = cum, bound
    return buckets[-1] if buckets else 0.0


def _serving_rates() -> dict:
    """ISSUE 18 serving-plane stage, leg by leg:

    * **fsync A/B** (`serving_fsync_write_speedup`): direct concurrent
      Volume appends with SEAWEEDFS_TPU_DURABILITY=sync (one fsync pair
      per mutation — the per-write strawman) vs =batch (one fsync pair
      per group-commit barrier).  Same threads, same payloads; the
      speedup is pure fsync batching, and the batch run's commit/write
      counter deltas report the achieved mean batch size.
    * **sendfile A/B** (`serving_sendfile_read_speedup`): whole-needle
      GETs through the volume HTTP path with SEAWEEDFS_TPU_SENDFILE
      toggled per phase (the env is read per request), needle cache off
      so every GET takes the disk path.  Every response is sha256'd
      against the written payload in BOTH phases —
      `serving_byte_identity` gates the speedup.
    * **keep-alive leg** (ISSUE 18f): parks >=2000 idle keep-alive
      sockets on the event-loop front end, then drives M active
      clients — req/s, p99, the server's own open-socket gauge,
      per-socket RSS delta (client+server share this process, so the
      delta is an upper bound on the server's share), and a post-run
      probe of every idle socket proving zero resets.
    """
    import hashlib
    import http.client
    import os
    import resource
    import shutil
    import tempfile
    import threading
    import urllib.request
    from concurrent.futures import ThreadPoolExecutor

    from seaweedfs_tpu.stats.metrics import (
        FSYNC_BATCH_COMMITS,
        FSYNC_BATCH_WRITES,
        HTTPD_OPEN_SOCKETS,
        SENDFILE_BYTES,
        SENDFILE_FALLBACK,
    )
    from seaweedfs_tpu.storage import Needle, SuperBlock
    from seaweedfs_tpu.storage.volume import Volume

    out: dict = {}

    def emit(**kv) -> None:
        print(json.dumps({"partial": True, **kv}), flush=True)

    def _with_env(key: str, val: str | None):
        """Set/unset one env var, returning an undo callable."""
        old = os.environ.get(key)
        if val is None:
            os.environ.pop(key, None)
        else:
            os.environ[key] = val

        def undo() -> None:
            if old is None:
                os.environ.pop(key, None)
            else:
                os.environ[key] = old
        return undo

    # ---- leg 1: fsync A/B (direct volume appends, no HTTP) ---------------
    n_threads = int(os.environ.get("SEAWEEDFS_TPU_BENCH_FSYNC_THREADS", "16"))
    per_thread = int(os.environ.get("SEAWEEDFS_TPU_BENCH_FSYNC_WRITES", "64"))
    payload_1k = os.urandom(1024)

    def _fsync_writes_per_s(mode: str) -> float:
        tmp = tempfile.mkdtemp(prefix=f"swfs-fsync-{mode}-")
        undo = _with_env("SEAWEEDFS_TPU_DURABILITY", mode)
        # a parked writer can't queue a second mutation, so the barrier
        # can only ever hold n_threads pendings — cap the batch there or
        # the leader burns the full max-delay waiting for writers that
        # cannot arrive
        undo_batch = _with_env("SEAWEEDFS_TPU_FSYNC_MAX_BATCH",
                               str(n_threads))
        try:
            v = Volume(tmp, "", 1, super_block=SuperBlock())
            start = threading.Barrier(n_threads)

            def writer(tid: int) -> None:
                start.wait()
                for k in range(per_thread):
                    v.append_needle(Needle(
                        cookie=0x5EAF00D,
                        id=1 + tid * per_thread + k,
                        data=payload_1k))

            threads = [threading.Thread(target=writer, args=(t,))
                       for t in range(n_threads)]
            t0 = time.perf_counter()
            for th in threads:
                th.start()
            for th in threads:
                th.join()
            dt = time.perf_counter() - t0
            v.close()
            return n_threads * per_thread / dt
        finally:
            undo()
            undo_batch()
            shutil.rmtree(tmp, ignore_errors=True)

    sync_rate = _fsync_writes_per_s("sync")
    commits0 = FSYNC_BATCH_COMMITS.labels().value
    writes0 = FSYNC_BATCH_WRITES.labels().value
    batch_rate = _fsync_writes_per_s("batch")
    commits = FSYNC_BATCH_COMMITS.labels().value - commits0
    writes = FSYNC_BATCH_WRITES.labels().value - writes0
    out.update({
        "serving_fsync_sync_writes_per_s": round(sync_rate, 1),
        "serving_fsync_batch_writes_per_s": round(batch_rate, 1),
        "serving_fsync_write_speedup": round(batch_rate / sync_rate, 2)
        if sync_rate else None,
        "serving_fsync_batch_commits": int(commits),
        "serving_fsync_mean_batch_size": round(writes / commits, 1)
        if commits else None,
        "serving_fsync_concurrency": n_threads,
    })
    emit(**{k: out[k] for k in (
        "serving_fsync_write_speedup", "serving_fsync_mean_batch_size")})

    # ---- legs 2+3 share one in-process master + volume server ------------
    from seaweedfs_tpu.master.server import MasterServer
    from seaweedfs_tpu.volume.server import VolumeServer

    reserved: set[int] = set()

    def _port() -> int:
        import socket

        while True:
            with socket.socket() as s:
                s.bind(("127.0.0.1", 0))
                p = s.getsockname()[1]
            if (p <= 55000 and p not in reserved
                    and p + 10000 not in reserved):
                reserved.update((p, p + 10000))
                return p

    tmp = tempfile.mkdtemp(prefix="swfs-serving-")
    # cache off: a needle-cache hit declines sendfile by design, so the
    # A/B must keep every GET on the disk path to measure the copy
    undo_cache = _with_env("SEAWEEDFS_TPU_NEEDLE_CACHE_MB", "0")
    master = MasterServer(ip="127.0.0.1", port=_port(),
                          volume_size_limit_mb=1024)
    master.start()
    vs_ = VolumeServer(directories=[tmp], ip="127.0.0.1", port=_port(),
                       master_addresses=[f"127.0.0.1:{master.grpc_port}"],
                       pulse_seconds=0.5, max_volume_count=16)
    vs_.start()
    local = threading.local()

    def conn() -> http.client.HTTPConnection:
        c = getattr(local, "c", None)
        if c is None:
            c = http.client.HTTPConnection("127.0.0.1", vs_.port,
                                           timeout=30)
            local.c = c
        return c

    def _post(fid: str, payload: bytes) -> int:
        body = (b"--bb\r\nContent-Disposition: form-data; "
                b'name="file"; filename="b.bin"\r\n\r\n'
                + payload + b"\r\n--bb--\r\n")
        c = conn()
        try:
            c.request("POST", f"/{fid}", body, {
                "Content-Type": "multipart/form-data; boundary=bb"})
            resp = c.getresponse()
            resp.read()
            return resp.status
        except (http.client.HTTPException, OSError):
            c.close()
            local.c = None
            return 599

    try:
        deadline = time.time() + 15
        while time.time() < deadline and len(master.topo.nodes) < 1:
            time.sleep(0.1)

        # ---- leg 2: sendfile A/B -----------------------------------------
        big_n = int(os.environ.get("SEAWEEDFS_TPU_BENCH_SENDFILE_N", "48"))
        big_bytes = int(os.environ.get(
            "SEAWEEDFS_TPU_BENCH_SENDFILE_KB", "512")) * 1024
        rounds = int(os.environ.get(
            "SEAWEEDFS_TPU_BENCH_SENDFILE_ROUNDS", "8"))
        with urllib.request.urlopen(
            f"http://127.0.0.1:{master.port}/dir/assign?count={big_n + 4096}",
            timeout=20,
        ) as r:
            first = json.loads(r.read())
        vid, _, rest = first["fid"].partition(",")
        key_hex, cookie = rest[:-8], rest[-8:]
        base_key = int(key_hex, 16)

        def fid(i: int) -> str:
            return f"{vid},{base_key + i:x}{cookie}"

        digests: dict[str, str] = {}
        for i in range(big_n):
            payload = os.urandom(big_bytes)
            digests[fid(i)] = hashlib.sha256(payload).hexdigest()
            assert _post(fid(i), payload) < 300, "sendfile-leg write failed"

        identity_ok = True

        def _read_phase(read_c: int = 8) -> float:
            nonlocal identity_ok
            lat_bytes = [0]
            lock = threading.Lock()

            def read_one(j: int) -> None:
                nonlocal identity_ok
                f = fid(j % big_n)
                c = conn()
                try:
                    c.request("GET", f"/{f}")
                    resp = c.getresponse()
                    body = resp.read()
                    if resp.status != 200:
                        identity_ok = False
                        return
                except (http.client.HTTPException, OSError):
                    c.close()
                    local.c = None
                    identity_ok = False
                    return
                if hashlib.sha256(body).hexdigest() != digests[f]:
                    identity_ok = False
                with lock:
                    lat_bytes[0] += len(body)

            t0 = time.perf_counter()
            with ThreadPoolExecutor(read_c) as pool:
                list(pool.map(read_one, range(big_n * rounds)))
            dt = time.perf_counter() - t0
            return lat_bytes[0] / dt / 1e9

        undo_sf = _with_env("SEAWEEDFS_TPU_SENDFILE", "0")
        _read_phase()  # warm the page cache so both phases read warm
        off_gbps = _read_phase()
        undo_sf()
        undo_sf = _with_env("SEAWEEDFS_TPU_SENDFILE", "1")
        sf0 = SENDFILE_BYTES.labels().value
        on_gbps = _read_phase()
        sf_bytes = SENDFILE_BYTES.labels().value - sf0
        undo_sf()
        out.update({
            "serving_sendfile_off_GBps": round(off_gbps, 3),
            "serving_sendfile_on_GBps": round(on_gbps, 3),
            "serving_sendfile_read_speedup": round(on_gbps / off_gbps, 2)
            if off_gbps else None,
            "serving_sendfile_bytes": int(sf_bytes),
            "serving_byte_identity": identity_ok,
            "serving_sendfile_payload_kb": big_bytes // 1024,
        })
        emit(serving_sendfile_read_speedup=out[
            "serving_sendfile_read_speedup"],
            serving_byte_identity=identity_ok)

        # ---- leg 3: thousands-of-sockets keep-alive ----------------------
        idle_target = int(os.environ.get(
            "SEAWEEDFS_TPU_BENCH_IDLE_SOCKETS", "2000"))
        active_c = int(os.environ.get(
            "SEAWEEDFS_TPU_BENCH_ACTIVE_CLIENTS", "16"))
        active_n = int(os.environ.get(
            "SEAWEEDFS_TPU_BENCH_ACTIVE_REQS", "4000"))
        soft, hard = resource.getrlimit(resource.RLIMIT_NOFILE)
        need = idle_target * 2 + 1024
        if soft < need:
            lifted = min(need, hard)
            resource.setrlimit(resource.RLIMIT_NOFILE, (lifted, hard))
            if lifted < need:  # hard cap too low: shrink, don't fail
                idle_target = max(64, (lifted - 1024) // 2)

        # a small-file population for the active clients (1KB GETs)
        small_n = 256
        for i in range(small_n):
            p = os.urandom(1024)
            digests[fid(big_n + i)] = hashlib.sha256(p).hexdigest()
            assert _post(fid(big_n + i), p) < 300

        def _rss_kb() -> int:
            with open("/proc/self/status") as f:
                for line in f:
                    if line.startswith("VmRSS:"):
                        return int(line.split()[1])
            return 0

        rss_before = _rss_kb()
        idles: list[http.client.HTTPConnection] = []
        idles_lock = threading.Lock()

        def _park_one(_i: int) -> None:
            c = http.client.HTTPConnection("127.0.0.1", vs_.port,
                                           timeout=30)
            c.request("GET", f"/{fid(big_n)}")
            c.getresponse().read()  # keep-alive: socket parks on the loop
            with idles_lock:
                idles.append(c)

        with ThreadPoolExecutor(64) as pool:
            list(pool.map(_park_one, range(idle_target)))
        time.sleep(0.5)  # let the loop account every parked socket
        gauge_sockets = HTTPD_OPEN_SOCKETS.labels("volume").value
        rss_after_park = _rss_kb()

        lat: list[float] = []
        lat_lock = threading.Lock()
        failures = [0]

        def _active_one(j: int) -> None:
            f = fid(big_n + (j * 2654435761) % small_n)
            t0 = time.perf_counter()
            c = conn()
            try:
                c.request("GET", f"/{f}")
                resp = c.getresponse()
                resp.read()
                if resp.status != 200:
                    with lat_lock:
                        failures[0] += 1
                    return
            except (http.client.HTTPException, OSError):
                c.close()
                local.c = None
                with lat_lock:
                    failures[0] += 1
                return
            with lat_lock:
                lat.append(time.perf_counter() - t0)

        t0 = time.perf_counter()
        with ThreadPoolExecutor(active_c) as pool:
            list(pool.map(_active_one, range(active_n)))
        active_dt = time.perf_counter() - t0
        lat.sort()

        # every idle socket must still be usable: one GET each, any
        # reset/close counts against the zero-resets gate
        resets = [0]

        def _probe_idle(c: http.client.HTTPConnection) -> None:
            try:
                c.request("GET", f"/{fid(big_n)}")
                resp = c.getresponse()
                resp.read()
                if resp.status != 200:
                    raise OSError("bad status")
            except (http.client.HTTPException, OSError):
                with idles_lock:
                    resets[0] += 1

        with ThreadPoolExecutor(64) as pool:
            list(pool.map(_probe_idle, idles))
        for c in idles:
            c.close()

        out.update({
            "keepalive_idle_sockets": len(idles),
            "keepalive_open_sockets_gauge": int(gauge_sockets),
            "keepalive_active_reqs_per_s": round(len(lat) / active_dt, 1)
            if active_dt else None,
            "keepalive_active_p99_ms": round(
                lat[int(len(lat) * 0.99) - 1] * 1000, 2) if lat else None,
            "keepalive_active_failed": failures[0],
            "keepalive_resets": resets[0],
            "keepalive_rss_per_socket_kb": round(
                max(0, rss_after_park - rss_before) / max(len(idles), 1), 2),
            "keepalive_active_clients": active_c,
        })
        return out
    finally:
        undo_cache()
        vs_.stop()
        master.stop()
        shutil.rmtree(tmp, ignore_errors=True)


def _serving_smoke(concurrency: int = 64, n: int = 1500) -> dict:
    """ISSUE 18e CI smoke: the smallfile path at c>=64 keep-alive with
    the event-loop front end OFF then ON — every byte read back must
    match what was written and not one response may be a 5xx (or fail
    outright).  Bounded: two in-process clusters, ~2*n tiny requests
    each."""
    import os

    out: dict = {"serving_smoke_concurrency": concurrency}
    ok = True
    for mode in ("off", "volume"):
        old = os.environ.get("SEAWEEDFS_TPU_EVENTLOOP")
        os.environ["SEAWEEDFS_TPU_EVENTLOOP"] = mode
        try:
            res = _smallfile_rates(n=n, concurrency=concurrency,
                                   verify_bytes=True)
        finally:
            if old is None:
                os.environ.pop("SEAWEEDFS_TPU_EVENTLOOP", None)
            else:
                os.environ["SEAWEEDFS_TPU_EVENTLOOP"] = old
        tag = "eventloop_on" if mode == "volume" else "eventloop_off"
        # _smallfile_rates counts any >=300 status or socket error as
        # failed, so failed==0 across both phases IS the zero-5xx gate;
        # verify_bytes makes every read compare against the written
        # payload, so mismatches==0 is the byte-identity gate
        failed = res["smallfile_failed"] + res["smallfile_read_failed"]
        out[f"{tag}_write_reqs_per_s"] = res["smallfile_write_reqs_per_s"]
        out[f"{tag}_read_reqs_per_s"] = res["smallfile_read_reqs_per_s"]
        out[f"{tag}_failed"] = failed
        out[f"{tag}_byte_mismatches"] = res["smallfile_byte_mismatches"]
        ok = ok and failed == 0 and res["smallfile_byte_mismatches"] == 0
    out["serving_smoke_ok"] = ok
    return out


def _service_rates() -> dict:
    """ISSUE 6 service stage: N volumes' concurrent encode+rebuild GF
    jobs through the shared codec service vs per-volume direct dispatch.

    Two profiles, both on the host codec (the device path is verified
    byte-identical on the virtual mesh in tests/test_codec_service.py):

    * **interval** (the headline `service_speedup`): needle-interval-
      sized jobs (SEAWEEDFS_TPU_BENCH_SERVICE_KB, default 2KB — the
      reference's canonical 1KB-file benchmark decodes ~1.1KB intervals)
      mixing encode parity with rebuild decode-plan applies.  This is
      the regime the service exists for: per-job dispatch overhead
      dominates the GF kernel, and the scheduler's coalescing turns N
      producers' per-call Python into one kernel call per batch.
    * **bulk**: 1MB pipeline slices with reused output buffers — shows
      bulk encode loses nothing by routing through the service
      (`service_bulk_ratio`, expect ~0.9-1.0: kernel-bound either way).

    Occupancy and the p50/p99 of a job's queue wait come from the
    seaweedfs_ec_service_* registry deltas, so the numbers folded into
    the JSON are exactly what /metrics would report.
    """
    import os
    from concurrent.futures import ThreadPoolExecutor

    from seaweedfs_tpu.ops import gf256
    from seaweedfs_tpu.ops.codec_service import CodecService
    from seaweedfs_tpu.ops.rs_cpu import ReedSolomon
    from seaweedfs_tpu.stats.metrics import (
        EC_SERVICE_BATCH_JOBS,
        EC_SERVICE_STAGE,
    )

    n_vol = int(os.environ.get("SEAWEEDFS_TPU_BENCH_SERVICE_VOLUMES", "8"))
    kb = int(os.environ.get("SEAWEEDFS_TPU_BENCH_SERVICE_KB", "2"))
    n_jobs = int(os.environ.get("SEAWEEDFS_TPU_BENCH_SERVICE_JOBS", "6000"))
    group = 16
    width = kb << 10
    rng = np.random.default_rng(7)
    rs = ReedSolomon()
    blocks = [rng.integers(0, 256, (10, width), dtype=np.uint8)
              for _ in range(n_vol)]
    # rebuild decode plan for the worst-case loss (first 4 data shards)
    plan = gf256.decode_plan_for(
        rs.matrix, 10, list(range(4, 14)), (0, 1, 2, 3))

    result: dict = {"service_volumes": n_vol, "service_job_kb": kb,
                    "service_jobs_per_volume": n_jobs,
                    "service_mode": "host"}

    def emit(**kv) -> None:
        result.update(kv)
        print(json.dumps({"partial": True, **result}), flush=True)

    def baseline_worker(v: int) -> None:
        codec = ReedSolomon()  # per-volume dispatch: own codec, own calls
        if v % 2 == 0:
            for _ in range(n_jobs):
                codec.parity_of(blocks[v])
        else:
            for _ in range(n_jobs):
                codec.apply_rows(plan, list(blocks[v]))

    def service_worker(svc: CodecService, v: int) -> None:
        pend: list = []
        done = 0
        while done < n_jobs:
            g = min(group, n_jobs - done)
            if v % 2 == 0:
                pend.extend(svc.submit_parity_many([blocks[v]] * g))
            else:
                pend.extend(svc.submit_apply_many(plan, [blocks[v]] * g))
            done += g
            while len(pend) > 2 * group:
                pend.pop(0).result()
        for f in pend:
            f.result()

    total_bytes = n_vol * n_jobs * 10 * width
    rs.parity_of(blocks[0])  # warm the native lib before any timing

    # byte identity through the service before any rates are quoted
    svc = CodecService(mode="host")
    got = np.stack([np.asarray(r) for r in
                    svc.submit_parity(blocks[0]).result(30)])
    if not np.array_equal(got, rs.parity_of(blocks[0])):
        svc.close()
        return {"error": "service parity not byte-identical to cpu_simd"}
    got = np.stack([np.asarray(r) for r in
                    svc.submit_apply(plan, blocks[1]).result(30)])
    if not np.array_equal(got, np.stack(rs.apply_rows(plan, list(blocks[1])))):
        svc.close()
        return {"error": "service decode not byte-identical to cpu_simd"}
    result["service_byte_identical"] = True

    # best-of-2 (same reasoning as every other stage on this noisy host)
    base_dt = svc_dt = None
    occ_before = _hist_child_snapshot(EC_SERVICE_BATCH_JOBS, "pipeline")
    lat_before = _hist_child_snapshot(EC_SERVICE_STAGE, "queue_wait",
                                      "pipeline")
    for trial in range(2):
        t0 = time.perf_counter()
        with ThreadPoolExecutor(n_vol) as pool:
            list(pool.map(baseline_worker, range(n_vol)))
        dt = time.perf_counter() - t0
        base_dt = dt if base_dt is None else min(base_dt, dt)
        t0 = time.perf_counter()
        with ThreadPoolExecutor(n_vol) as pool:
            list(pool.map(lambda v: service_worker(svc, v), range(n_vol)))
        dt = time.perf_counter() - t0
        svc_dt = dt if svc_dt is None else min(svc_dt, dt)
        emit(per_volume_GBps=round(total_bytes / base_dt / 1e9, 3),
             service_GBps=round(total_bytes / svc_dt / 1e9, 3),
             service_speedup=round(base_dt / svc_dt, 3),
             service_trials=trial + 1)
    occ_after = _hist_child_snapshot(EC_SERVICE_BATCH_JOBS, "pipeline")
    jobs_delta = occ_after[1] - occ_before[1]
    if jobs_delta > 0:
        result["service_batch_occupancy_mean"] = round(
            (occ_after[2] - occ_before[2]) / jobs_delta, 2)
    # p50/p99 of a job's wait for the scheduler (submit -> popped into a
    # batch) over the service runs, from the histogram delta: deltas of
    # cumulative counts stay cumulative
    lat_after = _hist_child_snapshot(EC_SERVICE_STAGE, "queue_wait",
                                     "pipeline")
    lat_counts = [a - b for a, b in zip(lat_after[0], lat_before[0])]
    lat_n = lat_after[1] - lat_before[1]
    if lat_n:
        buckets = EC_SERVICE_STAGE.buckets
        result["service_queue_wait_p50_ms"] = round(
            _hist_quantile(buckets, lat_counts, lat_n, 0.50) * 1000, 3)
        result["service_queue_wait_p99_ms"] = round(
            _hist_quantile(buckets, lat_counts, lat_n, 0.99) * 1000, 3)
    svc.close()

    # bulk profile: 1MB pipeline slices, reused outputs on both sides
    bulk_w = 1 << 20
    bulk_jobs = int(os.environ.get("SEAWEEDFS_TPU_BENCH_SERVICE_BULK_JOBS",
                                   "30"))
    bulk_blocks = [rng.integers(0, 256, (10, bulk_w), dtype=np.uint8)
                   for _ in range(n_vol)]

    def bulk_base(v: int) -> None:
        codec = ReedSolomon()
        outs = [np.empty((4, bulk_w), np.uint8) for _ in range(4)]
        for k in range(bulk_jobs):
            codec.parity_into(list(bulk_blocks[v]), list(outs[k % 4]))

    def bulk_service(svc2: CodecService, v: int) -> None:
        outs = [np.empty((4, bulk_w), np.uint8) for _ in range(4)]
        pend: list = []
        for k in range(bulk_jobs):
            pend.append(svc2.submit_parity(bulk_blocks[v], out=outs[k % 4]))
            if len(pend) > 2:
                pend.pop(0).result()
        for f in pend:
            f.result()

    svc2 = CodecService(mode="host")
    bulk_bytes = n_vol * bulk_jobs * 10 * bulk_w
    bb = bs = None  # best-of-2: same noisy-host reasoning as every stage
    for _ in range(2):
        t0 = time.perf_counter()
        with ThreadPoolExecutor(n_vol) as pool:
            list(pool.map(bulk_base, range(n_vol)))
        bb = min(bb or 1e9, time.perf_counter() - t0)
        t0 = time.perf_counter()
        with ThreadPoolExecutor(n_vol) as pool:
            list(pool.map(lambda v: bulk_service(svc2, v), range(n_vol)))
        bs = min(bs or 1e9, time.perf_counter() - t0)
    svc2.close()
    result.update(
        per_volume_bulk_GBps=round(bulk_bytes / bb / 1e9, 3),
        service_bulk_GBps=round(bulk_bytes / bs / 1e9, 3),
        service_bulk_ratio=round(bb / bs, 3),
    )
    return result


def _soak_rates() -> dict:
    """ISSUE 9 / ROADMAP 5d soak smoke: production-shaped mixed traffic.

    Bounded (~SEAWEEDFS_TPU_SOAK_SECONDS, default 30s of load + setup):
    an in-process master + 2 volume servers run concurrent reads AND
    writes while the lifecycle controller executes one forced
    seal -> EC-encode transition on a filled volume and a vacuum on a
    garbage-heavy sibling.  Asserts:

      * every read during every stage returns the exact original bytes
        (byte-identity through seal, encode, volume delete, EC serving);
      * zero client-visible 5xx;
      * read p99 from the registry request histogram stays under the
        SLO (SEAWEEDFS_TPU_SOAK_P99_S, default 2.0s — generous for
        noisy 1-vCPU CI hosts; the point is catching order-of-magnitude
        regressions under mixed load, not microbenchmarking).

    Emits soak_ok plus the measured numbers; the CI step gates on
    soak_ok so every future PR is judged under production-shaped
    traffic, not single-op microbenches.
    """
    import os
    import shutil
    import socket
    import tempfile
    import threading
    import urllib.error
    import urllib.request
    from concurrent.futures import ThreadPoolExecutor

    from seaweedfs_tpu.master.server import MasterServer
    from seaweedfs_tpu.stats.metrics import REQUEST_HISTOGRAM
    from seaweedfs_tpu.volume.server import VolumeServer

    soak_s = float(os.environ.get("SEAWEEDFS_TPU_SOAK_SECONDS", "30"))
    slo_p99_s = float(os.environ.get("SEAWEEDFS_TPU_SOAK_P99_S", "2.0"))
    reserved: set[int] = set()

    def _port() -> int:
        while True:
            with socket.socket() as s:
                s.bind(("127.0.0.1", 0))
                p = s.getsockname()[1]
            if (p <= 55000 and p not in reserved
                    and p + 10000 not in reserved):
                reserved.update((p, p + 10000))
                return p

    tmp = tempfile.mkdtemp(prefix="swfs-soak-")
    journal_dir = tempfile.mkdtemp(prefix="swfs-soak-journal-")
    master = MasterServer(
        ip="127.0.0.1", port=_port(), volume_size_limit_mb=4,
        lifecycle_dir=journal_dir,
        lifecycle_policy={"*": {
            # force the pipeline inside the bounded window: seal at 10%
            # fullness, encode as soon as sealed+quiet 1s, vacuum at 25%
            "seal_full_percent": 10.0, "ec_cooldown_seconds": 1.0,
            "vacuum_garbage_ratio": 0.25,
        }})
    master.start()
    vols = []
    for i in range(2):
        d = os.path.join(tmp, f"v{i}")
        os.makedirs(d)
        v = VolumeServer(directories=[d], ip="127.0.0.1", port=_port(),
                         master_addresses=[f"127.0.0.1:{master.grpc_port}"],
                         pulse_seconds=0.5, max_volume_count=16)
        v.start()
        vols.append(v)
    errors: list[str] = []
    try:
        deadline = time.time() + 15
        while time.time() < deadline and len(master.topo.nodes) < 2:
            time.sleep(0.1)

        def put(fid: str, url: str, payload: bytes) -> bool:
            body = (b"--bb\r\nContent-Disposition: form-data; "
                    b'name="file"; filename="s.bin"\r\n\r\n'
                    + payload + b"\r\n--bb--\r\n")
            req = urllib.request.Request(
                f"http://{url}/{fid}", data=body, method="POST",
                headers={"Content-Type":
                         "multipart/form-data; boundary=bb"})
            with urllib.request.urlopen(req, timeout=20) as r:
                return r.status < 300

        def assign() -> tuple[str, str]:
            with urllib.request.urlopen(
                f"http://127.0.0.1:{master.port}/dir/assign", timeout=20
            ) as r:
                a = json.loads(r.read())
            return a["fid"], a["url"]

        def derived_fids(base_fid: str, n: int) -> list[str]:
            # consecutive keys on the SAME volume (the smallfile-bench
            # trick): lets the seeding fill one specific volume instead
            # of scattering across the whole writable set
            vid_s, _, rest = base_fid.partition(",")
            base_key = int(rest[:-8], 16)
            cookie = rest[-8:]
            return [f"{vid_s},{base_key + i:x}{cookie}" for i in range(n)]

        # seed the lifecycle target: fill one volume past the seal
        # threshold (4MB limit * 10% = ~420KB) with known payloads
        rng = np.random.default_rng(7)
        known: dict[tuple[str, str], bytes] = {}
        first_fid, first_url = assign()
        target_vid = int(first_fid.split(",")[0])
        for fid in derived_fids(first_fid, 10):
            payload = rng.integers(0, 256, 64 << 10).astype(
                np.uint8).tobytes()
            if put(fid, first_url, payload):
                known[(fid, first_url)] = payload
        # garbage-heavy sibling for the vacuum leg: write then delete
        # most of a second volume's needles
        g_base = None
        for _ in range(20):
            fid, url = assign()
            if int(fid.split(",")[0]) != target_vid:
                g_base = (fid, url)
                break
        if g_base is not None:
            g_fids = derived_fids(g_base[0], 10)
            for fid in g_fids:
                put(fid, g_base[1], os.urandom(32 << 10))
            for fid in g_fids[:-2]:
                req = urllib.request.Request(
                    f"http://{g_base[1]}/{fid}", method="DELETE")
                with urllib.request.urlopen(req, timeout=20):
                    pass

        stop = threading.Event()
        counts = {"reads": 0, "writes": 0}
        lock = threading.Lock()
        items = list(known.items())

        def reader(i: int) -> None:
            while not stop.is_set():
                (fid, url), want = items[counts["reads"] % len(items)]
                try:
                    with urllib.request.urlopen(
                            f"http://{url}/{fid}", timeout=20) as r:
                        got = r.read()
                        if r.status >= 500:
                            errors.append(f"read {fid}: {r.status}")
                        elif got != want:
                            errors.append(f"read {fid}: wrong bytes "
                                          f"({len(got)} vs {len(want)})")
                except urllib.error.HTTPError as e:
                    if e.code >= 500:
                        errors.append(f"read {fid}: {e.code}")
                except OSError as e:
                    errors.append(f"read {fid}: {e}")
                with lock:
                    counts["reads"] += 1

        def writer() -> None:
            # write failures do NOT gate the soak: the assign->write
            # window races the seal (a just-sealed volume bounces a
            # write until the next heartbeat updates the writable set),
            # and the production client re-assigns on that — modeled
            # here by simply retrying with a fresh assign
            while not stop.is_set():
                try:
                    fid, url = assign()
                    if put(fid, url, os.urandom(8 << 10)):
                        with lock:
                            counts["writes"] += 1
                except (urllib.error.HTTPError, OSError):
                    pass
                time.sleep(0.02)

        c0, n0, _t0 = _hist_child_snapshot(
            REQUEST_HISTOGRAM, "volumeServer", "get")
        pool = ThreadPoolExecutor(5)
        futs = [pool.submit(reader, i) for i in range(4)]
        futs.append(pool.submit(writer))
        t_start = time.perf_counter()
        # the forced lifecycle transition runs CONCURRENTLY with the
        # load: cycles until seal + ec_encode + vacuum all land
        transitions_done: dict = {}
        cycle_deadline = time.time() + max(soak_s - 2, 5)
        while time.time() < cycle_deadline:
            master.lifecycle.run_once()
            states = master.lifecycle.journal.counts()
            transitions_done = {
                j["key"]: j["state"]
                for j in master.lifecycle.journal.jobs(("done",))}
            if (f"{target_vid}:ec_encode" in transitions_done
                    and any(k.endswith(":vacuum")
                            for k in transitions_done)):
                break
            time.sleep(1.0)
        remaining = soak_s - (time.perf_counter() - t_start)
        if remaining > 0:
            time.sleep(min(remaining, soak_s))
        stop.set()
        pool.shutdown(wait=True)
        elapsed = time.perf_counter() - t_start
        c1, n1, _t1 = _hist_child_snapshot(
            REQUEST_HISTOGRAM, "volumeServer", "get")
        delta_counts = [b - a for a, b in zip(c0, c1)]
        p99 = _hist_quantile(
            list(REQUEST_HISTOGRAM.buckets), delta_counts, n1 - n0, 0.99)
        sealed = f"{target_vid}:seal" in transitions_done
        encoded = f"{target_vid}:ec_encode" in transitions_done
        vacuumed = any(k.endswith(":vacuum") for k in transitions_done)
        ok = (not errors and sealed and encoded and vacuumed
              and p99 <= slo_p99_s and counts["reads"] > 0)
        return {
            "soak_ok": bool(ok),
            "soak_seconds": round(elapsed, 1),
            "soak_reads": counts["reads"],
            "soak_writes": counts["writes"],
            "soak_read_p99_s": round(p99, 4),
            "soak_p99_slo_s": slo_p99_s,
            "soak_transitions": sorted(transitions_done),
            "soak_error_count": len(errors),
            "soak_errors": errors[:10],
            "soak_journal_states": master.lifecycle.journal.counts(),
        }
    finally:
        for v in vols:
            v.stop()
        master.stop()
        shutil.rmtree(tmp, ignore_errors=True)
        shutil.rmtree(journal_dir, ignore_errors=True)


def _cpu_rate(shard_bytes: int = 16 << 20, iters: int = 5) -> float:
    """Best single-pass rate: this shared vCPU sees multi-second steal
    spikes (observed swinging a mean-of-3 between 3.7 and 5.9 GB/s), so
    the min-latency pass is the codec's actual capability."""
    from seaweedfs_tpu.ops.rs_cpu import ReedSolomon

    rs = ReedSolomon()
    rng = np.random.default_rng(1)
    data = rng.integers(0, 256, (10, shard_bytes), dtype=np.uint8)
    rs.parity_of(data)  # warm
    best = float("inf")
    for _ in range(iters):
        start = time.perf_counter()
        rs.parity_of(data)
        best = min(best, time.perf_counter() - start)
    return (10 * shard_bytes) / best / 1e9


def _geo_rates() -> dict:
    """ISSUE 12: steady-state geo replication lag + throttled link
    throughput, two LIVE in-process clusters (master + volume + filer
    each) cross-linked active-active.

    Two phases:
      * steady state — paced small writes on A, per-object replication
        lag measured as time-to-visible on B (p50/p99 seconds behind);
      * burst — a batch of larger objects written at once, the link's
        measured MB/s compared against its token-bucket budget
        (SEAWEEDFS_TPU_BENCH_GEO_RATE_MBPS) while concurrent foreground
        reads on A must hold the soak read-p99 SLO.
    Byte-identity over the full key set gates the whole leg.
    """
    import os
    import shutil
    import socket
    import tempfile
    import threading
    import urllib.request

    from seaweedfs_tpu.filer.server import FilerServer
    from seaweedfs_tpu.master.server import MasterServer
    from seaweedfs_tpu.volume.server import VolumeServer

    rate_mbps = float(os.environ.get(
        "SEAWEEDFS_TPU_BENCH_GEO_RATE_MBPS", "2"))
    n_steady = int(os.environ.get("SEAWEEDFS_TPU_BENCH_GEO_OBJECTS", "80"))
    # burst sized to several times the bucket's 1s burst capacity, so
    # the measured link rate reflects the THROTTLE, not the free burst
    n_burst = int(os.environ.get("SEAWEEDFS_TPU_BENCH_GEO_BURST", "40"))
    burst_kb = int(os.environ.get("SEAWEEDFS_TPU_BENCH_GEO_BURST_KB",
                                  "128"))
    slo_p99_s = float(os.environ.get("SEAWEEDFS_TPU_SOAK_P99_S", "2.0"))

    reserved: set[int] = set()

    def _port() -> int:
        while True:
            with socket.socket() as s:
                s.bind(("127.0.0.1", 0))
                p = s.getsockname()[1]
            if (p <= 55000 and p not in reserved
                    and p + 10000 not in reserved):
                reserved.update((p, p + 10000))
                return p

    tmp = tempfile.mkdtemp(prefix="swfs-geo-")

    def _cluster(tag: str, cid: int):
        root = os.path.join(tmp, tag)
        os.makedirs(os.path.join(root, "vol"), exist_ok=True)
        m = MasterServer(ip="127.0.0.1", port=_port(),
                         volume_size_limit_mb=256)
        m.start()
        v = VolumeServer(directories=[os.path.join(root, "vol")],
                         ip="127.0.0.1", port=_port(),
                         master_addresses=[f"127.0.0.1:{m.grpc_port}"],
                         pulse_seconds=0.5, max_volume_count=16)
        v.start()
        f = FilerServer(masters=[f"127.0.0.1:{m.grpc_port}"],
                        ip="127.0.0.1", port=_port(), store="sqlite",
                        store_path=os.path.join(root, "filer.db"),
                        cluster_id=cid, geo_rate_mbps=rate_mbps)
        f.start()
        deadline = time.time() + 15
        while time.time() < deadline and len(m.topo.nodes) < 1:
            time.sleep(0.1)
        return m, v, f

    ma, va, fa = _cluster("a", 1)
    mb, vb, fb = _cluster("b", 2)
    from seaweedfs_tpu.replication.geo import GeoReplicator

    # cross-link AFTER both are up (same wiring -geoPeers does)
    ra = GeoReplicator(fa, f"127.0.0.1:{fb.port}",
                       journal_dir=os.path.join(tmp, "a", "geo"),
                       rate_mbps=rate_mbps)
    rb = GeoReplicator(fb, f"127.0.0.1:{fa.port}",
                       journal_dir=os.path.join(tmp, "b", "geo"),
                       rate_mbps=rate_mbps)
    fa.geo_replicators.append(ra)
    fb.geo_replicators.append(rb)
    ra.start()
    rb.start()

    def _put(f, path, data):
        req = urllib.request.Request(
            f"http://127.0.0.1:{f.port}{path}", data=data, method="PUT")
        with urllib.request.urlopen(req, timeout=30) as r:
            r.read()

    def _get(f, path):
        with urllib.request.urlopen(
                f"http://127.0.0.1:{f.port}{path}", timeout=30) as r:
            return r.read()

    def _visible(f, path, want, timeout_s=60.0) -> float:
        t0 = time.perf_counter()
        while time.perf_counter() - t0 < timeout_s:
            try:
                if _get(f, path) == want:
                    return time.perf_counter() - t0
            except Exception:
                pass
            time.sleep(0.004)
        raise TimeoutError(path)

    from seaweedfs_tpu.stats.metrics import REGISTRY

    def _geo_bytes() -> float:
        fam = REGISTRY.family("seaweedfs_geo_bytes_total")
        if fam is None:
            return 0.0
        return sum(float(line.rsplit(" ", 1)[1])
                   for line in fam.render() if not line.startswith("#"))

    objects: dict[str, bytes] = {}
    try:
        # -- steady state: per-object replication lag ----------------------
        lags = []
        for i in range(n_steady):
            key = f"/buckets/geo/s-{i}.bin"
            blob = os.urandom(2048)
            _put(fa, key, blob)
            lags.append(_visible(fb, key, blob))
            objects[key] = blob
        lags.sort()
        lag_p50 = lags[len(lags) // 2]
        lag_p99 = lags[min(len(lags) - 1, int(len(lags) * 0.99))]

        # -- burst under the token bucket + foreground reads ---------------
        bytes_before = _geo_bytes()
        read_lat: list[float] = []
        stop_reads = threading.Event()

        def _reader():
            keys = list(objects)
            i = 0
            while not stop_reads.is_set():
                t0 = time.perf_counter()
                try:
                    _get(fa, keys[i % len(keys)])
                    read_lat.append(time.perf_counter() - t0)
                except Exception:
                    read_lat.append(float("inf"))
                i += 1
                time.sleep(0.01)

        rt = threading.Thread(target=_reader, daemon=True)
        rt.start()
        t0 = time.perf_counter()
        burst: list[tuple[str, bytes]] = []
        for i in range(n_burst):
            key = f"/buckets/geo/burst-{i}.bin"
            blob = os.urandom(burst_kb << 10)
            _put(fa, key, blob)
            objects[key] = blob
            burst.append((key, blob))
        for key, blob in burst:
            _visible(fb, key, blob, timeout_s=300.0)
        burst_s = time.perf_counter() - t0
        stop_reads.set()
        rt.join(timeout=5)
        link_bytes = _geo_bytes() - bytes_before
        link_mbps = link_bytes / burst_s / (1 << 20)
        read_lat.sort()
        read_p99 = (read_lat[int(len(read_lat) * 0.99)]
                    if read_lat else 0.0)

        # -- full-scan byte identity ---------------------------------------
        identical = all(_get(fb, k) == v for k, v in objects.items())
        # the A->B link must not beat ~2x its budget (the 1s bucket
        # burst capacity makes 2x the honest bound, same as scrub); the
        # B->A link ships nothing here (it only sees origin-1-signed
        # applies, which it skips), so the shared-registry sum is A->B
        bounded = link_mbps <= 2.0 * rate_mbps
        return {
            "geo_objects": len(objects),
            "geo_lag_p50_s": round(lag_p50, 4),
            "geo_lag_p99_s": round(lag_p99, 4),
            "geo_burst_MB": round(link_bytes / (1 << 20), 3),
            "geo_burst_seconds": round(burst_s, 2),
            "geo_link_MBps": round(link_mbps, 3),
            "geo_rate_MBps": rate_mbps,
            "geo_bounded": bool(bounded),
            "geo_read_p99_s": round(read_p99, 4),
            "geo_read_p99_ok": bool(read_p99 <= slo_p99_s),
            "geo_byte_identical": bool(identical),
            "geo_ok": bool(identical and bounded
                           and read_p99 <= slo_p99_s),
        }
    finally:
        for srv in (ra, rb):
            srv.stop()
        for srv in (fa, fb, va, vb, ma, mb):
            try:
                srv.stop()
            except Exception:
                pass
        shutil.rmtree(tmp, ignore_errors=True)


def _stage_in_subprocess(
    flag: str, timeout_s: float, attempts: int = 3, backoff_s: float = 15.0,
    env_per_attempt: list[dict] | None = None,
) -> dict:
    """Run one device-touching bench stage in a worker process, retried.

    A chip belongs to one process at a time, so the parent that calls this
    must never have touched a jax backend itself: each stage child runs
    alone and exits before the next starts.  A thread can't be killed, a
    subprocess can — so every device stage lives behind this bounded retry
    loop.  `env_per_attempt[i]` overlays the environment of attempt i
    (e.g. halving the kernel buffer after a timeout instead of re-running
    the identical shape).
    """
    import os
    import subprocess
    import sys

    def _scan_lines(
        stdout: str | bytes | None,
    ) -> tuple[dict | None, dict | None]:
        """-> (latest rate-bearing JSON line, latest parseable JSON line).
        Partial lines count — that is the whole salvage contract.  The
        final line decides success (a stage that catches an exception
        prints {"error":...} LAST, with rc 0 — earlier measured partials
        must not mask that)."""
        if not stdout:
            return None, None
        if isinstance(stdout, bytes):
            stdout = stdout.decode("utf-8", errors="replace")
        best = final = None
        for line in reversed(stdout.strip().splitlines()):
            try:
                parsed = json.loads(line)
            except (json.JSONDecodeError, ValueError):
                continue
            if not isinstance(parsed, dict):
                continue
            if final is None:
                final = parsed
            if "error" not in parsed and any(
                k in parsed for k in ("rate", "e2e_rate", "devices")
            ):
                best = parsed
                break
        return best, final

    def _has_rate(parsed: dict | None) -> bool:
        return bool(parsed) and "error" not in parsed and any(
            k in parsed for k in ("rate", "e2e_rate", "devices"))

    last = "no attempt ran"
    crash_salvage: dict | None = None  # best partial from a crashed attempt
    for attempt in range(attempts):
        if attempt:
            time.sleep(backoff_s)
        env = dict(os.environ)
        if env_per_attempt and attempt < len(env_per_attempt):
            env.update(env_per_attempt[attempt])
        try:
            proc = subprocess.run(
                [sys.executable, os.path.abspath(__file__), flag],
                capture_output=True,
                text=True,
                timeout=timeout_s,
                env=env,
            )
        except subprocess.TimeoutExpired as exc:
            # the stage hung — salvage whatever partial measurements it
            # printed before we killed it
            best, _ = _scan_lines(exc.stdout)
            if _has_rate(best):
                best["timeout_salvaged"] = True
                return best
            last = f"{flag} timed out after {timeout_s:.0f}s"
            continue
        best, final = _scan_lines(proc.stdout)
        if (proc.returncode == 0 and final is not None
                and "error" not in final):
            return best if best is not None else final
        # crashed or error'd attempt: keep the best rate-bearing partial as
        # a last resort, but DO retry — the retry overlays (smaller
        # buffers) exist for exactly this case
        if _has_rate(best):
            if crash_salvage is None or best.get(
                    "rate", best.get("e2e_rate", 0)) >= crash_salvage.get(
                    "rate", crash_salvage.get("e2e_rate", 0)):
                crash_salvage = best
        if final is not None and "error" in final:
            last = final["error"]
        else:
            last = f"{flag} rc={proc.returncode}: {proc.stderr[-300:]}"
    if crash_salvage is not None:
        crash_salvage["crash_salvaged"] = True
        crash_salvage["crash_error"] = last[:300]
        return crash_salvage
    return {"error": last}


def _run_stage(fn, failure_extra: dict) -> None:
    """Print one stage's JSON line.  A stage that raises prints its error
    AND exits 1: a caught exception never leaves the exit code at 0."""
    import sys

    try:
        print(json.dumps(fn()))
    except Exception as exc:  # noqa: BLE001 — must emit parseable JSON
        print(json.dumps({
            **failure_extra, "error": f"{type(exc).__name__}: {exc}"[:500]}))
        sys.exit(1)


def main() -> None:
    import sys

    snapshot = "--metrics-snapshot" in sys.argv
    # flag(s) -> (stage, keys added to its failure line)
    stages = (
        (("--e2e-only",), _e2e_rates, {}),
        (("--e2e-cpu-only",), lambda: _e2e_rates(codec_name="cpu"), {}),
        (("--soak-only", "--soak"), _soak_rates, {"soak_ok": False}),
        (("--service-only", "--service"), _service_rates, {}),
        (("--degraded-only",), _degraded_read_rate, {}),
        (("--rebuild-only",), _rebuild_only_rates, {}),
        (("--geo-only",), _geo_rates, {"geo_ok": False}),
        (("--mass-repair-only", "--mass-repair"), _mass_repair_rates, {}),
        (("--serving-only",), _serving_rates, {}),
        (("--serving-smoke-only",), _serving_smoke,
         {"serving_smoke_ok": False}),
        (("--smallfile-only",),
         lambda: _smallfile_rates(metrics_snapshot=snapshot), {}),
        (("--flight-overhead-only",), _flight_overhead,
         {"flight_overhead_ok": False}),
        (("--kernel-only",), _tpu_pallas_rate, {}),
    )
    for flags, fn, failure_extra in stages:
        if any(f in sys.argv for f in flags):
            return _run_stage(fn, failure_extra)

    import os

    cpu = _cpu_rate()
    # stage subprocess timeout, env-configurable
    stage_timeout = float(os.environ.get(
        "SEAWEEDFS_TPU_BENCH_STAGE_TIMEOUT_S", "300"))
    # this parent never touches a jax backend: every device stage below
    # is a child that holds the chip alone and exits.  There is no probe
    # and no skip — a stage that cannot reach an accelerator fails, and
    # the failure fails the run.
    failed: list[str] = []
    tpu = _stage_in_subprocess(
        "--kernel-only", timeout_s=stage_timeout, attempts=3,
        env_per_attempt=[  # shrink the stage set on each retry: the
            # caps map to DISTINCT subsets of the fixed 4/16/64/256
            # stages ({4,16,64,256} -> {4,16} -> {4})
            {},
            {"SEAWEEDFS_TPU_BENCH_KERNEL_MB": "16"},
            {"SEAWEEDFS_TPU_BENCH_KERNEL_MB": "4"},
        ])
    # e2e runs BOTH codecs and reports the faster one — the framework's
    # `-ec.codec=auto` makes the same call at runtime.  The loser's rate
    # is preserved alongside.
    tpu_e2e = _stage_in_subprocess(
        "--e2e-only", timeout_s=stage_timeout, attempts=2)
    if "e2e_rate" not in tpu_e2e:
        failed.append("e2e")
    cpu_e2e = _stage_in_subprocess("--e2e-cpu-only",
                                   timeout_s=stage_timeout * 1.8,
                                   attempts=1)
    candidates = [c for c in (tpu_e2e, cpu_e2e) if "e2e_rate" in c]
    if candidates:
        e2e = max(candidates, key=lambda c: c["e2e_rate"])
        other = cpu_e2e if e2e is tpu_e2e else tpu_e2e
        if "e2e_rate" in other:
            e2e[f"{other.get('impl', 'other')}_e2e_GBps"] = round(
                other["e2e_rate"], 4)
            if "rebuild_rate" in other:
                e2e[f"{other.get('impl', 'other')}_rebuild_GBps"] = round(
                    other["rebuild_rate"], 4)
        else:
            loser = "tpu" if other is tpu_e2e else "cpu"
            e2e[f"{loser}_e2e_error"] = (
                other.get("error") or "stage yielded no measured rate"
            )[:300]
    else:
        e2e = tpu_e2e
    if "rate" in tpu:
        out = {
            "metric": "ec_encode_GBps",
            "value": round(tpu["rate"], 2),
            "unit": "GB/s",
            "vs_baseline": round(tpu["rate"] / cpu, 1) if cpu else None,
            "impl": "pallas_swar_u32",
            "cpu_simd_GBps": round(cpu, 3),
            "sweep_bytes": tpu["bytes"],
            "seconds": round(tpu["seconds"], 4),
        }
        for k in ("sweep_mb_per_shard", "put_GBps", "timeout_salvaged"):
            if k in tpu:
                out[f"kernel_{k}" if k == "timeout_salvaged" else k] = tpu[k]
    else:
        # no device number: say so and FAIL — the host codec's rate stays
        # under its own name (cpu_simd_GBps), never the headline's
        failed.append("kernel")
        out = {
            "metric": "ec_encode_GBps",
            "value": None,
            "unit": "GB/s",
            "impl": "pallas_swar_u32",
            "cpu_simd_GBps": round(cpu, 3),
            "error": (tpu.get("error") or "unknown")[:500],
        }
    if "e2e_rate" in e2e:
        out["ec_encode_e2e_GBps"] = round(e2e["e2e_rate"], 2)
        out["e2e_impl"] = e2e.get("impl", "tpu")
        out["e2e_bytes"] = e2e.get("e2e_bytes")
        if "e2e_seconds" in e2e:
            out["e2e_seconds"] = round(e2e["e2e_seconds"], 2)
        if "rebuild_rate" in e2e:
            out["ec_rebuild_GBps"] = round(e2e["rebuild_rate"], 2)
            if "rebuild_seconds" in e2e:
                out["rebuild_seconds"] = round(e2e["rebuild_seconds"], 2)
        for k in ("timeout_salvaged", "tpu_e2e_error", "cpu_e2e_error",
                  "warm_seconds", "e2e_fsync_rate",
                  "e2e_trials"):
            if k in e2e:
                out[k] = e2e[k]
        for k, v in e2e.items():  # the losing codec's rates
            if k.endswith("_GBps"):
                out[k] = v
    else:
        out["e2e_error"] = (e2e.get("error") or "unknown")[:300]
    def phase(name: str, fn) -> None:
        """Merge one later phase's keys; its failure lands under
        `<name>_error` AND fails the run (exit code 1 at the end)."""
        try:
            res = fn()
            if "error" in res:
                raise RuntimeError(res.pop("error"))
            out.update(res)
        except Exception as exc:  # noqa: BLE001
            failed.append(name)
            out[f"{name}_error"] = f"{type(exc).__name__}: {exc}"[:300]

    # BASELINE config 5: concurrent degraded reads (pure host path, no
    # device dispatch — cheap and deterministic, so no subprocess guard)
    phase("degraded", _degraded_read_rate)
    # the reference's ONLY published numbers: 1KB files at c=16 through
    # the full HTTP path (README.md:514-567) — measured on the same host
    phase("smallfile", lambda: _smallfile_rates(metrics_snapshot=snapshot))
    # ISSUE 20: flight-recorder overhead A/B (continuous profiler +
    # hot-key sketch on vs off) — subprocess-guarded because the legs
    # flip process-global kill switches
    if snapshot:
        phase("flight_overhead", lambda: _stage_in_subprocess(
            "--flight-overhead-only", timeout_s=stage_timeout, attempts=1))
    # ISSUE 18: serving-plane legs (fsync batching A/B, sendfile A/B,
    # thousands-of-sockets keep-alive) — subprocess-guarded: the
    # keep-alive leg lifts RLIMIT_NOFILE and parks ~2000 sockets
    phase("serving", lambda: _stage_in_subprocess(
        "--serving-only", timeout_s=stage_timeout, attempts=1))
    # ISSUE 6: codec-service batching vs per-volume dispatch (host SIMD,
    # in-process, deterministic — no subprocess guard needed)
    phase("service", _service_rates)
    # ISSUE 12: cross-cluster replication lag + throttled link throughput
    # (opt-in with --geo: spins two full clusters in-process)
    if "--geo" in sys.argv:
        phase("geo", _geo_rates)
    if failed:
        out["failed"] = failed
    print(json.dumps(out))
    if failed:
        sys.exit(1)


if __name__ == "__main__":
    main()
