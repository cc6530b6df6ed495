"""AOT compiles for a DESCRIBED TPU v5e (no chip attached, nothing runs):
what the chip's compiler refuses — a kernel Mosaic cannot tile, a program
whose temps do not fit HBM, a compile that takes a minute — fails here,
at no chip time.  See /opt/skills/guides/on-chip-measurement section 2.

All in ONE file, topology described inside a module-scoped fixture (only
one process at a time may load libtpu; under pytest-xdist only the worker
that is handed this file does), compile cache off around them (an entry
written for an unattached chip cannot be read back and would warn).
"""

import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from seaweedfs_tpu.ops import gf256
from seaweedfs_tpu.ops.rs_jax import _rows_of

HBM_BYTES = 16 << 30  # one TPU v5e chip
MIB = 1 << 20


@pytest.fixture(scope="module")
def topo():
    import os

    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        desc = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compile(fn, *shapes):
    t0 = time.perf_counter()
    compiled = fn.lower(*shapes).compile()
    return compiled, time.perf_counter() - t0


def _decode_rows(lost):
    """The survivor->lost decode plan _reconstruct builds for `lost`."""
    present = [i for i in range(14) if i not in lost]
    return gf256.decode_plan_for(
        gf256.rs_matrix(10, 14), 10, present, tuple(lost))


@pytest.mark.parametrize("what,shard_mib", [
    ("parity", 1), ("parity", 64), ("decode1", 64), ("decode4", 64)])
def test_pallas_kernels_compile_for_v5e(one_chip, what, shard_mib):
    """The entry every Pallas caller now takes (host-packed uint32 lane
    tiles: parity_of / encode_device / apply_rows_device / _reconstruct):
    a Mosaic custom call, no HBM temp to speak of, compiled in seconds —
    the old uint8 entry took 64x the input in temps and 80 s for the 1x10
    decode matrix."""
    from seaweedfs_tpu.ops.rs_pallas import LANES, _make_apply_pallas

    rows = {"parity": gf256.rs_parity_matrix(10, 4),
            "decode1": _decode_rows([3]),
            "decode4": _decode_rows([0, 2, 5, 9])}[what]
    fn = _make_apply_pallas(_rows_of(rows), False).as_u32_3d  # compiled
    d3 = jax.ShapeDtypeStruct(
        (10, shard_mib * MIB // (LANES * 4), LANES), jnp.uint32,
        sharding=one_chip)
    compiled, seconds = _compile(fn, d3)
    assert "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < 2 * mem.argument_size_in_bytes
    assert mem.argument_size_in_bytes == 10 * shard_mib * MIB
    assert mem.output_size_in_bytes == len(rows) * shard_mib * MIB
    assert seconds < 30, f"{what} took {seconds:.0f}s to compile"


def _jobs_program(mesh, rows, n, width):
    """(the service's program over n jobs, its n argument shapes: uint32
    lane tiles, (10, 32768, 128) at the encoder's slice width)"""
    from seaweedfs_tpu.parallel.mesh import (
        _lane_tile_shape,
        _sharded_apply_jobs,
    )

    job = jax.ShapeDtypeStruct(
        _lane_tile_shape(mesh, (10, width)), jnp.uint32,
        sharding=NamedSharding(mesh, P(None, mesh.axis_names, None)))
    return _sharded_apply_jobs(mesh, _rows_of(rows), n), [job] * n


@pytest.mark.parametrize("what", ["parity", "decode4"])
def test_service_batch_program_fits_hbm_twice(topo, what):
    """The codec service's device program (the XOR network over each
    job's array) for one DEFAULT_SLICE job, (10, 16 MiB), must fit the
    chip twice over: the scheduler keeps two batches in flight."""
    from seaweedfs_tpu.ops.codec_service import CodecService
    from seaweedfs_tpu.storage.ec.encoder import DEFAULT_SLICE

    mesh = Mesh(np.asarray(topo.devices[:1]).reshape(1, 1), ("dp", "sp"))
    rows = (gf256.rs_parity_matrix(10, 4) if what == "parity"
            else _decode_rows([0, 2, 5, 9]))
    w_pad = CodecService._pad_width(DEFAULT_SLICE, 1)
    assert w_pad == DEFAULT_SLICE  # slices are already a bucket
    fn, jobs = _jobs_program(mesh, rows, 1, w_pad)
    compiled, seconds = _compile(fn, *jobs)
    mem = compiled.memory_analysis()
    live = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes)
    assert 2 * live < HBM_BYTES, f"{live / 2**30:.1f} GiB per batch"
    assert seconds < 60


@pytest.mark.parametrize("chips,v", [(1, 1), (1, 8), (4, 1), (4, 8), (4, 16)])
def test_batches_up_to_the_cap_fit_hbm_twice(topo, chips, v):
    """Batches up to the largest CodecService._device_max_volumes lets
    through at the encoder's slice width — eight volumes' slices on one
    chip, sixteen (the job cap) over four — fit each chip by the compiler's
    own count: two batches' arrays resident and one program's temporaries,
    inside the share the cap is derived from, and no more than the cap
    itself counts for them.  The two ratios the cap is computed from are
    upper bounds of the compiler's: measured for the uint8 program of
    before (2.01 resident, 12.4 = 1,984 MiB of temporaries), where the
    lane-tile program keeps 1.40 (nothing pads) and 7.1 (1,136 MiB on one
    chip at V = 1-8).  Over four chips the result is gathered (ISSUE 38):
    a chip holds a quarter of every input and the WHOLE of every result,
    and the gather's own copy of the results is a temporary."""
    from seaweedfs_tpu.ops import codec_service as cs
    from seaweedfs_tpu.storage.ec.encoder import DEFAULT_SLICE

    mesh = Mesh(np.asarray(topo.devices[:chips]).reshape(1, chips),
                ("dp", "sp"))
    svc = cs.CodecService(mode="device", codec_name="tpu_xor", mesh=mesh)
    svc._device_bytes = int(cs._HBM_SHARE * chips * HBM_BYTES)
    job_bytes = 10 * DEFAULT_SLICE
    cap = svc._device_max_volumes(job_bytes)
    assert 1 << (cap.bit_length() - 1) == {1: 8, 4: 16}[chips] >= v
    fn, jobs = _jobs_program(mesh, gf256.rs_parity_matrix(10, 4), v,
                             DEFAULT_SLICE)
    assert jobs[0].shape == (10, 32768, 128)
    compiled, seconds = _compile(fn, *jobs)
    mem = compiled.memory_analysis()  # per device
    resident = mem.argument_size_in_bytes + mem.output_size_in_bytes
    held = 2 * resident + mem.temp_size_in_bytes
    assert held <= cs._HBM_SHARE * HBM_BYTES
    per_job_byte = job_bytes / chips
    # what _device_max_volumes counts for this batch on one device
    assert held <= per_job_byte * (
        cs._HBM_TEMP_PER_JOB_BYTE + 2 * v * svc._resident_per_job_byte())
    # a device's share of a job's ten rows in, four rows out: its share on
    # one chip, all of them on a mesh
    assert mem.output_size_in_bytes == v * 4 * DEFAULT_SLICE
    assert resident / v / per_job_byte == pytest.approx(
        1 + 0.4 * chips, abs=0.01)
    assert 1.4 < cs._HBM_RESIDENT_PER_JOB_BYTE
    gathered = (chips > 1) * mem.output_size_in_bytes
    temps = (mem.temp_size_in_bytes - gathered) / per_job_byte
    assert temps < 8 < cs._HBM_TEMP_PER_JOB_BYTE, (
        f"{mem.temp_size_in_bytes / MIB:.0f} MiB of temporaries")
    if chips == 1:
        assert 4 < temps
    else:
        assert "all-gather" in compiled.as_text()
    assert seconds < 90
    svc.close()


@pytest.mark.parametrize("chips", [1, 4])
def test_read_programs_compile_at_every_read_bucket(topo, chips):
    """What `CodecService.warm_reads` compiles when a volume loses
    shards: its decode plan (4 x 10 with four lost) over one block at each
    of the four read buckets, on one chip and column-split over four.
    Small and quick: a read is latency-bound."""
    from seaweedfs_tpu.ops import codec_service as cs

    mesh = Mesh(np.asarray(topo.devices[:chips]).reshape(1, chips),
                ("dp", "sp"))
    row = _decode_rows([0, 1, 2, 3])
    for width in cs._READ_BUCKETS:
        fn, jobs = _jobs_program(mesh, row, 1, width)
        compiled, seconds = _compile(fn, *jobs)
        mem = compiled.memory_analysis()  # per device
        assert mem.output_size_in_bytes == 4 * width  # gathered on four
        assert mem.temp_size_in_bytes < 16 * 10 * width // chips
        assert seconds < 30


def test_service_batch_holds_one_width_bucket():
    """A device batch is one program over jobs of ONE width bucket: a wide
    job between narrow ones waits its turn, so nothing is padded beyond
    its own bucket, and a head job over the cap still goes, alone."""
    from seaweedfs_tpu.ops.codec_service import CodecService

    svc = CodecService(mode="device", max_batch=16)
    svc._device_bytes = 64 << 20  # what _device_max_volumes divides up
    rng = np.random.default_rng(0)
    datas = [rng.integers(0, 256, (10, w), dtype=np.uint8)
             for w in [1 << 10] + [5 << 20] + [1 << 10] * 14]
    with svc._cond:
        for d in datas:
            svc._q.append(_job(svc, d))
        batch, reason = svc._collect_locked()
        assert [j.width for j in batch] == [1 << 10] * 8  # a power of two
        assert svc._q[0].width == 5 << 20 and len(svc._q) == 8
        wide, reason = svc._collect_locked()
        # 80 MiB padded: more than the devices hold, and it still goes, alone
        assert [j.width for j in wide] == [5 << 20]
    svc._q.clear()
    svc.close()


def _job(svc, data):
    from seaweedfs_tpu.ops.codec_service import _Job

    return _Job("parity", svc._parity_key, svc.parity_matrix, data,
                data.shape[1], None)


def test_distributed_reconstruct_compiles_with_all_reduce(topo):
    """The 2x2-mesh psum decode at a DEFAULT_SLICE-wide rebuild step: the
    compiler must put an all-reduce over dp in, and it must fit."""
    from seaweedfs_tpu.parallel.mesh import _reconstruct_program, make_mesh
    from seaweedfs_tpu.storage.ec.encoder import DEFAULT_SLICE

    mesh = make_mesh(topo.devices)
    assert dict(mesh.shape) == {"dp": 2, "sp": 2}
    a = jax.ShapeDtypeStruct(
        (10, 32, 8), jnp.int8,
        sharding=NamedSharding(mesh, P("dp", None, None)))
    x = jax.ShapeDtypeStruct(
        (10, DEFAULT_SLICE), jnp.uint8,
        sharding=NamedSharding(mesh, P("dp", "sp")))
    compiled, seconds = _compile(_reconstruct_program(mesh), a, x)
    assert "all-reduce" in compiled.as_text()
    mem = compiled.memory_analysis()
    live = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes)
    assert live < HBM_BYTES, f"{live / 2**30:.1f} GiB per device"
    assert seconds < 60
