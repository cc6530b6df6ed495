"""ctypes loader for the C++ native library (CRC32C, GF(2^8) SIMD codec).

The native byte-path mirrors the reference's use of SIMD for CRC32C and GF
arithmetic (klauspost/crc32, klauspost/reedsolomon).  Built on demand by
``build.py``; every caller must tolerate ``available() == False`` and fall
back to numpy.
"""

from __future__ import annotations

import ctypes
import os
import threading

_lock = threading.Lock()
_lib: ctypes.CDLL | None = None
_tried = False


def _host_simd_tier() -> int:
    """Best sw_gf_impl tier this host can run: 3 interleaved GFNI+AVX512,
    1 SSSE3, 0 scalar — the heal target for stale/portable builds."""
    try:
        with open("/proc/cpuinfo") as f:
            flags = f.read()
    except OSError:
        return 0
    if "gfni" in flags and "avx512bw" in flags and "avx512f" in flags:
        return 3
    if "ssse3" in flags:
        return 1
    return 0


def _load() -> ctypes.CDLL | None:
    global _lib, _tried
    if _tried:  # lock-free fast path: GIL-atomic read of a settled state
        return _lib
    with _lock:
        if _tried:
            return _lib
        _tried = True
        try:
            from . import build

            path = build.build()  # no-op when this host's object exists
        except Exception:
            return None
        try:
            lib = ctypes.CDLL(path)
        except OSError:
            return None
        # self-heal a stale/portable build: a lib without sw_gf_impl, or
        # one reporting the scalar path on an SSE-capable x86 host, was
        # compiled before the SIMD kernels (or with a failed
        # -march=native) — rebuild once and reload.  This exact staleness
        # silently cost 4x codec throughput for three rounds.
        try:
            impl = lib.sw_gf_impl()
        except AttributeError:
            impl = -1
        if impl < _host_simd_tier():
            try:
                import shutil
                import tempfile

                from . import build

                path = build.build(force=True)
                # dlopen caches the old mapping for the original path in
                # this process; load the healed build via a unique copy
                fd, fresh = tempfile.mkstemp(suffix=".so")
                os.close(fd)
                try:
                    shutil.copy(path, fresh)
                    lib = ctypes.CDLL(fresh)
                finally:
                    try:
                        os.unlink(fresh)  # mapping stays valid
                    except OSError:
                        pass
            except Exception:
                try:
                    lib = ctypes.CDLL(path)
                except OSError:
                    return None
        lib.sw_crc32c_update.restype = ctypes.c_uint32
        lib.sw_crc32c_update.argtypes = [
            ctypes.c_uint32,
            ctypes.c_char_p,
            ctypes.c_size_t,
        ]
        lib.sw_gf_apply.restype = None
        lib.sw_gf_apply.argtypes = [
            ctypes.c_char_p,  # matrix rows (R*S bytes)
            ctypes.c_int,  # R
            ctypes.c_int,  # S
            # raw-address arrays (c_void_p): callers fill them from
            # ndarray.ctypes.data without per-pointer c_char_p casts
            ctypes.POINTER(ctypes.c_void_p),  # inputs
            ctypes.POINTER(ctypes.c_void_p),  # outputs
            ctypes.c_size_t,  # block len
        ]
        _lib = lib
        return _lib


def available() -> bool:
    return _load() is not None


def crc32c_update(crc: int, data: bytes) -> int:
    lib = _load()
    assert lib is not None
    return int(lib.sw_crc32c_update(crc, data, len(data)))


def gf_apply(matrix_rows, inputs: list[bytes], out_count: int) -> list[bytearray]:
    """Apply (R,S) GF matrix to S equal-length buffers -> R buffers.

    ``inputs`` entries must be bytes objects; they are passed by pointer
    (ctypes does not copy bytes for c_char_p), so this is zero-copy in.
    """
    lib = _load()
    assert lib is not None
    import numpy as np

    m = np.ascontiguousarray(matrix_rows, dtype=np.uint8)
    r, s = m.shape
    if r != out_count:
        raise ValueError(f"matrix has {r} rows, caller expected {out_count}")
    if len(inputs) != s:
        raise ValueError(f"matrix has {s} cols, got {len(inputs)} inputs")
    n = len(inputs[0])
    outs = [bytearray(n) for _ in range(r)]
    # zero-copy in: the void* values point into the caller's bytes
    # objects, which `inputs` keeps alive across the call
    in_ptrs = (ctypes.c_void_p * s)(
        *[ctypes.cast(ctypes.c_char_p(b), ctypes.c_void_p) for b in inputs])
    out_bufs = [(ctypes.c_char * n).from_buffer(o) for o in outs]
    out_ptrs = (ctypes.c_void_p * r)(
        *[ctypes.addressof(ob) for ob in out_bufs])
    lib.sw_gf_apply(m.tobytes(), r, s, in_ptrs, out_ptrs, n)
    return outs


def gf_apply_fast(mbytes: bytes, r: int, s: int, inputs, outs, n: int) -> None:
    """Minimal-overhead GF matmul: prevalidated caller, prebuilt matrix
    bytes, raw ndarray pointers straight into the C kernel.

    The codec service's per-job hot path: ``gf_apply_arrays`` spends
    ~15-20us/call on list building, ascontiguousarray checks and matrix
    tobytes — more than the kernel itself below ~64KB.  Here the CALLER
    guarantees: ``inputs``/``outs`` are C-contiguous uint8 rows of length
    ``n``, ``mbytes`` is the (r, s) matrix's raw bytes.  No checks.
    """
    lib = _load()
    in_ptrs = (ctypes.c_void_p * s)(*[a.ctypes.data for a in inputs])
    out_ptrs = (ctypes.c_void_p * r)(*[o.ctypes.data for o in outs])
    lib.sw_gf_apply(mbytes, r, s, in_ptrs, out_ptrs, n)


def gf_apply_arrays(matrix_rows, inputs, out=None):
    """Zero-copy variant of gf_apply over numpy uint8 arrays.

    `inputs` are 1-D contiguous uint8 arrays of equal length (validated);
    returns a list of fresh uint8 arrays (or fills `out` when given).
    Pointers are passed straight to the C kernel — no tobytes copies.
    """
    lib = _load()
    assert lib is not None
    import numpy as np

    m = np.ascontiguousarray(matrix_rows, dtype=np.uint8)
    r, s = m.shape
    if len(inputs) != s:
        raise ValueError(f"matrix has {s} cols, got {len(inputs)} inputs")
    n = len(inputs[0])
    arrs = []
    for x in inputs:
        a = np.ascontiguousarray(x, dtype=np.uint8)
        if a.ndim != 1 or len(a) != n:
            raise ValueError("inputs must be equal-length 1-D u8 arrays")
        arrs.append(a)
    if out is None:
        out = [np.empty(n, dtype=np.uint8) for _ in range(r)]
    # void* arrays filled with raw addresses: building c_char_p casts per
    # pointer costs ~100us/call, which dominates small degraded-read
    # decodes (the per-needle latency path calls this per interval)
    in_ptrs = (ctypes.c_void_p * s)(*[a.ctypes.data for a in arrs])
    out_ptrs = (ctypes.c_void_p * r)(*[o.ctypes.data for o in out])
    lib.sw_gf_apply(m.tobytes(), r, s, in_ptrs, out_ptrs, n)
    return out
