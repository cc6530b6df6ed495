"""On-demand g++ build of the native library.

No pip/apt dependencies: a single translation unit compiled straight to a
shared object next to this file.  Callers treat failure as 'native
unavailable' and fall back to numpy.
"""

from __future__ import annotations

import hashlib
import os
import subprocess

_DIR = os.path.dirname(__file__)
_SRC = os.path.join(_DIR, "seaweed_native.cc")
_FLAGS = ["-O3", "-shared", "-fPIC", "-std=c++17", "-march=native"]


def _cpu_flags() -> str:
    """This host's CPU feature flags — what ``-march=native`` compiles to."""
    try:
        with open("/proc/cpuinfo") as f:
            for line in f:
                if line.startswith(("flags", "Features")):
                    return line
    except OSError:
        pass
    return ""


def so_path() -> str:
    """Where THIS host's build lives.  The object is keyed by a hash of
    source + compiler flags + host CPU flags: a tree copied to a machine
    with another CPU (the object is ``-march=native``) or an edited
    source names a different file and rebuilds on first use, instead of
    trusting an mtime and dying on an illegal instruction."""
    h = hashlib.sha256()
    with open(_SRC, "rb") as f:
        h.update(f.read())
    h.update(" ".join(_FLAGS).encode())
    h.update(_cpu_flags().encode())
    return os.path.join(_DIR, f"libseaweed_native.{h.hexdigest()[:16]}.so")


def build(force: bool = False) -> str:
    out = so_path()
    if not force and os.path.exists(out):
        return out
    # compile to a process-unique temp path, then atomically rename: a
    # concurrent process never dlopens a half-written .so
    tmp = f"{out}.{os.getpid()}.tmp"
    cmd = ["g++", *_FLAGS, _SRC, "-o", tmp]
    try:
        subprocess.run(cmd, check=True, capture_output=True, timeout=120)
    except (subprocess.CalledProcessError, FileNotFoundError, subprocess.TimeoutExpired) as e:
        # retry without -march=native (portable baseline) — but say so:
        # a silent scalar build costs ~4x codec throughput on SIMD hosts
        import sys

        detail = getattr(e, "stderr", b"") or b""
        print("seaweedfs_tpu native: -march=native build failed, falling "
              f"back to portable scalar codec: {detail[-300:]!r}",
              file=sys.stderr)
        flags = _cpu_flags()
        extra = [opt for feat, opt in (("ssse3", "-mssse3"),
                                       ("sse4_2", "-msse4.2"))
                 if feat in flags]
        cmd = (["g++", "-O2", "-shared", "-fPIC", "-std=c++17"] + extra +
               [_SRC, "-o", tmp])
        subprocess.run(cmd, check=True, capture_output=True, timeout=120)
    os.replace(tmp, out)
    return out


if __name__ == "__main__":
    print(build(force=True))
