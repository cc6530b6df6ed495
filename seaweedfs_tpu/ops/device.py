"""The accelerator THIS process holds, asked of its own JAX runtime.

A chip belongs to one process at a time, so the process that will run
the GF work is the only one that can say whether it has a device: there
is no out-of-process probe, no deadline and no fallback here.  A backend
that cannot initialise raises from ``held_device`` — at server start for
a device codec, or inside the rpc that first needs it — and the caller
fails instead of quietly running the host codec.

Also home of the persistent compile cache switch: every process that
will use the device calls ``enable_compile_cache`` once before its first
compile, so a restarted server does not pay every XLA/Mosaic compile
again.
"""

from __future__ import annotations

import os
import threading

# <checkout>/.jax_compile_cache — a FIXED path (the directory is part of
# the cache key, so one built from a temp name, pid or time never hits)
_DEFAULT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_compile_cache")

_lock = threading.Lock()
_cache_dir: "str | None" = None
_stats = {"listening": False, "requests": 0, "hits": 0,
          "compile_seconds": 0.0}


def _on_event(event: str, **_kw) -> None:
    if event == "/jax/compilation_cache/compile_requests_use_cache":
        _stats["requests"] += 1
    elif event == "/jax/compilation_cache/cache_hits":
        _stats["hits"] += 1


def _on_duration(event: str, seconds: float, **_kw) -> None:
    if event == "/jax/core/compile/backend_compile_duration":
        _stats["compile_seconds"] += seconds


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache; -> the directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set JAX reads it itself and no
    directory is set in code.  Otherwise the cache lives at a fixed,
    git-ignored path inside the checkout.  Idempotent; also starts the
    hit/miss/compile-seconds counters ``compile_cache_stats`` reports.
    """
    global _cache_dir
    with _lock:
        if _cache_dir is not None:
            return _cache_dir
        import jax

        if not _stats["listening"]:
            _stats["listening"] = True
            jax.monitoring.register_event_listener(_on_event)
            jax.monitoring.register_event_duration_secs_listener(
                _on_duration)
        env_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
        if env_dir:
            _cache_dir = env_dir
            return _cache_dir
        jax.config.update("jax_compilation_cache_dir", _DEFAULT_CACHE_DIR)
        # JAX's default keeps only compiles of >= 1s; the kernels here
        # take 1-3s on the chip, too close to that line for a warm start
        # to be told from a cold one
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.2)
        _cache_dir = _DEFAULT_CACHE_DIR
        return _cache_dir


def compile_cache_stats() -> dict:
    """Persistent-cache hits/misses and backend compile seconds seen by
    this process since ``enable_compile_cache`` (all zero before it)."""
    return {"dir": _cache_dir, "hits": _stats["hits"],
            "misses": _stats["requests"] - _stats["hits"],
            "compile_seconds": round(_stats["compile_seconds"], 3)}


def held_device() -> dict:
    """``{platform, kind, count}`` of the backend this process holds, as
    JAX reports it.  Initialises the backend on first call and RAISES
    (RuntimeError from jax) when it cannot — never degrades."""
    import jax

    devices = jax.devices()
    return {"platform": devices[0].platform,
            "kind": devices[0].device_kind,
            "count": len(devices)}
