"""Codec registry — the `-ec.codec={cpu|tpu|tpu_xor|tpu_mxu}` switch.

The reference hardwires klauspost/reedsolomon; here every consumer (file
encoder, degraded reads, gRPC handlers, shell commands) goes through
``get_codec`` so the backend is a deployment choice.

Backends: ``cpu`` (numpy + C++ SIMD, no jax) · ``tpu`` (the Pallas SWAR
kernel, compiled by Mosaic — it needs a TPU backend) · ``tpu_xor`` (fused
XLA XOR network) · ``tpu_mxu`` (bit-plane int8 matmul on the systolic
array).

The TPU codec is imported lazily: the CPU-only per-needle path (storage
servers doing small degraded reads) must not pay a jax import, and must work
on hosts without jax at all.
"""

from __future__ import annotations

import time

from ..stats.metrics import EC_BYTES_HISTOGRAM, EC_OP_HISTOGRAM
from ..telemetry import trace
from .rs_cpu import ReedSolomon

DATA_SHARDS = 10
PARITY_SHARDS = 4
TOTAL_SHARDS = DATA_SHARDS + PARITY_SHARDS


# ---------------------------------------------------------------------------
# EC-codec telemetry: every blocking codec call through get_codec records
# seaweedfs_ec_op_seconds{op,impl} + seaweedfs_ec_op_bytes{op,impl} and a
# span, so degraded-read and rebuild cost shows up in /metrics and
# /debug/traces attributed to the backend that did the GF math.
# ---------------------------------------------------------------------------


def _nbytes(x) -> int:
    if x is None:
        return 0
    n = getattr(x, "nbytes", None)
    if n is not None:
        return int(n)
    try:
        return len(x)
    except TypeError:
        return 0


def _arg_bytes(arg) -> int:
    if isinstance(arg, (list, tuple)):
        return sum(_nbytes(s) for s in arg)
    return _nbytes(arg)


class InstrumentedCodec:
    """Transparent telemetry proxy over a codec.

    Delegates everything (attributes, the device-resident async entries,
    hasattr-probed capabilities) and times only the BLOCKING operations —
    the async encode_device* futures are left alone because their wall
    time at dispatch is not the compute time; rs_jax spans cover those.
    """

    _TIMED = frozenset({
        "encode", "parity_of", "parity_into", "apply_rows",
        "reconstruct", "reconstruct_data", "reconstruct_one", "verify",
    })

    def __init__(self, inner, impl: str):
        self._inner = inner
        self._impl = impl

    def __getattr__(self, name: str):
        attr = getattr(self._inner, name)
        if name not in self._TIMED or not callable(attr):
            return attr
        impl = self._impl
        # histogram children and span name resolved ONCE per (op, impl):
        # the per-chunk encode loop must not pay registry-lock lookups
        # or import-machinery hits on every call
        op_hist = EC_OP_HISTOGRAM.labels(name, impl)
        bytes_hist = EC_BYTES_HISTOGRAM.labels(name, impl)
        span_name = f"ec.{name}"
        child_span = trace.child_span
        perf_counter = time.perf_counter

        def timed(*args, **kwargs):
            # max over the first two args: apply_rows leads with the tiny
            # plan matrix, every other op leads with the shard payload
            nbytes = max(
                (_arg_bytes(a) for a in args[:2]), default=0) if args else 0
            t0 = perf_counter()
            try:
                # metrics always; spans only inside an active trace — a
                # bulk encode calls this once per segment, and a root
                # span per segment would evict every request trace from
                # the ring
                with child_span(span_name, impl=impl, bytes=nbytes):
                    return attr(*args, **kwargs)
            finally:
                op_hist.observe(perf_counter() - t0)
                bytes_hist.observe(nbytes)

        timed.__name__ = name
        # cache on the instance: per-chunk hot paths (parity_into in the
        # encode loop) must not rebuild the closure every call
        self.__dict__[name] = timed
        return timed


def _instrument(codec, impl: str):
    return InstrumentedCodec(codec, impl)


def available_codecs() -> list[str]:
    """Canonical codec names usable with ``get_codec`` on this host."""
    import importlib.util

    names = ["auto", "cpu"]
    if importlib.util.find_spec("jax") is None:
        return names
    return names + ["tpu", "tpu_xor", "tpu_mxu"]


_AUTO_CHOICE: list[str] = []

# every name get_codec resolves to a jax-backed codec — the single
# source of truth shared with ops.codec_service's mode/routing logic
DEVICE_CODEC_NAMES = frozenset(
    {"tpu", "pallas", "tpu_pallas", "jax", "tpu_xor", "tpu_mxu", "mxu"})


def _resolve_auto() -> str:
    """``tpu`` when THIS process's jax holds an accelerator, else ``cpu``.

    Decided in process from ``jax.devices()`` (ops.device.held_device): a
    chip belongs to one process, so a child could not answer for us.  A
    backend that cannot initialise raises here — ``auto`` picks between
    what exists, it does not paper over a broken runtime.
    """
    import importlib.util

    if importlib.util.find_spec("jax") is None:
        return "cpu"
    from .device import held_device

    return "cpu" if held_device()["platform"] == "cpu" else "tpu"


def resolve_codec_name(name: str) -> str:
    """``auto`` -> the codec this process will actually build (cached for
    the process lifetime); any other name passes through unchanged."""
    if name != "auto":
        return name
    if not _AUTO_CHOICE:
        _AUTO_CHOICE.append(_resolve_auto())
    return _AUTO_CHOICE[0]


def get_codec(name: str = "cpu", data_shards: int = DATA_SHARDS,
              parity_shards: int = PARITY_SHARDS):
    """Return a codec with encode/reconstruct/reconstruct_data/verify.

    A device codec name is never replaced by the host codec: if the jax
    backend cannot initialise, the codec's first device call raises."""
    name = resolve_codec_name(name)
    if name in ("cpu", "go", "numpy"):
        return _instrument(ReedSolomon(data_shards, parity_shards), "cpu")
    if name in DEVICE_CODEC_NAMES:
        from .device import enable_compile_cache

        enable_compile_cache()  # before this process's first compile
    if name in ("tpu", "pallas", "tpu_pallas"):
        from .rs_jax import ReedSolomonTPU

        return _instrument(
            ReedSolomonTPU(data_shards, parity_shards, impl="pallas"),
            "pallas")
    if name in ("jax", "tpu_xor"):
        from .rs_jax import ReedSolomonTPU

        return _instrument(
            ReedSolomonTPU(data_shards, parity_shards, impl="xor"), "xor")
    if name in ("tpu_mxu", "mxu"):
        from .rs_jax import ReedSolomonTPU

        return _instrument(
            ReedSolomonTPU(data_shards, parity_shards, impl="mxu"), "mxu")
    raise ValueError(f"unknown ec codec {name!r}")
