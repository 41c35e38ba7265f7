"""Batch compression for the log store (§5: zStandard, batched records).

zstandard is optional: containers without it fall back to stdlib zlib
(same batched-blob protocol, slightly worse ratio).  Blobs are tagged
with a 1-byte header so the codecs can coexist; zlib-tagged blobs are
readable everywhere, zstd-tagged blobs need zstandard installed (a
clear RuntimeError says so).

A zstd (de)compression context must not be used by two threads at once
(concurrent serving readers decompress candidate batches in parallel), so
every thread keeps its own.
"""
from __future__ import annotations

import threading
import zlib

try:
    import zstandard as zstd
    HAVE_ZSTD = True
except ImportError:  # pragma: no cover - depends on container
    zstd = None
    HAVE_ZSTD = False

_LOCAL = threading.local()


def _ctx(name: str, make):
    ctx = getattr(_LOCAL, name, None)
    if ctx is None:
        ctx = make()
        setattr(_LOCAL, name, ctx)
    return ctx

_TAG_ZSTD = b"z"
_TAG_ZLIB = b"d"


def compress_batch(lines: list[str]) -> bytes:
    raw = "\n".join(lines).encode("utf-8")
    if HAVE_ZSTD:
        cctx = _ctx("cctx", lambda: zstd.ZstdCompressor(level=3))
        return _TAG_ZSTD + cctx.compress(raw)
    return _TAG_ZLIB + zlib.compress(raw, 6)


def decompress_raw(blob: bytes) -> bytes:
    """The UTF-8 bytes of a blob's batch: its lines joined by ``b"\\n"``."""
    tag, payload = blob[:1], blob[1:]
    if tag == _TAG_ZLIB:
        return zlib.decompress(payload)
    # zstd-tagged, or legacy untagged zstd blob
    if not HAVE_ZSTD:
        raise RuntimeError(
            "this store was written with zstandard; install it to read")
    dctx = _ctx("dctx", zstd.ZstdDecompressor)
    return dctx.decompress(payload if tag == _TAG_ZSTD else blob)


def decompress_batch(blob: bytes) -> list[str]:
    return decompress_raw(blob).decode("utf-8").split("\n")
