"""Codec-tagged blob compression with graceful degradation.

The platform persists state (``state.py`` filekv databases) and training
checkpoints (``train/checkpoint.py``) as compressed msgpack blobs.  zstd is
the preferred codec, but it is a third-party dependency; on clean
environments the hard import used to break *all* of ``repro.core`` at
collection time.  This module makes ``zstandard`` optional:

* every blob is prefixed with a 4-byte codec tag (``b"DXZ1"`` = zstd,
  ``b"DXL1"`` = stdlib zlib, ``b"DXZ2"`` = zstd with a trained dictionary)
  so readers dispatch on what was actually written, regardless of what is
  importable today;
* writers pick zstd when available, else zlib — both are self-describing;
* legacy untagged blobs (raw zstd frames, magic ``28 B5 2F FD``) written
  before tagging existed are still readable when zstd is installed.

**Trained dictionaries** (:func:`train_dictionary`) close zstd's gap on
*small* payloads: a generic compressor has nothing to reference inside a
100-byte message, but a dictionary trained on the first N payloads of a
subject carries the stream's shared structure (field names, common values),
so subsequent blobs compress far below the no-dictionary floor.  Dictionary
blobs get their own tag (``DXZ2``) and are NOT self-describing — the reader
must supply the same dictionary bytes, which is why the durable log stores
the trained dictionary alongside its segments.  On the zlib leg
:func:`train_dictionary` returns ``None`` and writers fall back to plain
tagged blobs: degradation, not failure.
"""
from __future__ import annotations

import zlib

try:
    import zstandard
    HAS_ZSTD = True
except ImportError:  # clean environment: fall back to stdlib
    zstandard = None  # type: ignore[assignment]
    HAS_ZSTD = False

TAG_ZSTD = b"DXZ1"
TAG_ZLIB = b"DXL1"
TAG_ZSTD_DICT = b"DXZ2"
_ZSTD_FRAME_MAGIC = b"\x28\xb5\x2f\xfd"  # legacy untagged blobs


class CompressionError(RuntimeError):
    pass


def train_dictionary(samples: list[bytes], *,
                     max_size: int = 4096) -> bytes | None:
    """Train a zstd dictionary from sample payloads; ``None`` = no dictionary.

    Returns ``None`` (write plain tagged blobs instead) when zstd is not
    installed, when there are too few samples to train from, or when training
    itself fails (zstd refuses degenerate sample sets, e.g. all-identical
    bytes) — callers degrade gracefully rather than branching on the codec.
    """
    if not HAS_ZSTD or len(samples) < 8:
        return None
    try:
        d = zstandard.train_dictionary(max_size, list(samples))
        return d.as_bytes()
    except Exception:
        return None


def available_codecs() -> list[str]:
    """Codecs this process can read and write, preferred first.

    The wire-compression negotiation (``transport.py`` hello exchange)
    advertises this list; the serving side picks the first common entry.
    zlib is always last — every peer has it, so negotiation can only fail
    on a malformed hello, never on codec availability.
    """
    return ["zstd", "zlib"] if HAS_ZSTD else ["zlib"]


def compress(data: bytes, *, level: int = 3,
             dictionary: bytes | None = None,
             codec: str | None = None) -> bytes:
    """Compress ``data`` with the best available codec; returns a tagged blob.

    ``dictionary`` (bytes from :func:`train_dictionary`) switches the zstd
    leg to dictionary compression (tag ``DXZ2``); the zlib leg ignores it
    (plain ``DXL1`` blobs stay self-describing).

    ``codec`` pins the codec instead of auto-selecting: ``"zlib"`` forces a
    ``DXL1`` blob even when zstd is installed (a negotiated-down wire
    connection must never emit a tag the peer cannot read), ``"zstd"``
    requires zstd and raises :class:`CompressionError` without it.
    """
    if codec == "zlib":
        return TAG_ZLIB + zlib.compress(data, level)
    if codec == "zstd" and not HAS_ZSTD:
        raise CompressionError(
            "codec 'zstd' requested but the 'zstandard' module is not "
            "installed")
    if codec not in (None, "zstd", "zlib"):
        raise CompressionError(f"unknown codec {codec!r}")
    if HAS_ZSTD:
        if dictionary is not None:
            zd = zstandard.ZstdCompressionDict(dictionary)
            return TAG_ZSTD_DICT + zstandard.ZstdCompressor(
                level=level, dict_data=zd).compress(data)
        return TAG_ZSTD + zstandard.ZstdCompressor(level=level).compress(data)
    return TAG_ZLIB + zlib.compress(data, level)


def decompress(blob: bytes, *, dictionary: bytes | None = None) -> bytes:
    """Inverse of :func:`compress`; dispatches on the codec tag.

    ``DXZ2`` (dictionary) blobs require the same ``dictionary`` bytes they
    were written with — a missing/mismatched dictionary raises
    :class:`CompressionError` instead of returning garbage.
    """
    tag = blob[:4]
    if tag == TAG_ZLIB:
        return zlib.decompress(blob[4:])
    if tag == TAG_ZSTD:
        if not HAS_ZSTD:
            raise CompressionError(
                "blob was written with zstd but the 'zstandard' module is "
                "not installed; install it to read this data")
        return zstandard.ZstdDecompressor().decompress(blob[4:])
    if tag == TAG_ZSTD_DICT:
        if not HAS_ZSTD:
            raise CompressionError(
                "dictionary blob was written with zstd but the 'zstandard' "
                "module is not installed")
        if dictionary is None:
            raise CompressionError(
                "blob was written with a trained dictionary; supply the "
                "dictionary bytes it was written with")
        zd = zstandard.ZstdCompressionDict(
            dictionary, dict_type=zstandard.DICT_TYPE_FULLDICT)
        try:
            return zstandard.ZstdDecompressor(dict_data=zd).decompress(blob[4:])
        except zstandard.ZstdError as e:
            raise CompressionError(f"dictionary decompression failed "
                                   f"(wrong dictionary?): {e}") from None
    if tag == _ZSTD_FRAME_MAGIC:  # pre-tagging blob
        if not HAS_ZSTD:
            raise CompressionError(
                "legacy zstd blob requires the 'zstandard' module")
        return zstandard.ZstdDecompressor().decompress(blob)
    raise CompressionError(f"unrecognized blob header {tag!r}")


def codec_name() -> str:
    """The codec new blobs will be written with ('zstd' or 'zlib')."""
    return "zstd" if HAS_ZSTD else "zlib"
