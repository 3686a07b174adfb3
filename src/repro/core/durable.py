"""Durable streams — append-only per-subject logs with replay and retention.

DataX subjects are fire-and-forget: a late subscriber sees nothing, a crash
loses in-flight history, and the paper's reuse story ("effortless reuse of
microservices and data streams") stops at whoever happened to be listening.
This module is the opt-in durability layer underneath the bus:

* :class:`DurableLog` — an append-only log of codec-tagged compressed blobs
  (``core/compression.py``), organized as **rolling segments**.  Every
  publish on a durable subject appends one record ``(offset, blob)`` where
  ``offset`` is a dense monotone sequence starting at 0; the offset rides on
  the delivered message as ``headers["offset"]``, which is what lets
  consumers pair state snapshots with log positions (exactly-once keyed
  recovery) and lets a replaying subscriber hand off to live delivery with
  no gaps and no duplicates.

* **Retention** (:class:`Retention`) — by record count, age, and/or total
  blob bytes.  Whole *sealed* segments are evicted at append time (the
  active segment never is), and evictions are counted so the metrics
  surface shows history being dropped.

* **Catalog** — per-log metadata (subject → segments, offset range, schema
  fingerprint, ``last_update``, trained dictionary) with optional on-disk
  persistence under a root directory: sealed segments are written as files
  and the catalog as ``catalog.dxc``, so a restarted process finds the
  history it wrote (H-STREAM's "query live streams and their histories"
  through one abstraction; the atd-data-lake catalog + ``last_update``
  incremental-reprocessing pattern).

* **Dictionary-trained compression** — the first ``train_dict_after``
  encoded messages of a subject train a zstd dictionary
  (:func:`~.compression.train_dictionary`); subsequent blobs compress with
  it (tag ``DXZ2``).  The dictionary is stored in the catalog/on disk so
  replay can decode, and the zlib leg degrades to plain tagged blobs.

The bus integration lives in ``bus.py`` (``MessageBus.make_durable``,
``subscribe(replay_from=...)``); the keyed exactly-once recovery helpers
pair a :class:`~.state.KeyedStore` snapshot watermark with a log offset
(:func:`resolve_replay_from`).
"""
from __future__ import annotations

import dataclasses
import hashlib
import io
import os
import threading
import time
from typing import TYPE_CHECKING, Iterable

import msgpack

from .compression import (HAS_ZSTD, TAG_ZLIB, TAG_ZSTD_DICT, compress,
                          decompress, train_dictionary)
from .schema import Message, StreamSchema

if TYPE_CHECKING:  # pragma: no cover - import cycle guard (state -> bus)
    from .state import Database


class DurableError(RuntimeError):
    pass


# ---------------------------------------------------------------------------
# Retention policy
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class Retention:
    """How much history a durable subject keeps (None = unbounded).

    Limits compose (evict until ALL are satisfied); eviction granularity is
    a whole sealed segment, so the live bound is approximate by up to one
    segment.  The active (still-filling) segment is never evicted.
    """

    max_records: int | None = None   # total retained records
    max_age_s: float | None = None   # drop segments whose newest record is older
    max_bytes: int | None = None     # total retained compressed bytes
    #                                  (sealed segments; the active segment
    #                                  counts once it seals)

    @staticmethod
    def of(spec: "Retention | dict | None") -> "Retention":
        """Coerce the plumbing-friendly forms (dict from a StreamSpec,
        None = keep everything) into a Retention."""
        if spec is None:
            return Retention()
        if isinstance(spec, Retention):
            return spec
        unknown = set(spec) - {"max_records", "max_age_s", "max_bytes"}
        if unknown:
            raise DurableError(f"unknown retention keys {sorted(unknown)}; "
                               f"allowed: max_records, max_age_s, max_bytes")
        return Retention(**spec)


def schema_fingerprint(schema: StreamSchema | None) -> str:
    """Stable digest of a stream schema — recorded in the catalog so an
    offline reader can detect that history predates a schema change."""
    if schema is None or not schema.fields:
        return "untyped"
    parts = [f"{name}:{f.kind}:{f.shape}:{f.dtype}"
             for name, f in sorted(schema.fields.items())]
    return hashlib.blake2s("|".join(parts).encode(),
                           digest_size=8).hexdigest()


# ---------------------------------------------------------------------------
# Record encoding — one record per published message
# ---------------------------------------------------------------------------

# A record blob is the wire encoding of the full message (subject, seq, ts,
# headers, payload — numpy-aware msgpack from bus.py), compressed into a
# codec-tagged blob.  Self-describing except for DXZ2 dictionary blobs,
# whose dictionary the log stores.

def _encode_record(msg: Message) -> bytes:
    from .bus import encode_message  # late import: bus imports this module
    return encode_message(msg)


def _decode_record(subject: str, offset: int, raw: bytes) -> Message:
    from .bus import decode_message
    msg = decode_message(raw)
    msg.headers["offset"] = offset
    return msg


# ---------------------------------------------------------------------------
# Segments
# ---------------------------------------------------------------------------

class Segment:
    """One contiguous run of records ``[base_offset, base_offset + n)``.

    While ACTIVE, records are the published :class:`Message` objects
    themselves — the append hot path neither encodes nor compresses (the
    same object the in-process bus hands its subscribers, so sharing it is
    no new aliasing).  Sealing bulk-encodes the run, packs it, and
    compresses it into ONE codec-tagged blob (tag ``DXZ2`` when the log's
    trained dictionary applies), which amortizes both the encoder and the
    codec to a single pass per ``segment_records`` appends and compresses
    far better than per-record blobs.  Per-record timestamps survive
    sealing (``tss``) so ``offset_at_ts`` never needs to decompress.
    """

    def __init__(self, base_offset: int):
        self.base_offset = base_offset
        # (ts, item) where item is a live Message (fresh appends) or raw
        # encoded bytes (a tail reloaded from disk); None once sealed
        self.records: list[tuple[float, object]] | None = []
        self.blob: bytes | None = None       # compressed run, once sealed
        self.tss: list[float] = []           # per-record ts, once sealed
        self.count = 0
        self.bytes = 0                       # compressed blob bytes (sealed)
        self.created_ts = time.time()
        self.last_ts = self.created_ts
        self.sealed = False

    def append(self, ts: float, item: "Message | bytes") -> None:
        self.records.append((ts, item))      # type: ignore[union-attr]
        self.count += 1
        self.last_ts = ts

    def _encoded_records(self) -> list[tuple[float, bytes]]:
        return [(ts, item if isinstance(item, (bytes, bytearray))
                 else _encode_record(item))   # type: ignore[arg-type]
                for ts, item in self.records]  # type: ignore[union-attr]

    def seal(self, level: int, dictionary: bytes | None) -> None:
        if self.blob is not None:
            self.sealed = True
            return
        packed = msgpack.packb(self._encoded_records(), use_bin_type=True)
        self.blob = compress(packed, level=level, dictionary=dictionary)
        self.tss = [ts for ts, _ in self.records]   # type: ignore[union-attr]
        self.bytes = len(self.blob)
        self.records = None
        self.sealed = True

    @property
    def next_offset(self) -> int:
        return self.base_offset + self.count

    def __len__(self) -> int:
        return self.count

    # -- (de)serialization (on-disk segment files) ---------------------------
    def to_bytes(self) -> bytes:
        if self.blob is not None:
            return msgpack.packb(
                {"base": self.base_offset, "created": self.created_ts,
                 "last": self.last_ts, "tss": self.tss, "blob": self.blob},
                use_bin_type=True)
        return msgpack.packb(
            {"base": self.base_offset, "created": self.created_ts,
             "records": self._encoded_records() if self.records else []},
            use_bin_type=True)

    @staticmethod
    def from_bytes(raw: bytes) -> "Segment":
        obj = msgpack.unpackb(raw, raw=False, strict_map_key=False)
        seg = Segment(obj["base"])
        seg.created_ts = obj["created"]
        if "blob" in obj:
            seg.blob = obj["blob"]
            seg.tss = list(obj["tss"])
            seg.count = len(seg.tss)
            seg.bytes = len(seg.blob)
            seg.last_ts = obj.get("last", seg.created_ts)
            seg.records = None
        else:
            for ts, rec in obj["records"]:
                seg.append(ts, rec)
        seg.sealed = True
        return seg


# ---------------------------------------------------------------------------
# The log
# ---------------------------------------------------------------------------

#: Records per segment before it seals and a new one starts.  Small enough
#: that retention (whole-segment granularity) tracks its limits closely,
#: large enough that the per-segment bookkeeping stays negligible.
DEFAULT_SEGMENT_RECORDS = 256

#: Encoded messages sampled before training the compression dictionary.
DEFAULT_TRAIN_AFTER = 64

_CATALOG_FILE = "catalog.dxc"
_DICT_FILE = "dict.bin"


class DurableLog:
    """Append-only log of one subject's messages, with rolling segments,
    retention, and an optional on-disk catalog.

    Thread-safe: ``append`` is called from every publisher of the subject,
    ``read`` from every replaying subscriber.  Offsets are dense (0, 1, 2,
    ...) and never reused; eviction moves ``earliest_offset`` forward.
    """

    def __init__(self, subject: str, *,
                 retention: Retention | dict | None = None,
                 root: str | None = None,
                 segment_records: int = DEFAULT_SEGMENT_RECORDS,
                 train_dict_after: int | None = DEFAULT_TRAIN_AFTER,
                 schema: StreamSchema | None = None,
                 compress_level: int = 1):
        self.subject = subject
        self.retention = Retention.of(retention)
        self.root = root
        self.segment_records = max(1, segment_records)
        self.fingerprint = schema_fingerprint(schema)
        self._level = compress_level
        self._lock = threading.Lock()
        self._segments: list[Segment] = [Segment(0)]
        self._cache_base = -1               # one-entry sealed-segment cache
        self._cache_records: list = []
        self.evicted_records = 0
        self.evicted_segments = 0
        self.last_update = 0.0
        # dictionary training state
        self._train_after = train_dict_after if train_dict_after else 0
        self._train_samples: list[bytes] = []
        self._dict: bytes | None = None
        if root:
            os.makedirs(root, exist_ok=True)
            self._load_locked()

    # -- append path ---------------------------------------------------------
    def append(self, msg: Message) -> int:
        """Append one message; returns its offset (dense, monotone).

        The hot path is a lock + list-append — encoding AND compression
        happen once per segment at roll time (:meth:`Segment.seal`), so a
        durable publish adds no per-message encode.  (While
        the dictionary trainer still needs samples, the first
        ``train_dict_after`` appends do encode — a one-time cost.)"""
        with self._lock:
            if self._dict is None and self._train_after:
                self._train_samples.append(_encode_record(msg))
                if len(self._train_samples) >= self._train_after:
                    self._dict = train_dictionary(self._train_samples)
                    self._train_samples = []
                    self._train_after = 0   # one-shot: train once per subject
                    if self._dict is not None and self.root:
                        self._write_file(_DICT_FILE, self._dict)
            seg = self._segments[-1]
            if seg.sealed or len(seg) >= self.segment_records:
                seg = self._roll_locked()
            offset = seg.next_offset
            now = time.time()
            seg.append(now, msg)
            self.last_update = now
            self._enforce_retention_locked()
            return offset

    def _roll_locked(self) -> Segment:
        old = self._segments[-1]
        old.seal(self._level, self._dict)
        if self.root:
            self._write_file(f"seg-{old.base_offset:012d}.dxl", old.to_bytes())
            self._write_catalog_locked()
        seg = Segment(old.next_offset)
        self._segments.append(seg)
        return seg

    def _enforce_retention_locked(self) -> None:
        r = self.retention
        if r.max_records is None and r.max_age_s is None \
                and r.max_bytes is None:
            return
        now = time.time()
        while len(self._segments) > 1:   # the active segment never evicts
            head = self._segments[0]
            total_records = sum(len(s) for s in self._segments)
            total_bytes = sum(s.bytes for s in self._segments)
            over = (
                (r.max_records is not None and total_records > r.max_records)
                or (r.max_bytes is not None and total_bytes > r.max_bytes)
                or (r.max_age_s is not None
                    and now - head.last_ts > r.max_age_s))
            if not over:
                break
            self._segments.pop(0)
            self.evicted_records += len(head)
            self.evicted_segments += 1
            if self._cache_base == head.base_offset:
                self._cache_base, self._cache_records = -1, []
            if self.root:
                path = os.path.join(self.root,
                                    f"seg-{head.base_offset:012d}.dxl")
                if os.path.exists(path):
                    os.remove(path)

    # -- read path -----------------------------------------------------------
    def next_offset(self) -> int:
        """The offset the NEXT append will get (== current log head)."""
        with self._lock:
            return self._segments[-1].next_offset

    def earliest_offset(self) -> int:
        """Oldest retained offset (== next_offset when the log is empty)."""
        with self._lock:
            return self._segments[0].base_offset

    def offset_at_ts(self, ts: float) -> int:
        """First retained offset whose record ts >= ``ts`` (log head if the
        whole retained history predates ``ts``).  Served from the per-record
        timestamps — sealed segments are never decompressed for this."""
        with self._lock:
            for seg in self._segments:
                if seg.last_ts < ts and len(seg):
                    continue
                tss = seg.tss if seg.records is None \
                    else [rts for rts, _ in seg.records]
                for i, rts in enumerate(tss):
                    if rts >= ts:
                        return seg.base_offset + i
            return self._segments[-1].next_offset

    def read(self, from_offset: int, max_n: int = 64) -> list[Message]:
        """Up to ``max_n`` decoded messages starting at ``from_offset``
        (clamped to the earliest retained offset).  Empty list = caught up.

        Each returned message carries its log position in
        ``headers["offset"]`` — identical to live delivery on a durable
        subject, so consumers never branch on replay-vs-live.
        """
        with self._lock:
            cursor = max(from_offset, self._segments[0].base_offset)
            plan: list[tuple[Segment, list | None]] = []
            served = 0
            for seg in self._segments:
                if seg.next_offset <= cursor or not len(seg):
                    continue
                # active segment: snapshot under the lock (it still grows);
                # sealed segments are immutable and decompress outside it
                plan.append((seg, list(seg.records)
                             if seg.records is not None else None))
                served += seg.next_offset - max(cursor, seg.base_offset)
                if served >= max_n:
                    break
            dictionary = self._dict
        msgs: list[Message] = []
        for seg, records in plan:
            if records is None:
                records = self._sealed_records(seg, dictionary)
            start = max(0, cursor - seg.base_offset)
            for i in range(start, len(records)):
                if len(msgs) >= max_n:
                    return msgs
                off = seg.base_offset + i
                item = records[i][1]
                if isinstance(item, (bytes, bytearray)):
                    msgs.append(_decode_record(self.subject, off, item))
                else:
                    # active-segment record: still a live Message — return a
                    # fresh envelope (same payload object, like in-proc
                    # delivery) with its log position stamped
                    msgs.append(Message(
                        subject=item.subject, payload=item.payload,
                        seq=item.seq, ts=item.ts,
                        headers={**item.headers, "offset": off}))
            cursor = seg.base_offset + len(records)
        return msgs

    def _sealed_records(self, seg: Segment,
                        dictionary: bytes | None) -> list:
        """Decompress a sealed segment's record run, with a one-entry cache
        — replay reads are sequential, so consecutive calls hit the same
        segment and pay the codec once."""
        with self._lock:
            if self._cache_base == seg.base_offset:
                return self._cache_records
        packed = decompress(seg.blob, dictionary=dictionary)  # type: ignore[arg-type]
        records = msgpack.unpackb(packed, raw=False)
        with self._lock:
            self._cache_base, self._cache_records = seg.base_offset, records
        return records

    # -- catalog -------------------------------------------------------------
    def info(self) -> dict:
        """The catalog entry: depth, segment/offset ranges, retention
        evictions, schema fingerprint, last_update — the sidecar surfaces
        this through its REST metrics and offline readers use it to bound
        incremental re-runs (the atd-data-lake ``last_update`` pattern)."""
        with self._lock:
            return {
                "subject": self.subject,
                "depth": sum(len(s) for s in self._segments),
                "bytes": sum(s.bytes for s in self._segments),
                "segments": len(self._segments),
                "earliest_offset": self._segments[0].base_offset,
                "next_offset": self._segments[-1].next_offset,
                "evicted_records": self.evicted_records,
                "evicted_segments": self.evicted_segments,
                "schema_fingerprint": self.fingerprint,
                "dict_trained": self._dict is not None,
                "last_update": self.last_update,
            }

    # -- persistence ---------------------------------------------------------
    def _write_file(self, name: str, data: bytes) -> None:
        path = os.path.join(self.root, name)           # type: ignore[arg-type]
        tmp = f"{path}.tmp"
        with open(tmp, "wb") as f:
            f.write(data)
        os.replace(tmp, path)

    def _write_catalog_locked(self) -> None:
        cat = {
            "subject": self.subject,
            "fingerprint": self.fingerprint,
            "segments": [s.base_offset for s in self._segments if s.sealed],
            "next_offset": self._segments[-1].next_offset,
            "evicted_records": self.evicted_records,
            "evicted_segments": self.evicted_segments,
            "last_update": self.last_update,
            "has_dict": self._dict is not None,
        }
        self._write_file(_CATALOG_FILE, compress(
            msgpack.packb(cat, use_bin_type=True), level=self._level))

    def flush(self) -> None:
        """Persist the active segment + catalog (root-backed logs only).

        Sealed segments are written as they roll; this makes the tail
        durable too (called at close/teardown and by tests)."""
        if not self.root:
            return
        with self._lock:
            seg = self._segments[-1]
            self._write_file(f"seg-{seg.base_offset:012d}.dxl", seg.to_bytes())
            self._write_catalog_locked()

    def _blob_readable(self, blob: bytes | None) -> bool:
        """Can a sealed blob be decompressed in THIS environment?  Raw-record
        segments (no blob) always can; ``DXL1`` is stdlib; ``DXZ1``/legacy
        frames need zstd; ``DXZ2`` needs the (already validated) dictionary."""
        if blob is None:
            return True
        tag = bytes(blob[:4])
        if tag == TAG_ZLIB:
            return True
        if tag == TAG_ZSTD_DICT:
            return HAS_ZSTD and self._dict is not None
        return HAS_ZSTD   # DXZ1 or a legacy untagged zstd frame

    def _load_locked(self) -> None:
        cat_path = os.path.join(self.root, _CATALOG_FILE)  # type: ignore[arg-type]
        if not os.path.exists(cat_path):
            return
        with open(cat_path, "rb") as f:
            cat = msgpack.unpackb(decompress(f.read()), raw=False,
                                  strict_map_key=False)
        if cat.get("has_dict"):
            # A missing dict.bin must not fail the catalog load: DXZ2
            # segments become unreadable (dropped below, counted as
            # evictions) but self-describing history still loads.
            dict_path = os.path.join(self.root, _DICT_FILE)  # type: ignore[arg-type]
            if os.path.exists(dict_path):
                with open(dict_path, "rb") as f:
                    self._dict = f.read()
        segments: list[Segment] = []
        for name in sorted(os.listdir(self.root)):       # type: ignore[arg-type]
            if not (name.startswith("seg-") and name.endswith(".dxl")):
                continue
            with open(os.path.join(self.root, name), "rb") as f:  # type: ignore[arg-type]
                segments.append(Segment.from_bytes(f.read()))
        if self._dict is not None:
            # A present-but-corrupt dict.bin must degrade exactly like a
            # missing one — validate against the first dictionary-tagged
            # blob before trusting it for every later read
            probe = next((s.blob for s in segments if s.blob is not None
                          and bytes(s.blob[:4]) == TAG_ZSTD_DICT), None)
            try:
                if probe is not None:
                    decompress(probe, dictionary=self._dict)
            except Exception:   # zstd raises its own types on garbage dicts
                self._dict = None
        if self._dict is not None:
            self._train_after = 0   # keep using the persisted dictionary
        kept: list[Segment] = []
        dropped_records = dropped_segments = 0
        for seg in segments:
            if self._blob_readable(seg.blob):
                kept.append(seg)
                continue
            dropped_records += len(seg)
            dropped_segments += 1
            path = os.path.join(self.root,               # type: ignore[arg-type]
                                f"seg-{seg.base_offset:012d}.dxl")
            if os.path.exists(path):
                os.remove(path)
        if kept and kept[-1] is segments[-1]:
            self._segments = kept
            tail = self._segments[-1]
            if tail.records is None:
                # the tail rolled (blob form) before the process died —
                # reopen it for appends by unpacking the run back to raw
                packed = decompress(tail.blob,  # type: ignore[arg-type]
                                    dictionary=self._dict)
                tail.records = [(ts, rec) for ts, rec in
                                msgpack.unpackb(packed, raw=False)]
                tail.bytes = sum(len(rec) for _, rec in tail.records)
                tail.blob = None
                tail.tss = []
            tail.sealed = False   # resume appending to the tail
        elif segments:
            # the on-disk tail was unreadable (or nothing survived): resume
            # appending at the old head so offsets stay dense and monotone
            head = max(cat.get("next_offset", 0), segments[-1].next_offset)
            self._segments = kept + [Segment(head)]
        self.evicted_records = cat.get("evicted_records", 0) + dropped_records
        self.evicted_segments = (cat.get("evicted_segments", 0)
                                 + dropped_segments)
        self.last_update = cat.get("last_update", 0.0)

    def close(self) -> None:
        self.flush()

    def drop(self) -> None:
        """Delete on-disk state (subject unregistered)."""
        if not self.root or not os.path.isdir(self.root):
            return
        for name in os.listdir(self.root):
            if name == _CATALOG_FILE or name == _DICT_FILE \
                    or (name.startswith("seg-") and name.endswith(".dxl")):
                try:
                    os.remove(os.path.join(self.root, name))
                except OSError:  # pragma: no cover - best-effort cleanup
                    pass


# ---------------------------------------------------------------------------
# Exactly-once keyed recovery — snapshot watermark resolution
# ---------------------------------------------------------------------------

#: Table (in a stream's platform database) where KeyedStore.snapshot()
#: records per-owner watermarks: all log offsets <= watermark are applied.
SNAPSHOT_TABLE = "__snapshots__"


def resolve_replay_from(replay_from, db: "Database | None"):
    """Resolve a StreamSpec's ``replay_from`` into a bus-level position.

    ``"snapshot"`` reads the stream database's snapshot watermarks
    (:data:`SNAPSHOT_TABLE`, written by ``KeyedStore.snapshot``) and replays
    from the SUFFIX after the oldest one — the exactly-once recovery
    contract: state up to the watermark is already in the store, so only
    later offsets need reprocessing (per-key applied-offset dedupe makes an
    over-long replay safe, never incorrect).  No snapshot yet → replay from
    ``"earliest"``.  Every other value passes through unchanged (offset int,
    timestamp float, ``"earliest"``).
    """
    if replay_from != "snapshot":
        return replay_from
    if db is None:
        return "earliest"
    try:
        table = db.table(SNAPSHOT_TABLE)
    except Exception:
        return "earliest"
    marks = [row.get("watermark") for _, row in table.scan()
             if row.get("watermark") is not None]
    if not marks:
        return "earliest"
    return int(min(marks)) + 1


def iter_log(log: DurableLog, from_offset: int = 0,
             batch: int = 64) -> Iterable[Message]:
    """Convenience iterator over the retained history (offline/queries)."""
    cursor = max(from_offset, log.earliest_offset())
    while True:
        msgs = log.read(cursor, batch)
        if not msgs:
            return
        yield from msgs
        cursor = msgs[-1].headers["offset"] + 1


__all__ = [
    "DurableError", "DurableLog", "Retention", "Segment", "SNAPSHOT_TABLE",
    "iter_log", "resolve_replay_from", "schema_fingerprint",
]
