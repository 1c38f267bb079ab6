"""Write-ahead log of consensus messages.

Reference: internal/consensus/wal.go — CRC32C + length framed records via
internal/autofile; WriteSync fsync barrier before height end;
SearchForEndHeight for replay.  WAL bytes are node-local: only durability
and replayability matter.

A record's payload is one tag byte and a body.  The four message kinds
carry their proto (``wire/pb.py``, through ``wire.encode``) after the
tag, ``block_part`` its height and round first; ``timeout`` and
``end_height`` carry their integers alone.  Anything else is canonical
JSON (bytes hex-encoded), whose first byte is ``{``: no tag takes that
value, so a WAL written in JSON throughout reads by the same rule.
"""
from __future__ import annotations

import json
import os
import struct
import zlib
from typing import Any, Callable, Iterator, Optional

from ..libs import metrics as libmetrics
from ..wire import decode, encode, pb
from .messages import dejsonify, jsonify

MAX_MSG_SIZE_BYTES = 1024 * 1024 * 2  # reference: wal.go maxMsgSizeBytes


class WALError(Exception):
    pass


class CorruptWALError(WALError):
    pass


def _frame(payload: bytes) -> bytes:
    crc = zlib.crc32(payload) & 0xFFFFFFFF
    return struct.pack(">II", crc, len(payload)) + payload


# -- the record payload --------------------------------------------------------

class _Kind:
    """A record type in binary: its tag, the integer fields before the
    body (big-endian int64 each), the key and descriptor of its proto
    body (``None`` for a type with no body)."""

    def __init__(self, tag: int, ints: tuple, body: Optional[str],
                 desc=None):
        self.tag = bytes((tag,))
        self.ints = ints
        self.head = struct.Struct(">" + "q" * len(ints))
        self.body = body
        self.desc = desc
        self.keys = frozenset(("type", *ints) + ((body,) if body else ()))
        self.fields = frozenset(f.name for f in desc.fields) \
            if desc is not None else frozenset()


_KINDS = {
    "vote": _Kind(1, (), "vote", pb.VOTE),
    "proposal": _Kind(2, (), "proposal", pb.PROPOSAL),
    "block_part": _Kind(3, ("height", "round"), "part", pb.PART),
    "aggregate_commit": _Kind(4, (), "commit", pb.AGGREGATE_COMMIT),
    "timeout": _Kind(5, ("height", "round", "step"), None),
    "end_height": _Kind(6, ("height",), None),
}
_BY_TAG = {k.tag: (t, k) for t, k in _KINDS.items()}

# records decoded in this process, by payload: plain integers read at
# scrape time, as the vote counters (types/vote.py)
_RECORDS = {"binary": 0, "json": 0}


def record_counts() -> dict:
    """Records decoded so far in this process, by codec."""
    return dict(_RECORDS)


libmetrics.DEFAULT.counter_func(
    "consensus", "wal_records_total",
    "WAL records decoded, by the payload they were read from: binary "
    "(a tag byte, then the proto of a message or the integers of a "
    "timeout or end-height marker) or json (canonical JSON: any other "
    "record, and every record of a WAL written before the binary "
    "payload).", "codec", lambda: _RECORDS)


def encode_record(msg: dict) -> bytes:
    """A record's payload: binary for a type of ``_KINDS`` whose keys
    are exactly that type's, with ints for its integers and, for a
    message, a proto dict of its descriptor as its body (what
    ``to_wal()`` gives); canonical JSON for anything else."""
    kind = _KINDS.get(msg.get("type"))
    if kind is not None and msg.keys() == kind.keys:
        ints = [msg[k] for k in kind.ints]
        body = msg.get(kind.body)
        if all(type(v) is int for v in ints) and (
                kind.body is None or
                (type(body) is dict and body.keys() <= kind.fields)):
            try:
                out = kind.tag + kind.head.pack(*ints)
                return out if kind.body is None else \
                    out + encode(kind.desc, body)
            except (TypeError, ValueError, OverflowError, struct.error):
                pass        # a value the proto cannot hold
    return json.dumps(jsonify(msg), separators=(",", ":"),
                      sort_keys=True).encode()


def decode_record(payload: bytes) -> dict:
    """A payload as a dict: ``type``, the integer fields, and a
    message's body under its key (``vote``, ``proposal``, ``part``,
    ``commit``) as a proto dict; a JSON record with its bytes
    restored.  Counted here, once a record."""
    if payload[:1] == b"{":
        _RECORDS["json"] += 1
        return dejsonify(json.loads(payload))
    t, kind = _BY_TAG.get(payload[:1], (None, None))
    if kind is None:
        raise CorruptWALError(f"unknown record tag {payload[:1].hex()}")
    _RECORDS["binary"] += 1
    rec = {"type": t}
    if kind.ints:
        rec.update(zip(kind.ints, kind.head.unpack_from(payload, 1)))
    if kind.body is not None:
        rec[kind.body] = decode(kind.desc, payload[1 + kind.head.size:])
    return rec


def _ends_height(payload: bytes, height: int) -> bool:
    """Whether a payload is the end-height marker of ``height``; a
    binary message's body is not decoded to tell."""
    if payload[:1] not in (b"{", _KINDS["end_height"].tag):
        return False
    rec = decode_record(payload)
    return rec.get("type") == "end_height" and rec.get("height") == height


class WAL:
    """Append-only message log with explicit fsync barriers and file
    rotation.

    Rotation mirrors the reference's autofile group (internal/autofile
    group.go): when the head file exceeds head_size_limit the head is
    renamed to `<path>.NNN` and a fresh head opened; when the group
    exceeds total_size_limit the oldest rotated files are deleted.
    Replay iterates rotated files oldest-first, then the head."""

    def __init__(self, path: str,
                 head_size_limit: int = 4 * 1024 * 1024,
                 total_size_limit: int = 128 * 1024 * 1024):
        os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
        self._path = path
        self._head_size_limit = head_size_limit
        self._total_size_limit = total_size_limit
        self._f = self._open_head()

    def _open_head(self):
        """Open the head for append, truncating any torn tail first.
        A crash mid-write leaves a partial frame at EOF; appending
        after it would make every later (valid) frame unreachable to
        replay, which stops at the first bad frame."""
        if os.path.exists(self._path):
            with open(self._path, "rb") as f:
                data = f.read()
            good = _scan_valid_prefix(data)
            if good < len(data):
                # keep a forensics copy of the cut bytes (mirrors
                # repair_wal_file's .corrupted stash)
                with open(self._path + ".corrupted", "ab") as f:
                    f.write(data[good:])
                with open(self._path, "r+b") as f:
                    f.truncate(good)
        return open(self._path, "ab")

    @property
    def path(self) -> str:
        return self._path

    def reopen(self) -> None:
        """Re-acquire the head-file handle.  Required after
        repair_wal_file: repair may rename the head to .corrupted and
        recreate it, and an already-open append handle would keep
        writing to the renamed inode."""
        try:
            self._f.close()
        except OSError:
            pass
        self._f = self._open_head()

    def write(self, msg: dict) -> None:
        """Buffered append (reference: WAL.Write for peer messages)."""
        payload = encode_record(msg)
        if len(payload) > MAX_MSG_SIZE_BYTES:
            raise WALError(f"msg is too big: {len(payload)} bytes")
        self._f.write(_frame(payload))
        if self._f.tell() > self._head_size_limit:
            self._rotate()

    def _rotate(self) -> None:
        """Head -> numbered group file; enforce the total size cap."""
        self._f.flush()
        os.fsync(self._f.fileno())
        self._f.close()
        existing = WAL.group_files(self._path)[:-1]   # without head
        nxt = 0
        if existing:
            nxt = int(existing[-1].rsplit(".", 1)[1]) + 1
        os.replace(self._path, f"{self._path}.{nxt:03d}")
        self._f = open(self._path, "ab")
        # prune oldest rotated files beyond the total limit
        files = WAL.group_files(self._path)[:-1]
        total = sum(os.path.getsize(f) for f in files)
        for f in files:
            if total <= self._total_size_limit:
                break
            total -= os.path.getsize(f)
            os.remove(f)

    def write_sync(self, msg: dict) -> None:
        """Append + flush + fsync (reference: WAL.WriteSync — used before
        signing our own messages and at height boundaries)."""
        self.write(msg)
        self.flush_and_sync()

    def flush_and_sync(self) -> None:
        self._f.flush()
        os.fsync(self._f.fileno())

    def write_end_height(self, height: int) -> None:
        """The fsync'd end-of-height barrier (reference:
        EndHeightMessage, state.go:1901-1911)."""
        self.write_sync({"type": "end_height", "height": height})

    def close(self) -> None:
        try:
            self.flush_and_sync()
        except ValueError:
            pass
        self._f.close()

    # ------------------------------------------------------------------
    @staticmethod
    def group_files(path: str) -> list[str]:
        """Rotated files (oldest first) + the head file, existing only."""
        d = os.path.dirname(path) or "."
        base = os.path.basename(path)
        rotated = []
        if os.path.isdir(d):
            for name in os.listdir(d):
                if name.startswith(base + "."):
                    suffix = name[len(base) + 1:]
                    if suffix.isdigit():
                        rotated.append(os.path.join(d, name))
        rotated.sort(key=lambda f: int(f.rsplit(".", 1)[1]))
        out = rotated
        if os.path.exists(path):
            out = rotated + [path]
        return out

    @staticmethod
    def iter_group(path: str, strict: bool = False) -> Iterator[dict]:
        """All messages across the rotated group, oldest first."""
        for f in WAL.group_files(path):
            yield from WAL.iter_messages(f, strict=strict)

    @staticmethod
    def iter_messages(path: str, strict: bool = False) -> Iterator[dict]:
        """Decode records; on a torn tail (crash mid-write) stop unless
        strict."""
        for msg, _ in WAL.iter_frames(path, strict=strict):
            yield msg

    @staticmethod
    def iter_frames(path: str, strict: bool = False,
                    decode: Callable[[bytes], Any] = decode_record
                    ) -> Iterator[tuple[Any, int]]:
        """(record, bytes of its frame) for one file, read a frame at
        a time: a playback of a long WAL holds one record, not the
        file.  ``decode`` makes the record of a payload (``bytes``:
        the payload itself).  A torn tail (crash mid-write: a header or
        payload cut short) is a clean stop unless strict; a whole frame
        that fails its CRC or claims more than the size limit is
        corruption, always an error — the rule of
        ``_scan_valid_prefix``."""
        with open(path, "rb") as f:
            pos = 0
            while True:
                head = f.read(8)
                if not head:
                    return
                if len(head) == 8:
                    crc, length = struct.unpack(">II", head)
                    if length > MAX_MSG_SIZE_BYTES:
                        raise CorruptWALError(
                            f"frame too large: {length}")
                    payload = f.read(length)
                    if len(payload) == length:
                        if zlib.crc32(payload) & 0xFFFFFFFF != crc:
                            raise CorruptWALError(
                                f"crc mismatch at offset {pos}")
                        yield decode(payload), 8 + length
                        pos += 8 + length
                        continue
                if strict:
                    raise CorruptWALError("truncated frame")
                return

    @staticmethod
    def search_for_end_height(path: str, height: int
                              ) -> Optional[list[dict]]:
        """Messages AFTER the end-height marker for `height`, or None if
        the marker is absent (reference: SearchForEndHeight).  No
        message before the marker is decoded."""
        if not WAL.group_files(path):
            return None
        found = False
        out: list[dict] = []
        for f in WAL.group_files(path):
            for payload, _ in WAL.iter_frames(f, decode=bytes):
                if found:
                    out.append(decode_record(payload))
                elif _ends_height(payload, height):
                    found = True
        return out if found else None


def _scan_valid_prefix(data: bytes) -> int:
    """Byte offset of the first invalid frame (== len(data) when all
    frames are intact).  THE corruption rule — iter_messages and repair
    share it so replay and repair always agree on the cut point."""
    pos = 0
    n = len(data)
    while pos < n:
        if n - pos < 8:
            return pos
        crc, length = struct.unpack(">II", data[pos:pos + 8])
        if length > MAX_MSG_SIZE_BYTES or n - pos - 8 < length:
            return pos
        payload = data[pos + 8:pos + 8 + length]
        if zlib.crc32(payload) & 0xFFFFFFFF != crc:
            return pos
        pos += 8 + length
    return pos


def repair_wal_file(path: str) -> int:
    """Repair the WAL GROUP: truncate the first file containing a
    corrupt frame and drop every later file — nothing after a corrupt
    frame can be trusted as a contiguous record (reference:
    consensus/wal.go repair driven by state.go OnStart's corruption
    retry).  Corrupt content is stashed in .corrupted files.  Returns
    bytes dropped."""
    import shutil
    dropped = 0
    cut = False
    for f_path in WAL.group_files(path):
        if cut:
            dropped += os.path.getsize(f_path)
            shutil.move(f_path, f_path + ".corrupted")
            continue
        with open(f_path, "rb") as f:
            data = f.read()
        good = _scan_valid_prefix(data)
        if good < len(data):
            cut = True
            dropped += len(data) - good
            shutil.copy(f_path, f_path + ".corrupted")
            with open(f_path, "r+b") as f:
                f.truncate(good)
    # the head file must exist for reopen even if it was dropped
    if not os.path.exists(path):
        open(path, "ab").close()
    return dropped


class NilWAL:
    """No-op WAL (reference: nilWAL)."""
    path = ""

    def write(self, msg: dict) -> None:
        pass

    def write_sync(self, msg: dict) -> None:
        pass

    def flush_and_sync(self) -> None:
        pass

    def write_end_height(self, height: int) -> None:
        pass

    def close(self) -> None:
        pass
