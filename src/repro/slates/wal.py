"""Write-ahead log of source event batches (beyond-paper extension).

Paper section 4.3: "Developing a replay capability to recover the lost
events in the queue is a subject of future work."  This is that future
work: the ingest path appends every source batch (per tick) to a zstd
frame log; after a crash, ``replay`` re-feeds batches from the last
flush frontier.  Associative updaters make replay exactly-once-by-merge
when combined with slate snapshots at flush boundaries (DESIGN.md
section 10).

Offsets are *logical*: every record has a stable byte offset that
survives ``truncate_before`` (the file carries a header recording the
logical offset of its first record), so a flush frontier's
``wal_offset`` stays valid after the log is compacted.  Files written by
older versions (no header) read back with base offset 0.
"""
from __future__ import annotations

import os
import struct
from typing import Dict, Iterator, Optional, Tuple

import msgpack
import numpy as np
from repro.slates import _compress

from repro.core.event import EventBatch

_MAGIC = b"MWAL"
_HDR_MAGIC = b"MWH1"
_HDR_LEN = 12           # magic + u64 logical base offset


def _enc(a):
    a = np.asarray(a)
    return {b"d": a.tobytes(), b"t": a.dtype.str, b"s": list(a.shape)}


def _dec(e):
    return np.frombuffer(e[b"d"], np.dtype(e[b"t"])).reshape(e[b"s"])


class WriteAheadLog:
    """Append-only log of ``(tick, {stream: EventBatch})`` records.

    ``append`` returns the logical end offset after the record — the
    replay point for a frontier recorded *after* that tick.  ``sync=True``
    fsyncs every append (durable against power loss, slower); the default
    flushes to the OS (durable against process crash, the failure model
    of the recovery tests).
    """

    def __init__(self, path: str, *, sync: bool = False,
                 level: Optional[int] = None):
        self.path = path
        self.sync = sync
        os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
        # append sits on the ingest hot path: zstd-1 when available,
        # raw frames under the zlib fallback (zlib-1 alone costs ~15%
        # of a 256-event tick).  Frames are tagged, so a log written at
        # one level replays anywhere.
        if level is None:
            level = 1 if _compress.HAVE_ZSTD else 0
        self._cctx = _compress.Compressor(level=level)
        self._dctx = _compress.Decompressor()
        self._base, self._hdr_len = self._read_header()
        if not os.path.exists(path) or os.path.getsize(path) == 0:
            with open(path, "wb") as f:
                f.write(_HDR_MAGIC + struct.pack("<Q", 0))
            self._base, self._hdr_len = 0, _HDR_LEN
        self._trim_torn_tail()
        self._f = open(path, "ab")
        self._end = self._base + os.path.getsize(path) - self._hdr_len
        self.bytes_written = 0      # appended by this handle

    # ---- offsets ----
    def _read_header(self) -> Tuple[int, int]:
        """(logical base offset, physical header length)."""
        if not os.path.exists(self.path):
            return 0, 0
        with open(self.path, "rb") as f:
            head = f.read(_HDR_LEN)
        if len(head) >= _HDR_LEN and head[:4] == _HDR_MAGIC:
            return struct.unpack("<Q", head[4:12])[0], _HDR_LEN
        return 0, 0   # legacy headerless file

    def _trim_torn_tail(self):
        """Cut a half-written record left by a crash mid-append, so the
        next append starts on a clean boundary."""
        size = os.path.getsize(self.path)
        with open(self.path, "rb") as f:
            f.seek(self._hdr_len)
            good = self._hdr_len
            while True:
                hdr = f.read(8)
                if len(hdr) < 8 or hdr[:4] != _MAGIC:
                    break
                (n,) = struct.unpack("<I", hdr[4:])
                if f.seek(n, 1) > size or f.tell() > size:
                    break
                good = f.tell()
        if good < size:
            with open(self.path, "r+b") as f:
                f.truncate(good)

    @property
    def offset(self) -> int:
        """Logical end offset (replay point for 'everything from now').
        Tracked incrementally — the append hot path must not stat."""
        return self._end

    # ---- write path ----
    def append(self, tick: int, sources: Dict[str, EventBatch]) -> int:
        payload = {}
        for stream, b in sources.items():
            payload[stream] = {
                "sid": _enc(b.sid), "ts": _enc(b.ts), "key": _enc(b.key),
                "valid": _enc(b.valid),
                "value": {k: _enc(v) for k, v in _flat(b.value)},
            }
        raw = self._cctx.compress(msgpack.packb({"tick": int(tick),
                                                 "src": payload}))
        self._f.write(_MAGIC + struct.pack("<I", len(raw)) + raw)
        self._f.flush()
        if self.sync:
            os.fsync(self._f.fileno())
        self._end += 8 + len(raw)
        self.bytes_written += 8 + len(raw)
        return self._end

    def close(self):
        self._f.close()

    # ---- compaction ----
    def truncate_before(self, offset: int):
        """Drop records wholly before logical ``offset`` (typically the
        flush frontier's wal_offset: those events are already reflected
        in flushed slates and will never be replayed).  Logical offsets
        of surviving records are unchanged."""
        if offset <= self._base:
            return
        end = self.offset
        if offset > end:
            raise ValueError(f"truncate offset {offset} beyond log end "
                             f"{end}")
        # frontier offsets come from append(), so they sit on record
        # boundaries; a mid-record offset drops the straddling record
        keep = []
        new_base = self._base
        for rec_off, rec_len, blob in self._iter_raw():
            if rec_off >= offset:
                keep.append(blob)
            else:
                new_base = rec_off + rec_len
        self._f.close()
        tmp = self.path + ".tmp"
        with open(tmp, "wb") as f:
            f.write(_HDR_MAGIC + struct.pack("<Q", new_base))
            for blob in keep:
                f.write(blob)
        os.replace(tmp, self.path)
        self._base, self._hdr_len = new_base, _HDR_LEN
        self._f = open(self.path, "ab")
        self._end = self._base + os.path.getsize(self.path) - _HDR_LEN

    # ---- read path ----
    def _iter_raw(self) -> Iterator[Tuple[int, int, bytes]]:
        """(logical offset, record length, raw record bytes) per record."""
        self._f.flush()
        with open(self.path, "rb") as f:
            f.seek(self._hdr_len)
            off = self._base
            while True:
                hdr = f.read(8)
                if len(hdr) < 8:
                    return
                assert hdr[:4] == _MAGIC, "corrupt WAL"
                (n,) = struct.unpack("<I", hdr[4:])
                body = f.read(n)
                if len(body) < n:
                    return   # torn tail write (crash mid-append): ignore
                yield off, 8 + n, hdr + body
                off += 8 + n

    def replay(self, from_tick: int = 0, *,
               from_offset: Optional[int] = None
               ) -> Iterator[Tuple[int, Dict[str, EventBatch]]]:
        """Yield ``(tick, sources)`` records.

        ``from_offset`` (logical, e.g. a frontier's wal_offset) skips
        records below it without decoding them; ``from_tick`` further
        filters by tick.  An offset below the truncation base starts at
        the first surviving record.
        """
        for off, _, blob in self._iter_raw():
            if from_offset is not None and off < from_offset:
                continue
            rec = msgpack.unpackb(self._dctx.decompress(blob[8:]),
                                  strict_map_key=False)
            if rec["tick"] < from_tick:
                continue
            out = {}
            for stream, b in rec["src"].items():
                sname = stream if isinstance(stream, str) \
                    else stream.decode()
                value = _unflat({(k if isinstance(k, str)
                                  else k.decode()): _dec(v)
                                 for k, v in b["value"].items()})
                out[sname] = EventBatch(
                    sid=_dec(b["sid"]), ts=_dec(b["ts"]),
                    key=_dec(b["key"]), value=value,
                    valid=_dec(b["valid"]))
            yield rec["tick"], out


def _flat(tree, prefix=""):
    if isinstance(tree, dict):
        out = []
        for k in sorted(tree):
            out.extend(_flat(tree[k], f"{prefix}{k}/"))
        return out
    return [(prefix.rstrip("/"), tree)]


def _unflat(flat: Dict[str, np.ndarray]):
    out = {}
    for k, v in flat.items():
        parts = k.split("/")
        cur = out
        for p in parts[:-1]:
            cur = cur.setdefault(p, {})
        cur[parts[-1]] = v
    return out
