"""Persistent slate store — the role Cassandra plays in paper section 4.2.

Slates are compressed before they are stored (zstd, zlib fallback: "our
applications often use JSON ... so Muppet compresses each slate before
storing it"), here a whole flush at a time.  The store simulates a
replicated cluster: N replica directories, write quorum W and read quorum
R (the paper's ONE / QUORUM / ALL knob), and per-write TTL with garbage
collection.

Layout: ``root/replica_<i>/<updater>/<seq>-<rows>.blk``.  A block holds
one write's rows as columns — ``key``, ``ts``, ``ttl``, ``seq`` and the
slate tree's leaves as ``[rows, ...]`` arrays — sorted by key with one
row per key, compressed as one frame.  ``seq`` is the store's write
sequence, so a flush of R rows costs O(R) whatever the store holds.  A
block is written under a temporary name and renamed, so readers see it
only once it is whole; blocks are never rewritten.

Per key the newest row wins: the largest ``ts``, then the largest
``seq`` (the later write), then the later row of one write.  Reads
merge the blocks' columns with numpy.  Compaction is size-tiered, as
Cassandra's default strategy: once ``TIER_BLOCKS`` blocks of one size
class (``floor(log4(rows))``) exist, they are merged into one, so the
blocks a read opens stay O(log rows) however many flushes ran.  A
merged block takes the largest ``seq`` of its inputs, so a block's
``seq`` is always the newest write it holds.

A flush frontier commits the writes up to a ``seq``: ``seal(seq)``
lets merges take only blocks up to it, so a merge never folds a write
that may yet be rolled back into committed rows, and
``discard_after(seq)`` rolls back the blocks a crashed run wrote past
its last frontier.
"""
from __future__ import annotations

import os
import struct
import threading
from dataclasses import dataclass
from typing import Any, Dict, List, Optional, Tuple

import msgpack
import numpy as np
from repro.slates import _compress

TIER_BLOCKS = 4      # blocks of one size class that are merged into one
_SUFFIX = ".blk"


def _flatten(tree, prefix="") -> List[Tuple[str, Any]]:
    if isinstance(tree, dict):
        out = []
        for k in sorted(tree):
            out.extend(_flatten(tree[k], f"{prefix}{k}/"))
        return out
    return [(prefix.rstrip("/"), tree)]


def _unflatten(flat):
    out: Dict[str, Any] = {}
    for k, v in flat:
        parts = k.split("/")
        cur = out
        for p in parts[:-1]:
            cur = cur.setdefault(p, {})
        cur[parts[-1]] = v
    if list(out.keys()) == [""]:
        return out[""]
    return out


@dataclass
class Rows:
    """Rows as columns: ``key``, ``ts``, ``ttl`` and ``seq`` (``[n]``
    int64) and the slate tree's leaves, ``(path, [n, ...] array)`` in
    path order."""
    key: np.ndarray
    ts: np.ndarray
    ttl: np.ndarray
    seq: np.ndarray
    leaves: List[Tuple[str, np.ndarray]]

    def __len__(self) -> int:
        return int(self.key.size)

    def signature(self):
        return tuple((p, a.dtype.str, a.shape[1:]) for p, a in self.leaves)

    def take(self, idx) -> "Rows":
        return Rows(self.key[idx], self.ts[idx], self.ttl[idx],
                    self.seq[idx], [(p, a[idx]) for p, a in self.leaves])

    def slates(self):
        """The slate tree with ``[n, ...]`` leaves."""
        return _unflatten(self.leaves)

    def slate(self, i: int):
        return _unflatten([(p, np.array(a[i])) for p, a in self.leaves])

    @staticmethod
    def concat(parts: List["Rows"]) -> "Rows":
        if len(parts) == 1:
            return parts[0]
        sig = parts[0].signature()
        if any(p.signature() != sig for p in parts[1:]):
            raise ValueError("one slate structure per updater: leaves, "
                             "dtypes or shapes differ between writes")
        cat = lambda f: np.concatenate([f(p) for p in parts])  # noqa: E731
        return Rows(cat(lambda p: p.key), cat(lambda p: p.ts),
                    cat(lambda p: p.ttl), cat(lambda p: p.seq),
                    [(path, cat(lambda p, j=j: p.leaves[j][1]))
                     for j, (path, _) in enumerate(parts[0].leaves)])


def newest(key: np.ndarray, ts: np.ndarray, seq: np.ndarray) -> np.ndarray:
    """Indices of the newest row of each key, in key order: the largest
    ``ts``, then the largest ``seq``, then the last row.  One stable sort
    by key (runs of sorted blocks merge in near-linear time) and
    segmented maxima."""
    n = key.size
    if n == 0:
        return np.zeros(0, np.int64)
    order = np.argsort(key, kind="stable")
    k, t, s = key[order], ts[order], seq[order]
    first = np.ones(n, bool)
    first[1:] = k[1:] != k[:-1]
    starts = np.nonzero(first)[0]
    sizes = np.diff(np.append(starts, n))
    top = t == np.repeat(np.maximum.reduceat(t, starts), sizes)
    s_top = np.where(top, s, np.iinfo(np.int64).min)
    win = top & (s == np.repeat(np.maximum.reduceat(s_top, starts), sizes))
    w = np.nonzero(win)[0]
    group = np.cumsum(first)[w]
    last = np.ones(w.size, bool)
    last[:-1] = group[1:] != group[:-1]
    return order[w[last]]


def expired(rows: Rows, now: Optional[int]) -> np.ndarray:
    """Rows whose TTL ran out by ``now`` (none when ``now`` is None)."""
    if now is None:
        return np.zeros(len(rows), bool)
    return (rows.ttl > 0) & (now - rows.ts > rows.ttl)


def _tier(rows: int) -> int:
    """Size class of a block: ``floor(log4(rows))``."""
    return (max(rows, 1).bit_length() - 1) // 2


def _alive(pid: int) -> bool:
    try:
        os.kill(pid, 0)
    except ProcessLookupError:
        return False
    except PermissionError:
        pass
    return True


class KVStore:
    """Replicated, compressed, columnar key-value store for slates (the
    layout and the newest-wins rule are in the module docstring)."""

    def __init__(self, root: str, *, replicas: int = 3, write_quorum: int = 2,
                 read_quorum: int = 2, flush_buffer: int = 1024):
        if not (1 <= write_quorum <= replicas and 1 <= read_quorum <= replicas):
            raise ValueError(f"quorums W={write_quorum} R={read_quorum} "
                             f"need 1 <= W, R <= {replicas} replicas")
        self.root = root
        self.replicas = replicas
        self.write_quorum = write_quorum
        self.read_quorum = read_quorum
        # _lock: pending rows, the counters, which block files exist;
        # _merge_lock: one merge (compaction or gc) at a time
        self._lock = threading.Lock()
        self._merge_lock = threading.Lock()
        self._pending: Dict[str, List[Rows]] = {}
        self._n_pending = 0
        self._flush_buffer = flush_buffer
        self._replica_down = [False] * replicas
        self._sealed: Optional[int] = None   # merges take seq <= this
        self.bytes_written = 0      # blocks of writes, over replicas
        self.bytes_compacted = 0    # blocks of merges, over replicas
        os.makedirs(root, exist_ok=True)
        seqs = [0]
        for dirpath, _, files in os.walk(root):
            for fn in files:
                if fn.endswith(_SUFFIX):
                    seqs.append(int(fn.split("-")[0]))
                elif fn.endswith(".tmp") and not _alive(
                        int(fn.rsplit(".", 2)[-2])):
                    # a block a crashed writer never renamed
                    os.remove(os.path.join(dirpath, fn))
        self._seq = max(seqs) + 1

    @property
    def last_seq(self) -> int:
        """The ``seq`` of the newest write (0 before any)."""
        with self._lock:
            return self._seq - 1

    def seal(self, seq: int):
        """Let merges take the blocks up to ``seq`` (a committed flush
        frontier's); newer blocks stay as written until sealed."""
        with self._lock:
            self._sealed = int(seq)

    def discard_after(self, seq: int) -> int:
        """Remove every block newer than ``seq``, on every replica: the
        writes a crashed run made past its last frontier.  Returns how
        many block files went."""
        removed = 0
        with self._merge_lock, self._lock:
            for i in range(self.replicas):
                d = os.path.join(self.root, f"replica_{i}")
                for updater in os.listdir(d) if os.path.isdir(d) else []:
                    for s, _, path in self._blocks(i, updater):
                        if s > seq:
                            os.remove(path)
                            removed += 1
            self._seq = min(self._seq, int(seq) + 1)
        return removed

    def _mergeable(self, blocks):
        return [b for b in blocks
                if self._sealed is None or b[0] <= self._sealed]

    # ---- fault injection (simulated replica failures) ----
    def set_replica_down(self, i: int, down: bool = True):
        self._replica_down[i] = down

    def _alive_replicas(self) -> List[int]:
        return [i for i in range(self.replicas) if not self._replica_down[i]]

    # ---- files ----
    def _dir(self, replica: int, updater: str) -> str:
        return os.path.join(self.root, f"replica_{replica}", updater)

    def _blocks(self, replica: int, updater: str) -> List[Tuple[int, int, str]]:
        """``(seq, rows, path)`` of the updater's blocks, oldest first."""
        d = self._dir(replica, updater)
        if not os.path.isdir(d):
            return []
        out = []
        for fn in os.listdir(d):
            if fn.endswith(_SUFFIX):
                seq, rows = fn[:-len(_SUFFIX)].split("-")
                out.append((int(seq), int(rows), os.path.join(d, fn)))
        return sorted(out)

    @staticmethod
    def _encode(rows: Rows) -> bytes:
        head = msgpack.packb({"n": len(rows), "leaves": [
            [p, a.dtype.str, list(a.shape[1:])] for p, a in rows.leaves]})
        cols = [rows.key, rows.ts, rows.ttl, rows.seq] + \
            [a for _, a in rows.leaves]
        # a codec context per block: contexts are not thread-safe, and
        # the flusher's merges run beside reads and writes of others
        return _compress.Compressor(level=3).compress(b"".join(
            [struct.pack("<I", len(head)), head]
            + [np.ascontiguousarray(a).tobytes() for a in cols]))

    @staticmethod
    def _decode(blob: bytes) -> Rows:
        raw = _compress.Decompressor().decompress(blob)
        (hl,) = struct.unpack_from("<I", raw)
        head = msgpack.unpackb(raw[4:4 + hl])
        n, off = head["n"], 4 + hl

        def col(dtype, shape=()):
            nonlocal off
            a = np.frombuffer(raw, np.dtype(dtype), int(np.prod(shape)) * n,
                              off).reshape((n, *shape))
            off += a.nbytes
            return a
        meta = [col(np.int64) for _ in range(4)]
        return Rows(*meta, [(p, col(dt, tuple(sh)))
                            for p, dt, sh in head["leaves"]])

    def _read_blocks(self, replica: int, updater: str) -> List[Rows]:
        while True:
            # listed under the lock a merge holds while it swaps its
            # block in, so a listing never misses both sides of a swap
            with self._lock:
                blocks = self._blocks(replica, updater)
            try:
                out = []
                for _, _, path in blocks:
                    with open(path, "rb") as f:
                        out.append(self._decode(f.read()))
                return out
            except FileNotFoundError:
                # a merge renamed its block in, then removed a listed
                # input: the next listing holds the merged block
                continue

    def _put_file(self, replica: int, updater: str, seq: int, n: int,
                  data: bytes) -> Tuple[str, str]:
        """Write ``data`` under a temporary name; ``(tmp, final)``."""
        d = self._dir(replica, updater)
        os.makedirs(d, exist_ok=True)
        path = os.path.join(d, f"{seq:016d}-{n}{_SUFFIX}")
        tmp = f"{path}.{os.getpid()}.tmp"
        with open(tmp, "wb") as f:
            f.write(data)
        return tmp, path

    # ---- write path ----
    def put(self, updater: str, key: int, slate, *, ts: int,
            ttl: int = 0) -> int:
        """Buffer one slate; returns the bytes written (0 while the rows
        stay buffered)."""
        leaves = [(p, np.asarray(v)[None]) for p, v in _flatten(slate)]
        return self._buffer(updater, Rows(
            np.asarray([key], np.int64), np.asarray([ts], np.int64),
            np.asarray([ttl], np.int64), np.zeros(1, np.int64), leaves))

    def put_many(self, updater: str, keys, slates, *, ts,
                 ttl: int = 0) -> int:
        """Buffer rows given as columns: ``keys`` ``[n]``, ``slates`` a
        tree of ``[n, ...]`` arrays, ``ts`` one write tick or ``[n]``
        (each slate's own last-update tick, so TTL expiry and newest-wins
        reads stay per-key exact across flushes).  Returns the bytes
        written."""
        key = np.asarray(keys, np.int64).reshape(-1)
        n = key.size
        leaves = [(p, np.asarray(v)) for p, v in _flatten(slates)]
        if any(a.shape[:1] != (n,) for _, a in leaves):
            raise ValueError(f"every slate leaf needs {n} rows")
        return self._buffer(updater, Rows(
            key, np.broadcast_to(np.asarray(ts, np.int64), (n,)),
            np.full(n, ttl, np.int64), np.zeros(n, np.int64), leaves))

    def _buffer(self, updater: str, rows: Rows) -> int:
        if not len(rows):
            return 0
        with self._lock:
            parts = self._pending.setdefault(updater, [])
            if parts and parts[0].signature() != rows.signature():
                raise ValueError("one slate structure per updater: "
                                 "leaves, dtypes or shapes differ")
            parts.append(rows)
            self._n_pending += len(rows)
            if self._n_pending >= self._flush_buffer:
                return self._flush_locked()
        return 0

    def flush(self) -> int:
        """Write the buffered rows, one block per updater on every live
        replica; returns the bytes written."""
        with self._lock:
            return self._flush_locked()

    def _flush_locked(self) -> int:
        pending, self._pending, self._n_pending = self._pending, {}, 0
        total = 0
        for updater, parts in pending.items():
            rows = Rows.concat(parts)
            seq, self._seq = self._seq, self._seq + 1
            rows.seq = np.full(len(rows), seq, np.int64)
            rows = rows.take(newest(rows.key, rows.ts, rows.seq))
            data = self._encode(rows)
            written = 0
            for i in self._alive_replicas():
                tmp, path = self._put_file(i, updater, seq, len(rows), data)
                os.replace(tmp, path)
                written += 1
            if written < self.write_quorum:
                raise IOError(f"write quorum failed "
                              f"({written}/{self.write_quorum})")
            self.bytes_written += written * len(data)
            total += written * len(data)
        return total

    # ---- read path ----
    def get(self, updater: str, key: int, *, now: Optional[int] = None):
        """Quorum read: the newest row among ``read_quorum`` replicas;
        an expired one (TTL) reads as missing."""
        self.flush()
        key = int(key)
        best = None                       # (ts, seq, rows, row)
        seen = 0
        for i in self._alive_replicas():
            for rows in self._read_blocks(i, updater):
                j = int(np.searchsorted(rows.key, key))
                if j < len(rows) and rows.key[j] == key:
                    cand = (int(rows.ts[j]), int(rows.seq[j]), rows, j)
                    if best is None or cand[:2] >= best[:2]:
                        best = cand
            seen += 1
            if seen >= self.read_quorum:
                break
        if seen < self.read_quorum:
            raise IOError(f"read quorum failed ({seen}/{self.read_quorum})")
        if best is None:
            return None
        _, _, rows, j = best
        if expired(rows.take([j]), now)[0]:
            return None
        return rows.slate(j)

    def _scan(self, updater: str, now: Optional[int]) -> Optional[Rows]:
        self.flush()
        parts = [rows for i in self._alive_replicas()
                 for rows in self._read_blocks(i, updater)]
        if not parts:
            return None
        rows = Rows.concat(parts)
        rows = rows.take(newest(rows.key, rows.ts, rows.seq))
        dead = expired(rows, now)
        return rows.take(~dead) if dead.any() else rows

    def scan_columns(self, updater: str, *, now: Optional[int] = None
                     ) -> Tuple[np.ndarray, np.ndarray, Any]:
        """Every live slate as columns, in key order: ``(keys [n], ts
        [n], slates)`` with ``slates`` a tree of ``[n, ...]`` arrays
        (None when the updater has none).  Recovery restores tables from
        these without touching a row in Python."""
        rows = self._scan(updater, now)
        if rows is None:
            return np.zeros(0, np.int64), np.zeros(0, np.int64), None
        return rows.key, rows.ts, rows.slates()

    def scan_records(self, updater: str, *, now: Optional[int] = None
                     ) -> Dict[int, Tuple[int, Any]]:
        """Like ``scan`` but returns ``{key: (ts, slate)}`` — each
        slate's write tick, for per-slot TTL clocks."""
        rows = self._scan(updater, now)
        if rows is None:
            return {}
        return {k: (t, rows.slate(i)) for i, (k, t) in enumerate(
            zip(rows.key.tolist(), rows.ts.tolist()))}

    def scan(self, updater: str, *, now: Optional[int] = None):
        """Bulk read of every live slate (paper section 5 'bulk reading of
        slates')."""
        return {k: slate
                for k, (_, slate) in self.scan_records(updater,
                                                       now=now).items()}

    # ---- maintenance ----
    def _merge(self, replica: int, updater: str, blocks, *,
               now: Optional[int] = None) -> int:
        """Replace ``blocks`` of one replica by one block of their newest
        rows, without the rows expired by ``now``, under the largest
        ``seq`` of the inputs; returns how many keys expired.  Writes
        nothing when a merge of expiry only finds none."""
        parts = []
        for _, _, path in blocks:
            with open(path, "rb") as f:
                parts.append(self._decode(f.read()))
        rows = Rows.concat(parts)
        rows = rows.take(newest(rows.key, rows.ts, rows.seq))
        dead = expired(rows, now)
        n_dead = int(dead.sum())
        if now is not None and not n_dead:
            return 0
        rows = rows.take(~dead)
        tmp = data = path = None
        if len(rows):
            data = self._encode(rows)
            tmp, path = self._put_file(replica, updater,
                                       max(b[0] for b in blocks), len(rows),
                                       data)
        with self._lock:
            if tmp is not None:
                # over the newest input itself when the row counts agree
                os.replace(tmp, path)
                self.bytes_compacted += len(data)
            for _, _, old in blocks:
                if old != path:
                    os.remove(old)
        return n_dead

    def compact(self):
        """Merge every size class that holds ``TIER_BLOCKS`` blocks, on
        every live replica (the flusher's merge thread calls this after
        writes)."""
        with self._merge_lock:
            for i in self._alive_replicas():
                d = os.path.join(self.root, f"replica_{i}")
                for updater in sorted(os.listdir(d)) if os.path.isdir(d) \
                        else []:
                    while True:
                        tiers: Dict[int, list] = {}
                        for b in self._mergeable(self._blocks(i, updater)):
                            tiers.setdefault(_tier(b[1]), []).append(b)
                        full = [t for t in tiers.values()
                                if len(t) >= TIER_BLOCKS]
                        if not full:
                            break
                        self._merge(i, updater, full[0])

    def gc(self, updater: str, *, now: int) -> int:
        """Drop expired rows (the store-side TTL GC of section 4.2):
        merges each live replica's blocks of ``updater`` into one without
        the keys whose newest row expired, once every block is sealed (a
        partial merge could let an older row with a longer TTL
        resurface); returns how many were dropped, summed over
        replicas."""
        removed = 0
        with self._merge_lock:
            for i in self._alive_replicas():
                blocks = self._blocks(i, updater)
                if blocks and len(self._mergeable(blocks)) == len(blocks):
                    removed += self._merge(i, updater, blocks, now=now)
        return removed
