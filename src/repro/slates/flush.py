"""Slate cache <-> KV store synchronization.

Implements the paper's flush knob ("immediate write-through" ...
"only when evicted from cache"), background-thread flushing (the Muppet
2.0 background-I/O thread, so the update hot loop never blocks on the
store), read-through restore after a crash, and the *flush frontier*
(DESIGN.md section 10): the durable ``(tick, wal_offset)`` watermark
from which WAL replay resumes after recovery.
"""
from __future__ import annotations

import enum
import json
import os
import queue as pyqueue
import threading
from dataclasses import dataclass, replace
from typing import Dict, List, Optional, Sequence, Union

import jax
import jax.numpy as jnp
import numpy as np

from repro.slates import table as tbl
from repro.slates.kvstore import KVStore, newest
from repro.telemetry.trace import span


class FlushPolicy(enum.Enum):
    IMMEDIATE = "immediate"    # write-through every tick
    EVERY_K = "every_k"        # every k ticks
    ON_EVICT = "on_evict"      # only under table pressure / TTL expiry


@dataclass
class FlushConfig:
    policy: FlushPolicy = FlushPolicy.EVERY_K
    every_k: int = 16
    occupancy_evict: float = 0.85   # ON_EVICT pressure threshold


class FlushError(RuntimeError):
    """One or more background flush writes failed; ``.errors`` holds the
    underlying exceptions in arrival order."""

    def __init__(self, errors: Sequence[BaseException]):
        self.errors = list(errors)
        super().__init__(
            f"{len(self.errors)} flush write(s) failed: "
            f"{self.errors[0]!r}")


# ---------------------------------------------------------------------------
# flush frontier: the durable replay watermark
# ---------------------------------------------------------------------------

@dataclass
class FlushFrontier:
    """Everything before ``tick`` / ``wal_offset`` is durably reflected
    in the KV store; recovery restores slates and replays the WAL from
    here.  ``wal_offset`` is an int (single shard) or a per-shard list
    (DistributedEngine: one WAL per shard, one barrier tick).
    ``store_seq`` is the store's newest write the frontier covers: blocks
    past it belong to a flush whose frontier was never saved.  ``meta``
    is an opaque json-serializable driver cursor (e.g. the source index
    at the boundary) that survives even full WAL truncation."""

    tick: int = 0
    wal_offset: Union[int, List[int]] = 0
    meta: Optional[dict] = None
    store_seq: int = 0

    def save(self, path: str):
        tmp = path + ".tmp"
        with open(tmp, "w") as f:
            json.dump({"tick": int(self.tick),
                       "wal_offset": self.wal_offset,
                       "meta": self.meta,
                       "store_seq": int(self.store_seq)}, f)
        os.replace(tmp, path)   # atomic: a crash mid-save keeps the old
                                # frontier, replay just covers more ticks

    @staticmethod
    def load(path: str) -> Optional["FlushFrontier"]:
        if not os.path.exists(path):
            return None
        with open(path) as f:
            d = json.load(f)
        return FlushFrontier(tick=int(d["tick"]),
                             wal_offset=d["wal_offset"],
                             meta=d.get("meta"),
                             store_seq=int(d.get("store_seq", 0)))


# rows a snapshot gathers on the device per call: one compiled gather per
# table shape, whatever the number of dirty rows
SNAPSHOT_ROWS = 1 << 16


def begin_dirty_snapshot(table: tbl.SlateTable):
    """Start the device->host fetch for a flush snapshot.

    Only the dirty occupied rows leave the device: the slots' dirty bits,
    packed eight to a byte, come to the host (one sync, 1/8 byte a
    slot), their indices are taken there, and the rows are gathered on
    the device in calls of ``SNAPSHOT_ROWS`` (the last one padded) whose
    host transfer is kicked off asynchronously.  The gathers read the
    table before the next chunk's donation deletes its buffers, so the
    token stays valid; :func:`finish_dirty_snapshot` resolves it to host
    rows whenever the driver is ready — typically after the next chunk
    has been dispatched, so the transfer and the serialization behind it
    overlap device compute.  Returns ``(token, cleared_table)``; the
    cleared table (dirty bits dropped) is usable immediately."""
    idx = _set_bits(np.asarray(jax.device_get(
        _dirty_bits(table.dirty, table.keys))), table.capacity)
    step = min(SNAPSHOT_ROWS, table.capacity)
    parts = []
    for lo in range(0, idx.size, step):
        part = np.zeros(step, np.int32)
        part[:min(step, idx.size - lo)] = idx[lo:lo + step]
        rows = _gather_rows(table.keys, table.ts, table.vals, part)
        for leaf in jax.tree.leaves(rows):
            leaf.copy_to_host_async()
        parts.append(rows)
    none = jax.tree.map(lambda a: np.zeros((0,) + a.shape[1:], a.dtype),
                        (table.keys, table.ts, table.vals))
    cleared = replace(table, dirty=jnp.zeros_like(table.dirty))
    return (idx.size, parts, none), cleared


@jax.jit
def _dirty_bits(dirty, keys):
    return jnp.packbits(dirty & (keys != tbl.EMPTY), bitorder="little")


@jax.jit
def _gather_rows(keys, ts, vals, idx):
    return keys[idx], ts[idx], jax.tree.map(lambda v: v[idx], vals)


def _set_bits(packed: np.ndarray, n: int) -> np.ndarray:
    """Indices of the set bits among the first ``n`` of ``packed``
    (little bit order), ascending: the nonzero 64-bit words are found
    first, so the work follows the set bits more than ``n``."""
    packed = np.concatenate([packed, np.zeros(-packed.size % 8, np.uint8)])
    words = packed.view("<u8")
    nz = np.flatnonzero(words)
    bits = np.flatnonzero(np.unpackbits(words[nz].view(np.uint8),
                                        bitorder="little"))
    idx = nz[bits // 64] * 64 + bits % 64
    return idx[idx < n].astype(np.int32)


def finish_dirty_snapshot(token):
    """Resolve an in-flight snapshot to host ``(keys, ts, vals)`` of the
    dirty occupied slots, in slot order (the flusher's row format)."""
    n, parts, none = token
    return jax.tree.map(lambda *xs: np.concatenate(xs)[:n], none,
                        *jax.device_get(parts))


def dirty_snapshot(table: tbl.SlateTable):
    """Host copies of (keys, ts, slates) for dirty slots, and the cleared
    table — the synchronous begin+finish composition; serialization and
    disk I/O still run on the flusher thread."""
    token, cleared = begin_dirty_snapshot(table)
    keys, ts, vals = finish_dirty_snapshot(token)
    return keys, ts, vals, cleared


def restore_into(table: tbl.SlateTable, keys: np.ndarray, slates,
                 ts: np.ndarray) -> tbl.SlateTable:
    """Re-insert flushed slates after a crash (read-through warm-up).

    ``ts`` is per-key (each slate's last-update tick, as recorded by the
    store): restoring per-slot timestamps keeps TTL eviction after
    recovery identical to the pre-crash schedule.  Idempotent: keys
    already present are overwritten, not merged, so a crash *during*
    recovery just means recovering again from the same frontier.

    One compiled program per table shape and power-of-two row count:
    the rows are padded with invalid ones, which claim no slot.
    """
    n = len(keys)
    if n == 0:
        return table
    size = 1 << (n - 1).bit_length()

    def pad(a, dtype=None):
        a = np.asarray(a, dtype)
        if size == n:
            return a
        return np.concatenate([a, np.zeros((size - n,) + a.shape[1:],
                                           a.dtype)])
    return _restore(table, pad(keys, np.dtype(table.keys.dtype)),
                    np.arange(size) < n, jax.tree.map(pad, slates),
                    pad(ts, np.int32))


@jax.jit
def _restore(table, keys, valid, slates, ts):
    table, slot, _, placed = tbl.insert_or_find(table, keys, valid)
    table = tbl.write_slates(table, slot, placed, slates, ts)
    # restored slates are clean (they came *from* the store)
    return replace(table, dirty=jnp.zeros_like(table.dirty))


class Flusher:
    """Background flusher thread: consumes dirty snapshots, writes to the
    KV store.  ``flush_table`` is called from the engine driver per the
    policy; ``drain`` joins outstanding work (flush barriers / shutdown)
    and **re-raises** any write error as :class:`FlushError` — a frontier
    must never advance past a failed store write.  A second thread merges
    the store's blocks (``KVStore.compact``) after writes, outside what
    ``drain`` waits for; its errors surface at the next ``drain`` too.

    With ``track_deltas`` the flusher also retains a host-side copy of
    every row it successfully wrote since the last ``drain_deltas()``
    call — the flush *stream* a :class:`~repro.slates.replica.
    SlateReplica` consumes to refresh incrementally instead of
    re-scanning the whole store (DESIGN.md section 15)."""

    def __init__(self, store: KVStore, cfg: Optional[FlushConfig] = None,
                 *, track_deltas: bool = False, tracer=None):
        self.store = store
        self.cfg = cfg or FlushConfig()
        self.track_deltas = track_deltas
        self.tracer = tracer
        # guards what the thread hands over: the written rows' deltas
        # (updater -> [(keys, ts, vals)]), the rows written per updater
        # and the errors
        self._lock = threading.Lock()
        self._deltas: dict = {}
        self.rows_written: Dict[str, int] = {}
        self.errors: list = []
        self._q: pyqueue.Queue = pyqueue.Queue()
        self._thread = threading.Thread(target=self._loop, daemon=True)
        self._thread.start()
        # store merges run on a thread of their own: a write queued
        # behind a merge would hold the next frontier commit up
        self._merge_due = threading.Event()
        self._closing = False
        self._merger = threading.Thread(target=self._merge_loop,
                                        daemon=True)
        self._merger.start()

    def _loop(self):
        while True:
            item = self._q.get()
            if item is None:
                self._q.task_done()
                return
            try:
                updater, keys, ts, vals, ttl = item
                with span(self.tracer, "store_write", updater=updater,
                          rows=len(keys)) as a:
                    a["bytes"] = self.store.put_many(
                        updater, keys, vals, ts=ts, ttl=ttl) \
                        + self.store.flush()
                with self._lock:
                    self.rows_written[updater] = \
                        self.rows_written.get(updater, 0) + len(keys)
                    if self.track_deltas:
                        # recorded only after the write landed: a delta
                        # the replica merges is always durably in the
                        # store too
                        self._deltas.setdefault(updater, []).append(
                            (keys, ts, vals))
                self._merge_due.set()
            except Exception as e:
                with self._lock:
                    self.errors.append(e)
            finally:
                self._q.task_done()

    def _merge_loop(self):
        while True:
            self._merge_due.wait()
            self._merge_due.clear()
            if self._closing:
                return
            try:
                self.store.compact()
            except Exception as e:
                with self._lock:
                    self.errors.append(e)

    def merge_soon(self):
        """Wake the merge thread (the store's sealed blocks changed)."""
        self._merge_due.set()

    def drain_deltas(self) -> dict:
        """Hand off (and clear) the rows written since the last call:
        ``{updater: {key: (ts, slate)}}``, newest write per key.  Call
        after ``drain()`` (a flush barrier) so the handoff covers every
        row at the frontier."""
        with self._lock:
            d, self._deltas = self._deltas, {}
        out = {}
        for updater, writes in d.items():
            keys = np.concatenate([np.asarray(k, np.int64)
                                   for k, _, _ in writes])
            ts = np.concatenate([np.asarray(t, np.int64)
                                 for _, t, _ in writes])
            vals = jax.tree.map(lambda *xs: np.concatenate(xs),
                                *[v for _, _, v in writes])
            idx = newest(keys, ts, np.zeros_like(keys))
            rows = _rows_of(jax.tree.map(lambda v: v[idx], vals), idx.size)
            out[updater] = {k: (t, row) for k, t, row in zip(
                keys[idx].tolist(), ts[idx].tolist(), rows)}
        return out

    def should_flush(self, tick: int, table: tbl.SlateTable) -> bool:
        p = self.cfg.policy
        if p is FlushPolicy.IMMEDIATE:
            return True
        if p is FlushPolicy.EVERY_K:
            return tick % self.cfg.every_k == 0
        occ = float(jax.device_get(table.occupancy()))
        return occ >= self.cfg.occupancy_evict * table.capacity

    def flush_rows(self, updater: str, keys: np.ndarray, ts: np.ndarray,
                   vals, ttl: int = 0):
        """Enqueue pre-snapshotted host rows (the per-shard flush path of
        ``DistributedEngine`` snapshots all shards in one device_get and
        feeds each shard's rows here).  Store write ticks are the
        per-row ``ts`` (each slate's last-update tick)."""
        if len(keys):
            self._q.put((updater, np.asarray(keys), np.asarray(ts), vals,
                         ttl))

    def flush_table(self, updater: str, table: tbl.SlateTable,
                    ttl: int = 0) -> tbl.SlateTable:
        keys, ts, vals, cleared = dirty_snapshot(table)
        self.flush_rows(updater, keys, ts, vals, ttl)
        return cleared

    def _raise_accumulated(self):
        with self._lock:
            errs, self.errors = self.errors, []
        if errs:
            raise FlushError(errs)

    def drain(self):
        """Join outstanding writes; raises :class:`FlushError` if any
        failed (callers must not record a frontier past the failure)."""
        self._q.join()
        try:
            self.store.flush()
        except Exception as e:
            with self._lock:
                self.errors.append(e)
        self._raise_accumulated()

    def close(self):
        try:
            self.drain()
        finally:
            self.halt()

    def halt(self):
        """Stop both threads without draining: queued writes are dropped,
        as a crashed process drops them; a write or merge under way
        finishes first."""
        drop_queued(self._q)
        self._q.put(None)
        self._thread.join(timeout=5)
        self._closing = True
        self._merge_due.set()
        self._merger.join(timeout=5)


def drop_queued(q: pyqueue.Queue):
    """Take every item off ``q`` unprocessed, marking each done."""
    while True:
        try:
            q.get_nowait()
        except pyqueue.Empty:
            return
        q.task_done()


def _rows_of(vals, n: int):
    """Split a pytree of [n, ...] arrays into n per-key pytrees (the
    replica tier's flush stream).  One iteration pass per leaf (``list``
    walks the leading axis once) instead of n fancy-index calls per
    leaf."""
    leaves, treedef = jax.tree.flatten(vals)
    if not leaves:
        return [jax.tree.unflatten(treedef, []) for _ in range(n)]
    per_leaf = [list(lf) for lf in leaves]
    return [jax.tree.unflatten(treedef, list(row))
            for row in zip(*per_leaf)]
