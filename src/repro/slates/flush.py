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
from typing import List, Optional, Sequence, Union

import jax
import jax.numpy as jnp
import numpy as np

from repro.slates import table as tbl
from repro.slates.kvstore import KVStore


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
    (DistributedEngine: one WAL per shard, one barrier tick).  ``meta``
    is an opaque json-serializable driver cursor (e.g. the source index
    at the boundary) that survives even full WAL truncation."""

    tick: int = 0
    wal_offset: Union[int, List[int]] = 0
    meta: Optional[dict] = None

    def save(self, path: str):
        tmp = path + ".tmp"
        with open(tmp, "w") as f:
            json.dump({"tick": int(self.tick),
                       "wal_offset": self.wal_offset,
                       "meta": self.meta}, f)
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
                             meta=d.get("meta"))


def begin_dirty_snapshot(table: tbl.SlateTable):
    """Start the device->host fetch for a flush snapshot.

    Device-side copies are taken first (so the token stays valid after
    the next chunk's donation deletes the table buffers) and their host
    transfer is kicked off asynchronously; :func:`finish_dirty_snapshot`
    resolves the token to host rows whenever the driver is ready —
    typically after the next chunk has been dispatched, so the transfer
    and the serialization behind it overlap device compute.  Returns
    ``(token, cleared_table)``; the cleared table (dirty bits dropped)
    is usable immediately."""
    token = (jnp.copy(table.dirty), jnp.copy(table.keys),
             jnp.copy(table.ts), jax.tree.map(jnp.copy, table.vals))
    for leaf in jax.tree.leaves(token):
        leaf.copy_to_host_async()
    cleared = replace(table, dirty=jnp.zeros_like(table.dirty))
    return token, cleared


def finish_dirty_snapshot(token):
    """Resolve an in-flight snapshot to host ``(keys, ts, vals)`` of the
    dirty occupied slots (the flusher's row format)."""
    dirty_d, keys_d, ts_d, vals_d = token
    dirty = np.asarray(jax.device_get(dirty_d))
    keys = np.asarray(jax.device_get(keys_d))
    ts = np.asarray(jax.device_get(ts_d))
    idx = np.nonzero(dirty & (keys != -1))[0]
    vals = jax.tree.map(lambda v: np.asarray(jax.device_get(v))[idx],
                        vals_d)
    return keys[idx], ts[idx], vals


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
    """
    if len(keys) == 0:
        return table
    k = jnp.asarray(keys, table.keys.dtype)
    valid = jnp.ones((len(keys),), bool)
    table, slot, found, placed = tbl.insert_or_find(table, k, valid)
    vals = jax.tree.map(jnp.asarray, slates)
    table = tbl.write_slates(table, slot, placed, vals,
                             jnp.asarray(ts, jnp.int32))
    # restored slates are clean (they came *from* the store)
    return replace(table, dirty=jnp.zeros_like(table.dirty))


class Flusher:
    """Background flusher thread: consumes dirty snapshots, writes to the
    KV store.  ``flush_table`` is called from the engine driver per the
    policy; ``drain`` joins outstanding work (flush barriers / shutdown)
    and **re-raises** any write error as :class:`FlushError` — a frontier
    must never advance past a failed store write.

    With ``track_deltas`` the flusher also retains a host-side copy of
    every row it successfully wrote since the last ``drain_deltas()``
    call — the flush *stream* a :class:`~repro.slates.replica.
    SlateReplica` consumes to refresh incrementally instead of
    re-scanning the whole store (DESIGN.md section 15)."""

    def __init__(self, store: KVStore, cfg: Optional[FlushConfig] = None,
                 *, track_deltas: bool = False):
        self.store = store
        self.cfg = cfg or FlushConfig()
        self.track_deltas = track_deltas
        self._deltas: dict = {}          # updater -> {key: (ts, slate)}
        self._dlock = threading.Lock()
        self._q: pyqueue.Queue = pyqueue.Queue()
        self._thread = threading.Thread(target=self._loop, daemon=True)
        self._thread.start()
        self.errors: list = []

    def _loop(self):
        while True:
            item = self._q.get()
            if item is None:
                self._q.task_done()
                return
            try:
                updater, keys, ts, vals, ttl = item
                rows = _rows_of(vals, len(keys))
                self.store.put_many(updater,
                                    zip(keys.tolist(), rows),
                                    ts=ts.tolist(), ttl=ttl)
                self.store.flush()
                if self.track_deltas:
                    # recorded only after the write landed: a delta the
                    # replica merges is always durably in the store too
                    with self._dlock:
                        d = self._deltas.setdefault(updater, {})
                        for k, t, row in zip(keys.tolist(), ts.tolist(),
                                             rows):
                            old = d.get(k)
                            if old is None or old[0] <= t:
                                d[k] = (t, row)
            except Exception as e:
                self.errors.append(e)
            finally:
                self._q.task_done()

    def drain_deltas(self) -> dict:
        """Hand off (and clear) the rows written since the last call:
        ``{updater: {key: (ts, slate)}}``, newest write per key.  Call
        after ``drain()`` (a flush barrier) so the handoff covers every
        row at the frontier."""
        with self._dlock:
            d, self._deltas = self._deltas, {}
        return d

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
        if self.errors:
            errs, self.errors = self.errors, []
            raise FlushError(errs)

    def drain(self):
        """Join outstanding writes; raises :class:`FlushError` if any
        failed (callers must not record a frontier past the failure)."""
        self._q.join()
        try:
            self.store.flush()
        except Exception as e:
            self.errors.append(e)
        self._raise_accumulated()

    def close(self):
        try:
            self.drain()
        finally:
            self._q.put(None)
            self._thread.join(timeout=5)


def _rows_of(vals, n: int):
    """Split a pytree of [n, ...] arrays into n per-key pytrees.  One
    iteration pass per leaf (``list`` walks the leading axis once)
    instead of n fancy-index calls per leaf."""
    leaves, treedef = jax.tree.flatten(vals)
    if not leaves:
        return [jax.tree.unflatten(treedef, []) for _ in range(n)]
    per_leaf = [list(lf) for lf in leaves]
    return [jax.tree.unflatten(treedef, list(row))
            for row in zip(*per_leaf)]
