"""Distributed MapUpdate engine: the single-shard tick under shard_map.

Muppet's data path — workers hash events to peers and write directly into
their queues — becomes one ``all_to_all`` per workflow hop: each shard
buckets its outgoing events by destination shard (ring lookup), the
collective delivers every bucket, and the receiving shard enqueues.  No
master is on the data path; the ring is a runtime *array* input with a
fixed shape, so failure re-routes and elastic joins/leaves/reweights
swap ring contents without recompiling — ``scale`` / ``add_shards`` /
``remove_shards`` / ``rebalance`` migrate slates and in-flight events
loss-free at a drain barrier (DESIGN.md section 12); only changing the
physical slot count (grow, or compaction shrink) recompiles.

Migration itself is tiered (DESIGN.md section 14): shape-preserving
reconfigures re-home slate rows *on device* — ``exchange_rows`` packs
each table's moving rows by their new ring owner and delivers them with
one ``all_to_all``, the same collective the event path uses — while
shape changes (physical grow, slot compaction) fall back to the host
remap.  Both paths produce bitwise-identical slates (the PR-4 parity
contract).

Two-choice dispatch (Muppet 2.0 dual queues): for associative updaters,
per-key load beyond ``two_choice_threshold`` in a tick spills to the
key's secondary shard; each shard then holds a *partial* aggregate and
``read_slate`` merges the (at most two) partials — the same <=2-contender
bound the paper proves acceptable in production.
"""
from __future__ import annotations

import threading
import time
from dataclasses import dataclass, field, replace
from functools import partial
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core import queues as q_mod
from repro.core import tick as tick_mod
from repro.core.durability import (DurabilityConfig, EngineDurability,
                                   merge_replay_ticks)
from repro.core.engine import Engine, EngineConfig, resolve_key_dtype
from repro.core.event import EventBatch, concat
from repro.core.hashing import HashRing, route, route_secondary
from repro.core.operators import AssociativeUpdater
from repro.core.workflow import Workflow
from repro.slates import flush as flush_mod
from repro.slates import table as tbl
from repro.telemetry import latency as lat_mod
from repro.telemetry import sketch as sk_mod
from repro.telemetry.controller import LoadAutoscaler
from repro.telemetry.metrics import MetricsRegistry, TelemetryConfig
from repro.telemetry.trace import ControlLog, Tracer, span, tracer_for


def _linear_shard_index(axis_names):
    """This shard's linearized id over the (possibly multi-) mesh axes —
    the shard-dim index of the global state arrays, matching
    ``np.prod``-order linearization (trailing axis fastest)."""
    idx = None
    for a in axis_names:
        i = jax.lax.axis_index(a)
        idx = i if idx is None else idx * jax.lax.axis_size(a) + i
    return idx


def _salt(name: str) -> int:
    h = 2166136261
    for c in name.encode():
        h = ((h ^ c) * 16777619) & 0xFFFFFFFF
    return h


def exchange(batch: EventBatch, dest, axis_names, cap_per_dest: int
             ) -> Tuple[EventBatch, jnp.ndarray]:
    """Route events to destination shards with one all_to_all.

    Per-destination buckets have static capacity; excess events are
    dropped and counted (bounded queues, paper section 4.3).  Returns the
    received local batch [n*cap] and the local overflow count.
    """
    n = jax.lax.axis_size(axis_names)
    B = batch.capacity
    dest = jnp.where(batch.valid, dest, n)              # invalid -> sink
    order = jnp.argsort(dest, stable=True)
    sb = batch.take(order)
    sdest = dest[order]
    pos = jnp.arange(B, dtype=jnp.int32) - jnp.searchsorted(
        sdest, sdest, side="left").astype(jnp.int32)
    ok = sb.valid & (sdest < n) & (pos < cap_per_dest)
    slot = jnp.where(ok, sdest * cap_per_dest + pos, n * cap_per_dest)
    dropped = jnp.sum((sb.valid & (sdest < n) & ~ok).astype(jnp.int32))

    buckets = EventBatch.empty(
        n * cap_per_dest,
        jax.tree.map(lambda a: (a.shape[1:], a.dtype), sb.value),
        key_dtype=sb.key.dtype)

    def put(dst, src):
        return dst.at[slot].set(src, mode="drop")

    buckets = EventBatch(
        sid=put(buckets.sid, sb.sid), ts=put(buckets.ts, sb.ts),
        key=put(buckets.key, sb.key),
        value=jax.tree.map(put, buckets.value, sb.value),
        valid=put(buckets.valid, ok))

    def a2a(x):
        return jax.lax.all_to_all(
            x.reshape((n, cap_per_dest) + x.shape[1:]), axis_names,
            split_axis=0, concat_axis=0).reshape((n * cap_per_dest,)
                                                 + x.shape[1:])

    received = EventBatch(
        sid=a2a(buckets.sid), ts=a2a(buckets.ts), key=a2a(buckets.key),
        value=jax.tree.map(a2a, buckets.value), valid=a2a(buckets.valid))
    return received, dropped


def exchange_rows(t: tbl.SlateTable, dest_salt: int, ring_hashes,
                  ring_shards, axis_names, cap_per_dest: int, combine
                  ) -> Tuple[tbl.SlateTable, jnp.ndarray]:
    """Slate-row migration as one all_to_all (DESIGN.md section 14.1):
    the table-row generalization of :func:`exchange`, run under
    shard_map on every shard at a reconfigure boundary.

    Each shard routes its rows through the *new* ring, packs movers
    ``(key, value, ts, dirty)`` into per-destination buckets, trades
    buckets with the collective, and rebuilds its table from stayers +
    arrivals.  Duplicate keys converging on one shard (two-choice /
    hot-split partials) fold via the updater's ``combine`` (else
    last-ts-wins), exactly like the host rebuild: folded rows are
    dirty, the fold is timestamp-monotone, and rows that do not fit
    (bucket overflow, full table) are dropped and counted.  ``combine``
    must be associative and — for bitwise parity with the host path's
    first-encountered fold order — commutative, which every partial-
    producing dispatch mode already requires.

    ``cap_per_dest`` bounds rows moved per (src, dest) pair; the caller
    sizes it from an exact on-device count (``_migrate_device``), so
    nothing is lost in practice.  Returns ``(new_table, moved_out)``.
    """
    n = jax.lax.axis_size(axis_names)
    me = _linear_shard_index(axis_names)
    C = t.capacity
    valid = t.keys != tbl.EMPTY
    owner = route(t.keys, dest_salt, ring_hashes, ring_shards)
    mover = valid & (owner != me)
    moved_out = jnp.sum(mover.astype(jnp.int32))

    # pack movers into per-destination buckets (the exchange() layout)
    dest = jnp.where(mover, owner, n)                   # stayers -> sink
    order = jnp.argsort(dest, stable=True)
    sdest = dest[order]
    pos = jnp.arange(C, dtype=jnp.int32) - jnp.searchsorted(
        sdest, sdest, side="left").astype(jnp.int32)
    ok = (sdest < n) & (pos < cap_per_dest)
    slot = jnp.where(ok, sdest * cap_per_dest + pos, n * cap_per_dest)
    lost = jnp.sum(((sdest < n) & ~ok).astype(jnp.int32))

    def bucket(src, fill):
        buf = jnp.full((n * cap_per_dest,) + src.shape[1:], fill,
                       src.dtype)
        return buf.at[slot].set(src[order], mode="drop")

    def a2a(x):
        return jax.lax.all_to_all(
            x.reshape((n, cap_per_dest) + x.shape[1:]), axis_names,
            split_axis=0, concat_axis=0).reshape((n * cap_per_dest,)
                                                 + x.shape[1:])

    rvalid = a2a(jnp.zeros((n * cap_per_dest,), bool)
                 .at[slot].set(ok, mode="drop"))
    rkeys = a2a(bucket(t.keys, tbl.EMPTY))
    rts = a2a(bucket(t.ts, 0))
    rdirty = a2a(bucket(t.dirty, False))
    rvals = jax.tree.map(lambda v: a2a(bucket(v, 0)), t.vals)

    # candidates = stayers ∪ arrivals; sort valid-first, key-ascending
    # (two stable passes — no 64-bit composite key needed) so duplicate
    # keys are adjacent and segment folding is a single scan
    stay = valid & (owner == me)
    ckeys = jnp.concatenate([t.keys, rkeys])
    cvalid = jnp.concatenate([stay, rvalid])
    cts = jnp.concatenate([t.ts, rts])
    cdirty = jnp.concatenate([t.dirty, rdirty])
    cvals = jax.tree.map(lambda a, b: jnp.concatenate([a, b]),
                         t.vals, rvals)
    o1 = jnp.argsort(ckeys, stable=True)
    o2 = jnp.argsort(jnp.where(cvalid[o1], 0, 1).astype(jnp.int32),
                     stable=True)
    order2 = o1[o2]
    ks, vs = ckeys[order2], cvalid[order2]
    ts_s, dt_s = cts[order2], cdirty[order2]
    vals_s = jax.tree.map(lambda v: v[order2], cvals)

    prev_same = jnp.concatenate(
        [jnp.zeros((1,), bool), (ks[1:] == ks[:-1]) & vs[1:] & vs[:-1]])
    seg_start = ~prev_same

    def _b(mask, like):
        return mask.reshape(mask.shape + (1,) * (like.ndim - 1))

    def fold(a, b):
        fa, va, ta, da = a
        fb, vb, tb, db = b
        if combine is not None:
            merged = combine(va, vb)
        else:
            merged = jax.tree.map(
                lambda x, y: jnp.where(_b(tb >= ta, x), y, x), va, vb)
        v = jax.tree.map(lambda m, y: jnp.where(_b(fb, m), y, m),
                         merged, vb)
        return (fa | fb, v,
                jnp.where(fb, tb, jnp.maximum(ta, tb)),
                jnp.where(fb, db, jnp.ones_like(db)))

    _, fvals, fts, fdirty = jax.lax.associative_scan(
        fold, (seg_start, vals_s, ts_s, dt_s))

    # one representative per key: the last row of its sorted run holds
    # the full fold; singleton runs keep their original ts/dirty
    rep = vs & ~jnp.concatenate([prev_same[1:], jnp.zeros((1,), bool)])

    fresh = replace(
        t, keys=jnp.full((C,), tbl.EMPTY, t.keys.dtype),
        ts=jnp.zeros((C,), jnp.int32),
        dirty=jnp.zeros((C,), bool),
        vals=jax.tree.map(jnp.zeros_like, t.vals),
        dropped=t.dropped + lost)
    fresh, slot2, _, placed = tbl.insert_or_find(fresh, ks, rep)
    safe = jnp.where(placed, slot2, C)
    new_vals = jax.tree.map(
        lambda dst, src: dst.at[safe].set(src.astype(dst.dtype),
                                          mode="drop"),
        fresh.vals, fvals)
    new = replace(
        fresh,
        ts=fresh.ts.at[safe].set(fts, mode="drop"),
        dirty=fresh.dirty.at[safe].set(fdirty, mode="drop"),
        vals=new_vals,
        dropped=fresh.dropped + jnp.sum((rep & ~placed).astype(jnp.int32)))
    return new, moved_out


def exchange_queue(q: q_mod.QueueState, dest_salt: int, ring_hashes,
                   ring_shards, axis_names, cap_per_dest: int
                   ) -> Tuple[q_mod.QueueState, jnp.ndarray]:
    """Queued-event re-homing as one all_to_all: the queue counterpart
    of :func:`exchange_rows`, so a planned leave with backlog
    (``drain_max=0``, or a drain barrier that could not retire the
    queues) stays on the device migration path instead of falling back
    to the host remap.

    Mirrors ``_migrate_queues_host`` exactly: every in-``size`` slot is
    scanned in dequeue order, routed by its key's *primary* owner on
    the new ring (validity flags ride along as payload, like the host
    scan), and each destination rebuilds its queue compacted at head 0
    in (source shard asc, dequeue order) — the host path's
    shard-ascending concat.  ``dropped`` carries plus any overflow
    (bucket or destination-capacity); ``peak`` restarts at the
    post-migration backlog, the rebalance window's load signal.
    Returns ``(new_queue, moved_out)``.
    """
    n = jax.lax.axis_size(axis_names)
    me = _linear_shard_index(axis_names)
    buf = q.buf
    C = buf.capacity
    pos = (q.head + jnp.arange(C, dtype=jnp.int32)) % C
    live = jnp.arange(C, dtype=jnp.int32) < q.size
    sid, ts, key = buf.sid[pos], buf.ts[pos], buf.key[pos]
    vflag = buf.valid[pos]
    vals = jax.tree.map(lambda v: v[pos], buf.value)
    owner = route(key, dest_salt, ring_hashes, ring_shards)
    moved_out = jnp.sum((live & (owner != me)).astype(jnp.int32))

    # all live events (stayers included) go through the buckets so the
    # rebuild's arrival order is purely (src, dequeue) — host parity
    dest = jnp.where(live, owner, n)                    # dead -> sink
    order = jnp.argsort(dest, stable=True)
    sdest = dest[order]
    bpos = jnp.arange(C, dtype=jnp.int32) - jnp.searchsorted(
        sdest, sdest, side="left").astype(jnp.int32)
    ok = (sdest < n) & (bpos < cap_per_dest)
    slot = jnp.where(ok, sdest * cap_per_dest + bpos, n * cap_per_dest)
    lost = jnp.sum(((sdest < n) & ~ok).astype(jnp.int32))

    def bucket(src, fill):
        b = jnp.full((n * cap_per_dest,) + src.shape[1:], fill,
                     src.dtype)
        return b.at[slot].set(src[order], mode="drop")

    def a2a(x):
        return jax.lax.all_to_all(
            x.reshape((n, cap_per_dest) + x.shape[1:]), axis_names,
            split_axis=0, concat_axis=0).reshape((n * cap_per_dest,)
                                                 + x.shape[1:])

    rlive = a2a(jnp.zeros((n * cap_per_dest,), bool)
                .at[slot].set(ok, mode="drop"))
    rsid, rts, rkey = a2a(bucket(sid, 0)), a2a(bucket(ts, 0)), \
        a2a(bucket(key, 0))
    rvflag = a2a(bucket(vflag, False))
    rvals = jax.tree.map(lambda v: a2a(bucket(v, 0)), vals)

    # compact arrivals at head 0; received layout is already src-major
    # with dequeue order within each source, so rank order == the host
    # rebuild's FIFO order
    rank = jnp.cumsum(rlive.astype(jnp.int32)) - 1
    fits = rlive & (rank < C)
    size = jnp.sum(fits.astype(jnp.int32))
    tgt = jnp.where(fits, rank, C)

    def scat(src, fill):
        b = jnp.full((C,) + src.shape[1:], fill, src.dtype)
        return b.at[tgt].set(src, mode="drop")

    nbuf = EventBatch(
        sid=scat(rsid, 0), ts=scat(rts, 0), key=scat(rkey, 0),
        value=jax.tree.map(lambda v: scat(v, 0), rvals),
        valid=scat(rvflag, False))
    drops = lost + jnp.sum((rlive & ~fits).astype(jnp.int32))
    return q_mod.QueueState(
        buf=nbuf, head=jnp.zeros_like(q.head), size=size,
        dropped=q.dropped + drops, peak=size), moved_out


@dataclass
class AutoscalePolicy:
    """Declarative elasticity for ``DistributedEngine.run`` (DESIGN.md
    section 12): scale the active shard set at given source ticks and/or
    rebalance the weighted ring from the per-shard load signal every k
    source ticks.  Exposed through the front door as
    ``RuntimeConfig(autoscale=AutoscalePolicy(...))``."""

    scale_at: Dict[int, int] = field(default_factory=dict)
    # source tick -> target active shard count (fires before that tick)
    rebalance_every: int = 0     # source ticks between reweights; 0 = off
    drain_max: int = 64          # drain-barrier bound per reconfigure
    on_change: Optional[Any] = None  # callback(MigrationReport), e.g. log


@dataclass
class MigrationReport:
    """What a live reconfigure moved (scale / rebalance / leave)."""

    n_shards: int                # physical shard slots after
    active: List[int]            # active shard ids after
    drain_ticks: int             # barrier ticks run before migration
    moved_rows: Dict[str, int]   # slate rows re-homed, per updater
    moved_events: Dict[str, int]  # queued events re-homed, per operator
    recompiled: bool             # physical shape change (grow/compact)
    pause_s: float = 0.0         # wall seconds the stream stood still
    bytes_moved: int = 0         # payload re-homed (rows + events)
    path: str = "host"           # "device" (all_to_all) or "host" remap


@dataclass
class DistConfig(EngineConfig):
    exchange_slack: float = 2.0   # per-dest bucket capacity multiplier
    two_choice_threshold: int = 0  # 0 = off; else per-key spill point
    axis_names: Tuple[str, ...] = ("data",)
    # tick-scheduled AutoscalePolicy, or a closed-loop LoadAutoscaler
    # driven by the telemetry subsystem (DESIGN.md 13.3)
    autoscale: Optional[Any] = None
    # hot-key split set capacity (fixed shape).  0 = the split routing
    # path is not compiled into the tick at all (no per-event secondary
    # route); >0 opts in, and a LoadAutoscaler with skew > 0 implies 8.
    # Needs cfg.telemetry and no durability.  See split_keys.
    hot_key_capacity: int = 0
    # migration tier selection (DESIGN.md 14.1).  "auto": reconfigures
    # that keep physical shapes re-home slate rows AND any queued
    # backlog on device (all_to_all row + event exchange — a drained
    # queue set is no longer required); "off" forces the host remap
    # everywhere (debug / parity baseline).
    device_migration: str = "auto"
    # physical slot compaction (DESIGN.md 14.2): when a deactivation
    # leaves >= this fraction of slots dead, shrink the mesh to the
    # active set and free the parked slots' HBM (shape change — the
    # tick recompiles, like grow).  0 disables; compact() forces it.
    compact_threshold: float = 0.75


class DistributedEngine:
    """Global state lives sharded on dim 0 (= shard axis) of every leaf."""

    def __init__(self, workflow: Workflow, mesh: Mesh,
                 config: Optional[DistConfig] = None):
        self.wf = workflow
        # the engine places state with NamedSharding and runs its own
        # shard_map programs, so every axis is Auto (jax.make_mesh gives
        # Explicit axes by default, which reject that placement)
        self.mesh = Mesh(mesh.devices, mesh.axis_names,
                         axis_types=(jax.sharding.AxisType.Auto,)
                         * len(mesh.axis_names))
        self.cfg = config or DistConfig()
        self.key_dtype = resolve_key_dtype(self.cfg.key_dtype)
        self.axes = self.cfg.axis_names
        self.n_shards = int(np.prod([mesh.shape[a] for a in self.axes]))
        self.ring = HashRing(self.n_shards)
        self._sharding = NamedSharding(mesh, P(self.axes))
        self._replicated = NamedSharding(mesh, P())
        cap = int(self.cfg.batch_size * self.cfg.exchange_slack
                  / self.n_shards)
        self.cap_per_dest = max(8, cap)
        self._plan_fn = None       # device-migration owner-count jit
        self._migrate_fns = {}     # (row_cap, ev_cap) -> jitted exchange
        self._read_fns = {}        # (updater, with_sec) -> batched read
        # serializes slate readers against in-flight reconfigures and
        # the donating step dispatches: a read racing either would see a
        # half-swapped ring or donated (deleted) buffers.  RLock so
        # read_split_slate can hold it across its sub-key loop while
        # read_slate re-acquires.  Drivers that publish a StateHandle
        # republish it *inside* the critical section (_live_handle).
        self.read_lock = threading.RLock()
        self._live_handle = None
        self._load_mark = np.zeros(self.n_shards)  # rebalance window base
        self.tick_cursor = 0      # post-run() *source* cursor
        # telemetry (DESIGN.md 13): a per-shard count-min sketch in the
        # jitted tick + the windowed registry; a closed-loop controller
        # implies it even when cfg.telemetry is unset
        tele = self.cfg.telemetry
        if tele is None and isinstance(self.cfg.autoscale,
                                       LoadAutoscaler):
            tele = self.cfg.autoscale.telemetry or TelemetryConfig()
        self.tele_cfg = tele
        self.telemetry: Optional[MetricsRegistry] = None
        self._salts = None
        self.tracer: Optional[Tracer] = tracer_for(tele)
        self.dur: Optional[EngineDurability] = None
        if self.cfg.durability is not None:
            self.attach_durability(self.cfg.durability)
        self._ctl_log: Optional[ControlLog] = None
        if tele is not None:
            self.telemetry = MetricsRegistry(
                tele, batch_size=self.cfg.batch_size)
            self._salts = self.telemetry.salts
            if tele.control_log:
                self._ctl_log = ControlLog(tele.control_log)
        # hot-key split set: fixed-shape runtime input of the tick, so
        # split/unsplit swap contents without recompiling (ring-style).
        # Opt-in (explicit capacity, or a skew-enabled controller):
        # compiling it in costs every associative delivery a secondary
        # ring route, so plain-telemetry runs skip it entirely.
        hot_cap = self.cfg.hot_key_capacity
        if (hot_cap == 0 and isinstance(self.cfg.autoscale,
                                        LoadAutoscaler)
                and self.cfg.autoscale.skew > 0.0):
            hot_cap = 8
        self._hot_capacity = (hot_cap if tele is not None
                              and self.cfg.durability is None else 0)
        self._hot_keys = np.zeros(max(1, self._hot_capacity),
                                  self.key_dtype)
        self._hot_valid = np.zeros(max(1, self._hot_capacity), bool)
        self._build_tick_programs()

    @property
    def key_bits(self) -> int:
        return int(self.key_dtype.itemsize) * 8

    # ---- state ----
    def init_state(self):
        """Fresh global state, built by one jitted program whose outputs
        are laid out per ``_shard_tree``: each shard's rows are created
        on its own device (building them eagerly would stage every
        shard's tables on the first device)."""
        def per_shard(make):
            return jax.tree.map(
                lambda x: jnp.broadcast_to(
                    x[None], (self.n_shards,) + x.shape), make())

        kd = self.key_dtype

        def build():
            queues = {op.name: per_shard(partial(
                q_mod.make_queue, self.cfg.queue_capacity,
                op.in_value_spec, key_dtype=kd))
                for op in self.wf.operators}
            tables = {up.name: per_shard(partial(
                tbl.make_table, up.table_capacity, up.slate_spec(),
                key_dtype=kd))
                for up in self.wf.updaters()}
            z = lambda: jnp.zeros((self.n_shards,), jnp.int32)
            state = {
                "queues": queues, "tables": tables,
                "tick": z(),
                "exchange_dropped": z(),
                "throttle_hits": z(),
                "deferred": z(),
                "processed": {op.name: z() for op in self.wf.operators},
            }
            if self.tele_cfg is not None:
                tc = self.tele_cfg
                state["sketch"] = per_shard(partial(
                    sk_mod.make_sketch, tc.depth, tc.width, tc.sample,
                    key_dtype=kd))
                if tc.latency_buckets > 0:
                    state["lat_hist"] = per_shard(partial(
                        lat_mod.make_hist,
                        [u.name for u in self.wf.updaters()],
                        tc.latency_buckets))
            return state

        shardings = self._shard_tree(jax.eval_shape(build))
        return jax.jit(build, out_shardings=shardings)()

    def _shard_tree(self, state):
        def spec(path_unused, leaf):
            if leaf.ndim >= 1 and leaf.shape[0] == self.n_shards:
                return self._sharding
            return self._replicated
        return jax.tree_util.tree_map_with_path(spec, state)

    # ---- the per-shard tick ----
    def _local_tick(self, state, sources, ring_hashes, ring_shards,
                    hot_keys, hot_valid):
        """One shard's tick under ``shard_map``: the shared tick
        (``core/tick.py``) on this shard's rows, with the ring route and
        the ``all_to_all`` exchange as its ``send``."""
        local = jax.tree.map(lambda x: x[0], state)
        tick = local["tick"]
        exchange_dropped = local.pop("exchange_dropped")

        def send(dest_op, batch):
            nonlocal exchange_dropped
            op = self.wf.by_name[dest_op]
            dshard = route(batch.key, _salt(dest_op), ring_hashes,
                           ring_shards)
            if (self.cfg.two_choice_threshold
                    and isinstance(op, AssociativeUpdater)):
                dshard = self._two_choice(batch, dshard, dest_op,
                                          ring_hashes, ring_shards)
            elif self._hot_capacity and isinstance(op, AssociativeUpdater):
                dshard = self._hot_split(batch, dshard, dest_op,
                                         ring_hashes, ring_shards,
                                         hot_keys, hot_valid, tick)
            recv, n = exchange(batch, dshard, self.axes, self.cap_per_dest)
            exchange_dropped = exchange_dropped + n
            return recv

        new, outs = tick_mod.tick(
            self.wf, self.cfg, self.tele_cfg, self._salts, local,
            jax.tree.map(lambda x: x[0], sources), send)
        new["exchange_dropped"] = exchange_dropped
        lift = lambda t: jax.tree.map(lambda x: x[None], t)
        return lift(new), lift(outs)

    def _two_choice(self, batch, primary, dest_op, ring_hashes,
                    ring_shards):
        """Spill a key's per-tick excess to its secondary shard."""
        secondary = route_secondary(batch.key, _salt(dest_op), ring_hashes,
                                    ring_shards)
        key_sink = jnp.where(
            batch.valid, batch.key,
            jnp.asarray(jnp.iinfo(batch.key.dtype).max, batch.key.dtype))
        order = jnp.argsort(key_sink, stable=True)
        sk = key_sink[order]
        rank_sorted = jnp.arange(batch.capacity, dtype=jnp.int32) - \
            jnp.searchsorted(sk, sk, side="left").astype(jnp.int32)
        rank = jnp.zeros_like(rank_sorted).at[order].set(rank_sorted)
        spill = rank >= self.cfg.two_choice_threshold
        return jnp.where(spill, secondary, primary)

    def _hot_split(self, batch, primary, dest_op, ring_hashes,
                   ring_shards, hot_keys, hot_valid, tick):
        """Runtime hot-key relief (DESIGN.md 13.4): events whose key is
        in the (fixed-shape) hot set alternate between the key's
        primary and secondary ring shard — two-choice dispatch, but
        targeted at controller-identified heavy hitters instead of a
        per-tick rank threshold.  The row-index/tick parity flip sends
        ~half of each tick's hot events to each shard and flips halves
        every tick.  An empty set leaves routing bit-identical."""
        secondary = route_secondary(batch.key, _salt(dest_op),
                                    ring_hashes, ring_shards)
        is_hot = jnp.any((batch.key[:, None] == hot_keys[None, :])
                         & hot_valid[None, :], axis=1)
        flip = ((jnp.arange(batch.capacity, dtype=jnp.int32) ^ tick)
                & 1) == 1
        return jnp.where(is_hot & flip & batch.valid, secondary, primary)

    def _tick_inputs(self):
        """The ring and the hot-key split set as runtime tick inputs:
        contents swap, shapes never do, so nothing recompiles."""
        rh, rs = self.ring.table()
        return (rh, rs, jnp.asarray(self._hot_keys),
                jnp.asarray(self._hot_valid))

    # ---- jit plumbing ----
    def _spec_like(self, tree):
        """Leading-dim-n_shards leaves are sharded, the rest replicated."""
        sharded, rep = P(self.axes), P()
        return jax.tree.map(
            lambda x: sharded
            if (hasattr(x, "ndim") and x.ndim >= 1
                and x.shape[0] == self.n_shards) else rep, tree)

    def _shard_jit(self, local_fn, src_spec, out_specs):
        """``jit(shard_map(local_fn))`` over ``(state, sources, *tick
        inputs)``, donating the state.  The specs are taken from the
        traced arguments, so a new source structure (``{}`` for a drain
        tick) traces its own program; ``out_specs(state_specs)`` gives
        the output specs."""
        rep = P()

        def run(st, src, rh, rs, hk, hv):
            specs = self._spec_like(st)
            fn = jax.shard_map(
                local_fn, mesh=self.mesh,
                in_specs=(specs, jax.tree.map(lambda _: src_spec, src),
                          rep, rep, rep, rep),
                out_specs=out_specs(specs), check_vma=False)
            return fn(st, src, rh, rs, hk, hv)

        return jax.jit(run, donate_argnums=(0,))

    def _local_chunk(self, st, src, rh, rs, hk, hv):
        def body(s, x):
            s2, outs = self._local_tick(s, x, rh, rs, hk, hv)
            return s2, (outs, s2["throttle_hits"])
        final, (outs, hits) = jax.lax.scan(body, st, src)
        return final, outs, hits

    def _build_tick_programs(self):
        sharded, stacked = P(self.axes), P(None, self.axes)
        self._step = self._shard_jit(self._local_tick, sharded,
                                     lambda specs: sharded)
        self._chunk = self._shard_jit(
            self._local_chunk, stacked,
            lambda specs: (specs, stacked, stacked))

    def step(self, state, sources: Dict[str, EventBatch]):
        """sources: global batches with leading dim n_shards*B_loc or
        [n_shards, B_loc] — pass [n_shards, B_loc] (leading shard axis)."""
        return self._step(state, sources, *self._tick_inputs())

    def run_chunk(self, state, stacked_sources: Dict[str, EventBatch]):
        """T device-resident ticks in one dispatch (DESIGN.md 2.2).

        ``stacked_sources`` leaves are [T, n_shards, B, ...] — tick axis
        leading (scanned), shard axis second (split by shard_map).
        Returns ``(state, stacked_outputs, info)``; output leaves are
        [T, n_shards, ...] and ``info['throttle_hits']`` is the
        [T, n_shards] on-device per-tick trace, so the host syncs once
        per chunk for the backpressure signal.
        """
        state, outs, hits = self._chunk(state, stacked_sources,
                                        *self._tick_inputs())
        return state, outs, {"throttle_hits": hits}

    # ---- durability (DESIGN.md section 10): per-shard WAL + frontier --
    def attach_durability(self, cfg: DurabilityConfig):
        """One WAL per shard (on durable storage, the role Cassandra's
        commit log plays), one shared slate store, one barrier frontier.
        Incompatible with two-choice dispatch: partial aggregates of the
        same key on two shards would clobber each other in the store."""
        if self.cfg.two_choice_threshold:
            raise ValueError("durability requires two_choice_threshold=0 "
                             "(per-key partials are not store-mergeable)")
        self.dur = EngineDurability(cfg, self.wf,
                                    self.cfg.queue_capacity,
                                    self.cfg.batch_size,
                                    n_shards=self.n_shards,
                                    tracer=self.tracer)

    def append_sources(self, tick: int, sources: Dict[str, EventBatch]):
        """Write-ahead: log each shard's slice of the [n_shards, B]
        source batches to that shard's WAL (call before ``step``).

        The device_get and the per-shard slicing run as one deferred
        thunk on the durability writer thread: the dispatch path only
        pays the enqueue.  Step/chunk dispatches never donate source
        buffers, so the captured device arrays stay valid until the
        thunk resolves; the frontier fence orders the thunk before any
        frontier that must cover this tick."""
        n_shards, dur = self.n_shards, self.dur

        def _log():
            host = {s: jax.tree.map(
                lambda x: np.asarray(jax.device_get(x)), b)
                for s, b in sources.items()}
            for sh in range(n_shards):
                sl = {s: EventBatch(sid=b.sid[sh], ts=b.ts[sh],
                                    key=b.key[sh],
                                    value=jax.tree.map(lambda x: x[sh],
                                                       b.value),
                                    valid=b.valid[sh])
                      for s, b in host.items()}
                sl = {s: b for s, b in sl.items() if b.valid.any()}
                dur._do_append(int(tick), sl, sh)

        dur.append_deferred(_log)

    def _step_empty(self, state):
        """One source-less tick (drain barriers, replay gap ticks)."""
        return self.step(state, {})[0]

    # one drain barrier for both engines: queue sizes sum over shards
    _drain_queues = Engine._drain_queues
    drain = Engine.drain

    def _flush_boundary(self, state, tick: int, meta=None):
        """Barrier-drain, flush every shard's dirty slates (one
        device_get per table), record the frontier.  ``meta`` is the
        driver cursor stored with the frontier (``_run_span`` records
        the source index, mirroring ``Engine.run``)."""
        dur = self.dur
        if dur.cfg.barrier:
            state, d = self._drain_queues(state, dur.cfg.drain_ticks_max)
            tick += d
        new_tables = {}
        for up in self.wf.updaters():
            t = state["tables"][up.name]
            dirty = np.asarray(jax.device_get(t.dirty))
            keys = np.asarray(jax.device_get(t.keys))
            ts = np.asarray(jax.device_get(t.ts))
            vals = jax.tree.map(lambda v: np.asarray(jax.device_get(v)),
                                t.vals)
            for sh in range(self.n_shards):
                idx = np.nonzero(dirty[sh] & (keys[sh] != -1))[0]
                dur.flusher.flush_rows(
                    up.name, keys[sh][idx], ts[sh][idx],
                    jax.tree.map(lambda v: v[sh][idx], vals), up.ttl)
            new_tables[up.name] = replace(
                t, dirty=jnp.zeros_like(t.dirty))
        state = dict(state)
        state["tables"] = new_tables
        dur.record_frontier(tick, meta=meta)
        return state, tick

    def run(self, state, source_fn, n_ticks: int, *, start_tick: int = 0,
            handle=None):
        """Uniform host driver (same shape as ``Engine.run``):
        ``source_fn(tick, max_events) -> dict[stream, EventBatch]`` with
        [n_shards, B]-leading batches; ``max_events`` is always ``None``
        here (per-shard backpressure is the exchange/queue bound, not a
        host-side ingest limit).  With durability attached, sources are
        write-ahead logged per shard and flush boundaries fire per the
        flush policy — the ``run_durable`` path.  ``handle`` (a
        :class:`~repro.core.engine.StateHandle`) is republished every
        tick.  Returns ``(state, outputs)`` with one output dict per
        source tick; the post-run tick cursor (drain ticks included) is
        left on ``self.tick_cursor`` for durable drivers that resume.

        With ``cfg.autoscale`` set to an :class:`AutoscalePolicy`, the
        drive loop fires live reconfigures at the policy's source-tick
        boundaries: ``scale_at[t]`` rescales the active shard set
        before tick ``t`` runs, and every ``rebalance_every`` ticks the
        weighted ring is rebuilt from the per-shard load signal.  With
        a :class:`~repro.telemetry.LoadAutoscaler` the loop closes
        instead: every decision window the telemetry registry reads the
        boundary signals and the controller picks scale / rebalance /
        split (DESIGN.md 13.3).  Either way ``source_fn`` must size its
        batches by the *current* ``self.n_shards``.

        Source index and engine tick are decoupled (the ``Engine.run``
        split ported here): ``source_fn`` sees consecutive indices
        ``start_tick .. start_tick + n_ticks`` regardless of flush or
        reconfigure drain ticks — WAL records are keyed by the engine
        tick, the frontier meta records the source cursor."""
        pol = self.cfg.autoscale
        self._live_handle = handle
        if pol is None:
            return self._run_span(state, source_fn, n_ticks,
                                  start_tick=start_tick, handle=handle)
        if isinstance(pol, LoadAutoscaler):
            return self._run_closed_loop(state, source_fn, n_ticks, pol,
                                         start_tick=start_tick,
                                         handle=handle)
        end = start_tick + n_ticks
        marks = {t for t in pol.scale_at if start_tick <= t < end}
        if pol.rebalance_every:
            marks |= {t for t in range(start_tick, end)
                      if t > start_tick
                      and (t - start_tick) % pol.rebalance_every == 0}
        outputs: List[Dict[str, Any]] = []
        t = start_tick
        self.tick_cursor = t
        for boundary in sorted(marks) + [end]:
            if boundary > t:
                state, outs = self._run_span(state, source_fn,
                                             boundary - t, start_tick=t,
                                             handle=handle)
                outputs.extend(outs)
                t = boundary
            if boundary < end:          # fire before tick `boundary` runs
                if boundary in pol.scale_at:
                    state, rep = self.scale(state, pol.scale_at[boundary],
                                            drain_max=pol.drain_max)
                else:
                    state, rep = self.rebalance(state,
                                                drain_max=pol.drain_max)
                if rep is not None and pol.on_change is not None:
                    pol.on_change(rep)
                if handle is not None:
                    handle.state = state
        self.tick_cursor = max(t, self.tick_cursor)
        return state, outputs

    def _run_closed_loop(self, state, source_fn, n_ticks: int, pol, *,
                         start_tick: int = 0, handle=None):
        """Observe -> decide -> act (DESIGN.md 13.3): run one decision
        window of source ticks, take the boundary telemetry reading,
        and let the :class:`LoadAutoscaler` choose an actuator.  The
        sketch ages at every window so heat stays recent."""
        assert self.telemetry is not None
        outputs: List[Dict[str, Any]] = []
        t = start_tick
        end = start_tick + n_ticks
        limit = pol.max_shards or len(jax.devices())
        lead = self._lead_axis_size()
        if lead > 1:
            # multi-axis meshes grow along their trailing axis, so the
            # reachable ceiling is the largest multiple of the leading
            # axes' product (never below the current physical size)
            limit = max(self.n_shards, (limit // lead) * lead)
        while t < end:
            n = min(pol.window - (t - start_tick) % pol.window, end - t)
            state, outs = self._run_span(state, source_fn, n,
                                         start_tick=t, handle=handle)
            outputs.extend(outs)
            t += n
            with span(self.tracer, "telemetry_observe", tick=t):
                report = self.telemetry.observe(self, state)
            if "sketch" in state:
                state = dict(state)
                state["sketch"] = sk_mod.decay(state["sketch"],
                                               self.tele_cfg.decay)
            action = pol.decide(
                report, n_active=len(self.active_shards), limit=limit,
                can_split=(self.dur is None and self._hot_capacity > 0),
                already_split=tuple(self.split_key_set()))
            rep = None
            if action is not None and t < end:
                t0 = time.perf_counter()
                if action.kind == "scale":
                    state, rep = self.scale(state, action.target,
                                            drain_max=pol.drain_max)
                elif action.kind == "rebalance":
                    w = pol.heat_weights(report, owners=self.heat_owners)
                    state, rep = self.rebalance(state, weights=w,
                                                drain_max=pol.drain_max)
                elif action.kind == "split":
                    state, rep = self.split_keys(state, action.keys)
                self.telemetry.note_pause(
                    rep.pause_s if rep is not None
                    else time.perf_counter() - t0,
                    bytes_moved=rep.bytes_moved if rep is not None
                    else 0)
                self.telemetry.rebase(self, state)
                if rep is not None and pol.on_change is not None:
                    pol.on_change(rep)
                if handle is not None:
                    handle.state = state
            if self._ctl_log is not None:
                self._ctl_log.log({
                    "tick": t,
                    "pressure": [float(x) for x in
                                 np.asarray(report.pressure).ravel()],
                    "event_latency_p99": report.event_latency_p99,
                    "queue_depth": float(
                        np.asarray(report.queue_depth).sum()),
                    "n_active": len(self.active_shards),
                    "action": None if action is None else {
                        "kind": action.kind, "target": action.target,
                        "keys": [int(k) for k in action.keys],
                        "reason": action.reason},
                    "applied": None if rep is None else {
                        "path": rep.path, "pause_s": rep.pause_s,
                        "moved_rows": rep.moved_rows,
                        "bytes_moved": rep.bytes_moved},
                })
        self.tick_cursor = t
        return state, outputs

    def _run_span(self, state, source_fn, n_ticks: int, *,
                  start_tick: int = 0, handle=None):
        """The inner drive loop.  Source index (``source_fn``'s ``t``)
        and engine tick (the WAL key, which also counts drain ticks)
        are tracked separately — the single-shard ``eng_tick`` +
        frontier ``meta.source_tick`` split ported from ``Engine.run``
        — so durable flush drains never consume source indices and
        ``source_fn`` is invoked exactly ``n_ticks`` times with
        consecutive indices, even across mid-run reconfigures."""
        outputs = []
        src_t = start_tick
        self._live_handle = handle
        eng_tick = int(np.asarray(jax.device_get(state["tick"])).max()) \
            if self.dur is not None else 0
        # without a closed-loop controller (which observes at its own
        # decision windows), this span keeps App.telemetry() fresh by
        # reading at every cfg window boundary
        observe = (self.telemetry is not None
                   and not isinstance(self.cfg.autoscale,
                                      LoadAutoscaler))
        obs_mark = start_tick
        for _ in range(n_ticks):
            srcs = source_fn(src_t, None)
            if self.dur is not None:
                self.append_sources(eng_tick, srcs)
            # step donates (deletes) the buffers a handle reader may be
            # holding: lock from dispatch until the fresh state is
            # republished
            with self.read_lock:
                state, outs = self.step(state, srcs)
                outputs.append(outs)
                src_t += 1
                eng_tick += 1
                if self.dur is not None and self.dur.due(
                        eng_tick, state["tables"]):
                    with span(self.tracer, "flush_boundary",
                              tick=eng_tick, source_tick=src_t):
                        state, eng_tick = self._flush_boundary(
                            state, eng_tick, meta={"source_tick": src_t})
                    if handle is not None:
                        handle.on_frontier_advance()
                if observe and src_t - obs_mark >= self.tele_cfg.window:
                    with span(self.tracer, "telemetry_observe",
                              tick=src_t):
                        report = self.telemetry.observe(self, state)
                    if handle is not None:
                        handle.on_telemetry(report)
                    state = dict(state)
                    state["sketch"] = sk_mod.decay(state["sketch"],
                                                   self.tele_cfg.decay)
                    obs_mark = src_t
                if handle is not None:
                    handle.state = state
        self.tick_cursor = src_t
        return state, outputs

    def run_durable(self, state, source_fn, n_ticks: int, *,
                    start_tick: int = 0):
        """Host driver: per-tick step with write-ahead logging and
        policy-driven flush boundaries.  ``source_fn(tick)`` returns
        [n_shards, B]-leading source batches.  Returns
        ``(state, next_source_tick)`` — the source cursor, which flush
        drain ticks no longer consume.  Thin wrapper over :meth:`run`
        — one durable drive loop to maintain."""
        assert self.dur is not None, "attach_durability first"
        state, _ = self.run(state, lambda t, _mx: source_fn(t), n_ticks,
                            start_tick=start_tick)
        return state, self.tick_cursor

    def recover(self, *, frontier=None):
        """Rebuild sharded state after losing any subset of machines:
        flushed slates are re-inserted on whatever shard the *current*
        ring routes them to (so a dead shard's keys land on survivors —
        the elastic-restore move of ``distributed/checkpoint.py``:
        host rows -> ``device_put`` with the target sharding), then each
        shard's WAL suffix replays through the shard_map tick, which
        re-routes every replayed event with the current ring."""
        dur = self.dur
        assert dur is not None, "attach_durability first"
        t_recover = time.perf_counter()
        frontier = frontier or dur.frontier
        f_tick = int(frontier.tick)
        offs = list(frontier.wal_offset) \
            if isinstance(frontier.wal_offset, (list, tuple)) \
            else [frontier.wal_offset] * self.n_shards
        if len(offs) < self.n_shards:   # frontier predates a scale-up:
            offs += [0] * (self.n_shards - len(offs))  # replay new WALs
                                                       # from the start
        # frontier from a *larger* pre-crash shard set (scaled up, then
        # restarted smaller): the extra shards' WAL suffixes must replay
        # too — their events re-route by the current ring anyway
        extra_wals = []
        if len(offs) > len(dur.wals):
            from repro.slates.wal import WriteAheadLog
            extra_wals = [WriteAheadLog(dur.cfg.wal_path(s),
                                        sync=dur.cfg.sync_wal)
                          for s in range(len(dur.wals), len(offs))]

        state = jax.device_get(self.init_state())
        state["tick"] = np.full((self.n_shards,), f_tick, np.int32)
        rh, rs = self.ring.table()
        with span(self.tracer, "recover_restore", frontier=f_tick):
            for up in self.wf.updaters():
                keys, ts, slates = dur.store.scan_columns(
                    up.name, now=f_tick if up.ttl else None)
                if not keys.size:
                    continue
                ks = keys.astype(self.key_dtype)
                shard_of = np.asarray(jax.device_get(
                    route(jnp.asarray(ks), _salt(up.name), rh, rs)))
                t = state["tables"][up.name]
                per_shard = []
                for sh in range(self.n_shards):
                    local = jax.tree.map(lambda x: jnp.asarray(x[sh]), t)
                    sel = np.nonzero(shard_of == sh)[0]
                    if len(sel):
                        local = flush_mod.restore_into(
                            local, ks[sel],
                            jax.tree.map(lambda v: v[sel], slates),
                            ts[sel].astype(np.int32))
                    per_shard.append(jax.device_get(local))
                state["tables"][up.name] = jax.tree.map(
                    lambda *xs: np.stack(xs), *per_shard)
            state = jax.tree.map(
                jnp.asarray, state,
                is_leaf=lambda x: isinstance(x, np.ndarray))
            state = jax.device_put(state, self._shard_tree(state))

        cur = f_tick
        with span(self.tracer, "recover_replay", frontier=f_tick) as sp:
            try:
                for tk, by_shard in merge_replay_ticks(
                        list(dur.wals) + extra_wals, offs):
                    if tk < f_tick:
                        continue
                    if len(offs) > self.n_shards:
                        by_shard = self._fold_shard_sources(by_shard)
                    while cur < tk:
                        state = self._step_empty(state)
                        cur += 1
                    state, _ = self.step(state, self._stack_shard_sources(
                        by_shard))
                    cur += 1
            finally:
                for w in extra_wals:
                    w.close()
            sp["replayed_ticks"] = cur - f_tick
        if self.telemetry is not None:
            self.telemetry.note_recovery(
                time.perf_counter() - t_recover)
        return state

    def _fold_shard_sources(self, by_shard: Dict[int, Dict[str, Any]]
                            ) -> Dict[int, Dict[str, Any]]:
        """Fold replay records from shard slots beyond the current
        physical size onto live slots (source slot is irrelevant — the
        tick re-routes every event by key through the current ring)."""
        folded: Dict[int, Dict[str, Any]] = {}
        for sh, src in sorted(by_shard.items()):
            tgt = sh % self.n_shards
            cur = folded.setdefault(tgt, {})
            for s, b in src.items():
                cur[s] = b if s not in cur else concat(
                    [jax.tree.map(jnp.asarray, cur[s]),
                     jax.tree.map(jnp.asarray, b)])
        return folded

    def _stack_shard_sources(self, by_shard: Dict[int, Dict[str, Any]]
                             ) -> Dict[str, EventBatch]:
        """Per-shard replay records -> [n_shards, B] source batches
        (missing shards/streams become all-invalid rows)."""
        caps: Dict[str, int] = {}
        tmpl: Dict[str, EventBatch] = {}
        for src in by_shard.values():
            for s, b in src.items():
                if s not in caps or b.capacity > caps[s]:
                    caps[s], tmpl[s] = b.capacity, b

        def one(sh, s):
            b = by_shard.get(sh, {}).get(s)
            if b is None:
                t = tmpl[s]
                return EventBatch.empty(
                    caps[s], jax.tree.map(
                        lambda a: (a.shape[1:], a.dtype), t.value),
                    key_dtype=t.key.dtype)
            return EventBatch(sid=jnp.asarray(b.sid),
                              ts=jnp.asarray(b.ts),
                              key=jnp.asarray(b.key),
                              value=jax.tree.map(jnp.asarray, b.value),
                              valid=jnp.asarray(b.valid)).pad_to(caps[s])

        return {s: jax.tree.map(
            lambda *xs: jnp.stack(xs),
            *[one(sh, s) for sh in range(self.n_shards)])
            for s in tmpl}

    def close(self):
        if self.dur is not None:
            self.dur.close()
        if self._ctl_log is not None:
            self._ctl_log.close()

    # ---- failure / elasticity (host side; master of section 4.3) ----
    def fail_shard(self, state, shard: int):
        """Machine crash: re-route ring; the dead shard's unflushed slates
        and queued events are lost (paper semantics).  The ring table is
        shape-stable (padded), so the swap needs no recompilation —
        contrast :meth:`scale` / :meth:`remove_shards`, whose planned
        membership changes migrate state loss-free first."""
        self.ring.fail(shard)

        def zap(leaf):
            if hasattr(leaf, "ndim") and leaf.ndim >= 1 and \
                    leaf.shape[0] == self.n_shards:
                return leaf.at[shard].set(jnp.zeros_like(leaf[shard]))
            return leaf

        state = dict(state)
        state["queues"] = jax.tree.map(zap, state["queues"])
        # tables: mark every slot empty on the dead shard
        new_tables = {}
        for name, t in state["tables"].items():
            keys = t.keys.at[shard].set(
                jnp.full_like(t.keys[shard], tbl.EMPTY))
            dirty = t.dirty.at[shard].set(
                jnp.zeros_like(t.dirty[shard]))
            new_tables[name] = replace(t, keys=keys, dirty=dirty)
        state["tables"] = new_tables
        return state

    # ---- live elasticity (DESIGN.md section 12) ---------------------
    @property
    def active_shards(self) -> List[int]:
        return [int(s) for s in np.nonzero(self.ring.alive)[0]]

    def scale(self, state, new_n_shards: int, *, drain_max: int = 64):
        """Live resize to ``new_n_shards`` *active* shards, loss-free.

        Scale-up reactivates dead slots first (content-only ring swap,
        no recompilation), then grows the physical slot count / mesh if
        needed (the one move that recompiles).  Scale-down deactivates
        the highest-numbered active shards and migrates everything off
        them.  Returns ``(state, MigrationReport)``.
        """
        if new_n_shards < 1:
            raise ValueError("need at least one active shard")
        active = self.active_shards
        if new_n_shards == len(active):
            return state, self._report(0, {}, {}, recompiled=False)
        if new_n_shards < len(active):
            return self.remove_shards(state, active[new_n_shards:],
                                      drain_max=drain_max)
        dead = [s for s in range(self.n_shards) if not self.ring.alive[s]]
        activate = dead[:new_n_shards - len(active)]
        grow_to = new_n_shards if len(active) + len(activate) \
            < new_n_shards else None
        return self._reconfigure(state, grow_to=grow_to,
                                 activate=activate, drain_max=drain_max)

    def add_shards(self, state, k: int, *, drain_max: int = 64):
        """Grow the active shard set by ``k`` (elastic join)."""
        return self.scale(state, len(self.active_shards) + k,
                          drain_max=drain_max)

    def remove_shards(self, state, shards, *, drain_max: int = 64):
        """Planned leave: migrate the given shards' slates and queued
        events to the survivors, then deactivate them — loss-free,
        unlike :meth:`fail_shard`.  Content-only ring swap (the slots
        stay allocated; rejoin them later via :meth:`scale`)."""
        shards = [int(s) for s in np.atleast_1d(shards)]
        for s in shards:
            if s >= self.n_shards or not self.ring.alive[s]:
                raise ValueError(f"shard {s} is not active")
        if len(self.active_shards) - len(shards) < 1:
            raise ValueError("cannot remove every active shard")
        return self._reconfigure(state, deactivate=shards,
                                 drain_max=drain_max)

    def shard_load(self, state) -> np.ndarray:
        """Per-shard pressure signal from the queue stats: high-water
        marks + backlog, with drops weighted heavier (a dropping shard
        is past saturation)."""
        load = np.zeros(self.n_shards)
        for q in state["queues"].values():
            g = lambda x: np.asarray(jax.device_get(x), np.float64)
            load += g(q.peak) + g(q.size) + 4.0 * g(q.dropped)
        return load

    def _rebase_load_window(self, state, load: Optional[np.ndarray] = None):
        """Restart the rebalance load window at the current pressure.

        Shared by ``rebalance()``'s no-op exits and ``_reconfigure``:
        the next window's delta must measure only load accrued *after*
        this point.  Queue peaks restart at migrations, and a
        controller invoking ``rebalance()`` back-to-back (outside the
        cadence path) must see an empty window — not recycled history
        that would reweight twice for the same pressure."""
        self._load_mark = self.shard_load(state) if load is None else load

    def rebalance(self, state, *, gain: float = 0.5, floor: float = 0.25,
                  cap: float = 4.0, drain_max: int = 64, weights=None):
        """Load-aware ring reweighting: shards whose queues ran hot
        since the last rebalance shed vnode arcs (key ranges) to cold
        shards.  Content-only ring swap + row migration — no
        recompilation.  Returns ``(state, report_or_None)``.

        ``weights``: explicit per-shard target weights (e.g. the
        sketch-informed heat weights of
        :meth:`~repro.telemetry.LoadAutoscaler.heat_weights`) instead
        of the queue-delta heuristic; they are clipped to
        ``[floor, cap]`` and no-op reweights are still skipped."""
        alive = self.ring.alive
        if weights is not None:
            w = np.clip(np.asarray(weights, np.float64), floor, cap)
            target = np.where(alive, w, self.ring.weights)
        else:
            load = self.shard_load(state)
            if load.shape != self._load_mark.shape:
                self._load_mark = np.zeros_like(load)
            delta = np.clip(load - self._load_mark, 0.0, None)
            mean = float(delta[alive].mean()) if alive.any() else 0.0
            if mean <= 0.0:
                self._rebase_load_window(state, load)
                return state, None
            # cold shards (delta < mean) gain weight, hot shards lose
            # it; gain damps the step, floor/cap bound the skew.  Dead
            # slots keep their stored weight — their zero load is
            # absence, not coldness, and must not compound toward cap
            # across windows
            ratio = (mean + 1.0) / (delta + 1.0)
            target = self.ring.weights * np.power(ratio, gain)
            target = np.clip(target / target[alive].mean(), floor, cap)
            target = np.where(alive, target, self.ring.weights)
        if np.array_equal(self.ring.vnode_counts(),
                          self.ring.counts_for(target)):
            # balanced load: the reweight would not move a single vnode
            # — skip the drain barrier + host remap entirely
            self._rebase_load_window(state)
            return state, None
        return self._reconfigure(state, weights=target,
                                 drain_max=drain_max)

    # ---- runtime hot-key splitting (DESIGN.md 13.4) -----------------
    def split_keys(self, state, keys):
        """Live hotspot relief for heavy-hitter keys (paper Example 6
        made runtime): register ``keys`` in the hot set so their events
        spread across the key's primary *and* secondary ring shard;
        ``read_slate`` merges the (<= 2) partials with the updater's
        combine — the same contender bound the paper accepts for
        two-choice dispatch.  Content-only swap of a fixed-shape array:
        no recompilation, no migration, takes effect next tick.
        Returns ``(state, None)``; undo with :meth:`clear_split`."""
        if self._hot_capacity == 0:
            raise ValueError(
                "split_keys needs the hot-key split path compiled in: "
                "set DistConfig.hot_key_capacity > 0 (or use a "
                "LoadAutoscaler with skew > 0) together with "
                "cfg.telemetry, durability off")
        if self.dur is not None:
            raise ValueError(
                "split_keys requires durability off: per-key partials "
                "are not store-mergeable (the two_choice_threshold "
                "constraint)")
        if len(self.active_shards) < 2:
            return state, None
        cur = [int(k) for k, v in zip(self._hot_keys, self._hot_valid)
               if v]
        for k in keys:
            if int(k) not in cur:
                cur.append(int(k))
        # active splits keep priority: evicting one would strand its
        # partials (read_slate stops merging the secondary) — new keys
        # beyond capacity wait for clear_split
        cur = cur[:self._hot_capacity]
        hk = np.zeros_like(self._hot_keys)
        hv = np.zeros_like(self._hot_valid)
        hk[:len(cur)] = cur
        hv[:len(cur)] = True
        self._hot_keys, self._hot_valid = hk, hv
        return state, None

    def clear_split(self, state, *, drain_max: int = 64):
        """Deactivate every hot-key split and converge the partials:
        one same-ring reconfigure whose table rebuild folds duplicate
        keys via the updater's combine, so each formerly-split key ends
        up whole on its owner shard again."""
        if not self._hot_valid.any():
            return state, None
        self._hot_valid = np.zeros_like(self._hot_valid)
        return self._reconfigure(state, drain_max=drain_max)

    def split_key_set(self) -> List[int]:
        """Currently split (hot) keys."""
        return [int(k) for k, v in zip(self._hot_keys, self._hot_valid)
                if v]

    def heat_owners(self, keys) -> np.ndarray:
        """Ring owner per key *per updater* — [n_updaters, K], one row
        per updater salt, the heavy-hitter -> arc attribution used by
        :meth:`~repro.telemetry.LoadAutoscaler.heat_weights`.  Routing
        is salted by destination, so a key heavy for two updaters heats
        two (generally different) shards; the sketch counts the key once
        per subscribing updater's dequeue, and ``heat_weights`` splits a
        hitter's estimated mass evenly across these rows."""
        ups = list(self.wf.updaters())
        ks = np.asarray(keys, self.key_dtype)
        if not ups:
            return np.zeros((1, len(ks)), np.int32)
        return np.stack([self.ring.owners(ks, _salt(u.name))
                         for u in ups])

    def _report(self, drain_ticks, moved_rows, moved_events, *,
                recompiled: bool, pause_s: float = 0.0,
                bytes_moved: int = 0, path: str = "host"
                ) -> MigrationReport:
        return MigrationReport(
            n_shards=self.n_shards, active=self.active_shards,
            drain_ticks=drain_ticks, moved_rows=moved_rows,
            moved_events=moved_events, recompiled=recompiled,
            pause_s=pause_s, bytes_moved=bytes_moved, path=path)

    def _reconfigure(self, state, *, grow_to: Optional[int] = None,
                     activate=(), deactivate=(), weights=None,
                     drain_max: int = 64, force_compact: bool = False):
        """The migration kernel behind scale/remove/rebalance:

        1. drain-barrier the queues (and flush, with durability);
        2. swap in the new ring (membership / weights / physical size);
        3. re-home slate rows to their new owners — on device when the
           physical shapes are unchanged and the barrier emptied the
           queues (``exchange_rows`` under shard_map: no host round
           trip), else the host remap + ``device_put`` fallback (the
           elastic-restore move of ``distributed/checkpoint.py``),
           which also re-homes any leftover queued events;
        4. resume on the swapped ring — recompilation only if the
           physical slot count changed (grow, or compaction shrink).

        Both tiers yield bitwise-identical slates (DESIGN.md 14.3).

        Runs under ``read_lock``: concurrent slate readers must observe
        either the pre-migration state (old ring, rows in place) or the
        post-migration state — never a half-swapped ring over mid-
        exchange rows, and never the deleted buffers the drain steps
        donate.  The live :class:`StateHandle` (when a driver published
        one) is re-pointed at the migrated state *before* the lock is
        released, so a reader waking on the lock can never see a handle
        still bound to pre-migration (freed) state.
        """
        with self.read_lock:
            with span(self.tracer, "reconfigure") as sp:
                state, report = self._reconfigure_impl(
                    state, grow_to=grow_to, activate=activate,
                    deactivate=deactivate, weights=weights,
                    drain_max=drain_max, force_compact=force_compact)
                # reconcile the report's measured pause with the traced
                # span: pause_s was clocked inside the impl, so the
                # span's dur (same region plus handle repoint) must
                # bound it from above — a cheap invariant the trace
                # tests assert
                sp["pause_s"] = report.pause_s
                sp["path"] = report.path
                sp["n_shards"] = report.n_shards
                sp["drain_ticks"] = report.drain_ticks
            if self._live_handle is not None:
                self._live_handle.state = state
        return state, report

    def _reconfigure_impl(self, state, *, grow_to=None, activate=(),
                          deactivate=(), weights=None, drain_max=64,
                          force_compact=False):
        t_start = time.perf_counter()
        state, drained = self._drain_queues(state, drain_max)
        if self.dur is not None:
            tick = int(np.asarray(jax.device_get(state["tick"])).max())
            # the barrier retired every source fed so far, so the
            # frontier's driver cursor advances to the current source
            # cursor (monotone: a reconfigure on a freshly-recovered
            # engine must not regress a prior run's recorded cursor) —
            # with truncate_wal, a stale cursor would re-feed
            # already-flushed source ticks after a crash
            prev = (self.dur.frontier.meta or {}).get("source_tick", 0)
            meta = {"source_tick": max(int(prev),
                                       int(self.tick_cursor))}
            state, _ = self._flush_boundary(state, tick, meta=meta)
        old_n = self.n_shards

        grew = grow_to is not None and grow_to > old_n
        if grew:
            self._grow_physical(grow_to)
        for s in activate:
            self.ring.join(int(s))
        for s in deactivate:
            self.ring.fail(int(s))
        if weights is not None:
            self.ring.set_weights(weights)

        compacting = False
        if not grew:
            n_active = len(self.active_shards)
            dead_frac = 1.0 - n_active / self.n_shards
            want = force_compact or (
                self.cfg.compact_threshold > 0.0
                and dead_frac >= self.cfg.compact_threshold)
            if want and n_active < self.n_shards:
                lead = self._lead_axis_size()
                if n_active % lead == 0:
                    compacting = True
                elif force_compact:
                    raise ValueError(
                        f"cannot compact to {n_active} shards on a "
                        f"multi-axis mesh: the active count must be a "
                        f"multiple of the leading axes' product {lead}")

        use_device = (not grew and not compacting
                      and self.cfg.device_migration != "off")
        if use_device:
            # a non-empty backlog (planned leave with drain_max=0, or a
            # barrier that could not retire the queues) stays on this
            # path too: exchange_queue re-homes queued events with the
            # same all_to_all and rebases peaks at the new backlog
            state, moved_rows, moved_events, bytes_moved = \
                self._migrate_device(state)
            path = "device"
        else:
            host = jax.device_get(state)
            slot_map = None
            if grew:
                host = self._host_grow(host, old_n)
            if compacting:
                host, slot_map = self._compact_physical(host)
            moved_rows = self._migrate_tables_host(host["tables"],
                                                   slot_map=slot_map)
            moved_events = self._migrate_queues_host(host["queues"],
                                                     slot_map=slot_map)
            bytes_moved = self._bytes_of(moved_rows, moved_events)
            state = jax.tree.map(
                jnp.asarray, host,
                is_leaf=lambda x: isinstance(x, np.ndarray))
            state = jax.device_put(state, self._shard_tree(state))
            path = "host"
        if self.dur is not None:
            self.dur.resize(self.n_shards)
        # queue peak counters restarted at migration: rebase the
        # rebalance window on the post-migration load, or the next
        # window's delta would subtract peaks that no longer exist
        jax.block_until_ready(state["tables"])
        self._rebase_load_window(state)
        return state, self._report(
            drained, moved_rows, moved_events,
            recompiled=grew or compacting,
            pause_s=time.perf_counter() - t_start,
            bytes_moved=bytes_moved, path=path)

    def _queues_empty(self, state) -> bool:
        sizes = jax.device_get({k: q.size
                                for k, q in state["queues"].items()})
        return all(int(np.asarray(v).sum()) == 0
                   for v in sizes.values())

    def _reset_queue_peaks(self, state):
        """Rebase every queue's high-water mark at its current backlog
        (the host migrator's ``peak=new_sizes``) so the next rebalance
        window measures post-migration load only."""
        state = dict(state)
        state["queues"] = {
            name: q_mod.QueueState(
                buf=q.buf, head=q.head, size=q.size, dropped=q.dropped,
                peak=jax.device_put(jnp.copy(q.size),
                                    self._sharding))
            for name, q in state["queues"].items()}
        return state

    def _lead_axis_size(self) -> int:
        """Product of every mesh axis size except the trailing one —
        the granularity physical grow/compact must respect."""
        return int(np.prod([self.mesh.shape[a]
                            for a in self.axes[:-1]], dtype=np.int64)) \
            if len(self.axes) > 1 else 1

    def _row_bytes(self, up) -> int:
        n = self.key_dtype.itemsize + 4 + 1   # key + ts + dirty
        for leaf in jax.tree.leaves(up.slate_spec(),
                                    is_leaf=tbl._is_spec_leaf):
            shp, dt = leaf
            n += int(np.prod(shp, dtype=np.int64)) * np.dtype(dt).itemsize
        return n

    def _event_bytes(self, op) -> int:
        n = 4 * 2 + self.key_dtype.itemsize + 1  # sid + ts + key + valid
        for leaf in jax.tree.leaves(op.in_value_spec,
                                    is_leaf=tbl._is_spec_leaf):
            shp, dt = leaf
            n += int(np.prod(shp, dtype=np.int64)) * np.dtype(dt).itemsize
        return n

    def _bytes_of(self, moved_rows, moved_events) -> int:
        total = sum(moved_rows.get(up.name, 0) * self._row_bytes(up)
                    for up in self.wf.updaters())
        total += sum(moved_events.get(op.name, 0) * self._event_bytes(op)
                     for op in self.wf.operators)
        return total

    def _migrate_device(self, state):
        """The device migration tier (DESIGN.md 14.1): count row movers
        AND queued-event movers per (src, dest) with a tiny jitted
        plan, pick pow2 bucket capacities (bounding the jit cache),
        then run ``exchange_rows`` for every updater table and
        ``exchange_queue`` for every backlogged operator queue in one
        shard_map dispatch.  Slates and events never leave the device.
        Returns ``(state, moved_rows, moved_events, bytes_moved)``."""
        updaters = list(self.wf.updaters())
        rh, rs = self.ring.table()
        tables, queues = state["tables"], state["queues"]
        if self._plan_fn is None:
            sharded, rep = P(self.axes), P()
            specs = (self._spec_like(tables), self._spec_like(queues))
            n = self.n_shards
            operators = list(self.wf.operators)

            def plan_local(tb, qs, rh_, rs_):
                me = _linear_shard_index(self.axes)
                rows = {}
                for up in updaters:
                    t = jax.tree.map(lambda x: x[0], tb[up.name])
                    owner = route(t.keys, _salt(up.name), rh_, rs_)
                    mover = (t.keys != tbl.EMPTY) & (owner != me)
                    rows[up.name] = jnp.zeros((n,), jnp.int32).at[
                        jnp.where(mover, owner, n)].add(
                            1, mode="drop")[None]
                evs = {}
                for op in operators:
                    q = jax.tree.map(lambda x: x[0], qs[op.name])
                    C = q.buf.capacity
                    pos = (q.head
                           + jnp.arange(C, dtype=jnp.int32)) % C
                    live = jnp.arange(C, dtype=jnp.int32) < q.size
                    owner = route(q.buf.key[pos], _salt(op.name),
                                  rh_, rs_)
                    # count *all* live events per dest (stayers too):
                    # exchange_queue routes everything through the
                    # buckets, so the cap must cover to-self traffic
                    evs[op.name] = jnp.zeros((n,), jnp.int32).at[
                        jnp.where(live, owner, n)].add(
                            1, mode="drop")[None]
                return {"rows": rows, "events": evs}

            def plan(tb, qs, rh_, rs_):
                return jax.shard_map(plan_local, mesh=self.mesh,
                                     in_specs=specs + (rep, rep),
                                     out_specs=sharded,
                                     check_vma=False)(tb, qs, rh_, rs_)
            self._plan_fn = jax.jit(plan)
        plan = jax.device_get(self._plan_fn(tables, queues, rh, rs))
        moved = {name: int(np.asarray(c).sum())
                 for name, c in plan["rows"].items()}
        # event movers exclude the diagonal (stayers route to-self)
        moved_ev = {name: int(np.asarray(c).sum()
                              - np.trace(np.asarray(c)))
                    for name, c in plan["events"].items()}
        maxc = max((int(np.asarray(c).max())
                    for c in plan["rows"].values()), default=0)
        ev_maxc = max((int(np.asarray(c).max())
                       for c in plan["events"].values()), default=0)
        bytes_moved = self._bytes_of(moved, moved_ev)
        if maxc == 0 and sum(moved_ev.values()) == 0:
            # nothing re-homes: tables and queues stand (the caller
            # rebases queue peaks at the standing backlog)
            return self._reset_queue_peaks(state), moved, moved_ev, 0

        def pow2(c):
            cap = 8
            while cap < c:
                cap *= 2
            return cap
        cap_rows = pow2(maxc) if maxc else 0
        cap_ev = pow2(ev_maxc) if ev_maxc else 0
        fn = self._migrate_fns.get((cap_rows, cap_ev))
        if fn is None:
            fn = self._make_migrate_fn(tables, updaters,
                                       cap_rows, cap_ev)
            self._migrate_fns[(cap_rows, cap_ev)] = fn
        state = dict(state)
        state["tables"], qs = fn(tables, queues, rh, rs)
        # peak is rebased to the backlog (= size) inside the jit, so
        # the two leaves come back aliased to one buffer — copy so the
        # next donating step dispatch doesn't donate it twice
        state["queues"] = {
            name: q_mod.QueueState(buf=q.buf, head=q.head, size=q.size,
                                   dropped=q.dropped,
                                   peak=jnp.copy(q.peak))
            for name, q in qs.items()}
        return state, moved, moved_ev, bytes_moved

    def _make_migrate_fn(self, tables, updaters, cap_rows: int,
                         cap_ev: int):
        sharded, rep = P(self.axes), P()
        specs = self._spec_like(tables)
        operators = list(self.wf.operators)

        def mig_local(tb, qs, rh_, rs_):
            out_t = {}
            for up in updaters:
                t = jax.tree.map(lambda x: x[0], tb[up.name])
                if cap_rows:
                    t, _ = exchange_rows(
                        t, _salt(up.name), rh_, rs_, self.axes,
                        cap_rows, getattr(up, "combine", None))
                out_t[up.name] = jax.tree.map(lambda x: x[None], t)
            out_q = {}
            for op in operators:
                q = jax.tree.map(lambda x: x[0], qs[op.name])
                if cap_ev:
                    q, _ = exchange_queue(q, _salt(op.name), rh_, rs_,
                                          self.axes, cap_ev)
                else:   # no backlog anywhere: rebase peak in place
                    q = q_mod.QueueState(buf=q.buf, head=q.head,
                                         size=q.size,
                                         dropped=q.dropped,
                                         peak=q.size)
                out_q[op.name] = jax.tree.map(lambda x: x[None], q)
            return out_t, out_q

        def run(tb, qs, rh_, rs_):
            qspecs = self._spec_like(qs)
            return jax.shard_map(mig_local, mesh=self.mesh,
                                 in_specs=(specs, qspecs, rep, rep),
                                 out_specs=(sharded, sharded),
                                 check_vma=False)(tb, qs, rh_, rs_)
        return jax.jit(run, donate_argnums=(0, 1))

    def compact(self, state, *, drain_max: int = 64):
        """Force physical slot compaction (DESIGN.md 14.2): shrink the
        mesh/state to the current active shard set, freeing the parked
        slots' HBM, regardless of ``compact_threshold``.  No-op when
        every slot is active.  Returns ``(state, MigrationReport)``."""
        if len(self.active_shards) == self.n_shards:
            return state, self._report(0, {}, {}, recompiled=False,
                                       path="none")
        return self._reconfigure(state, drain_max=drain_max,
                                 force_compact=True)

    def _grow_physical(self, new_n: int):
        """More shard slots: bigger mesh over more devices, bigger
        state arrays — shapes change, jit caches reset.  Multi-axis
        meshes grow along their trailing axis (``('pod','data')`` keeps
        the pod count and widens each pod), so ``new_n`` must be a
        multiple of the leading axes' product."""
        lead = self._lead_axis_size()
        if new_n % lead:
            raise ValueError(
                f"multi-axis mesh {dict(self.mesh.shape)} grows along "
                f"its trailing axis {self.axes[-1]!r}: target {new_n} "
                f"must be a multiple of {lead}")
        devs = jax.devices()
        if len(devs) < new_n:
            raise ValueError(
                f"scale to {new_n} shards needs {new_n} devices; only "
                f"{len(devs)} visible")
        shape = tuple(int(self.mesh.shape[a])
                      for a in self.axes[:-1]) + (new_n // lead,)
        self.mesh = Mesh(np.asarray(devs[:new_n]).reshape(shape),
                         self.axes)
        self.n_shards = new_n
        self.ring.grow(new_n)
        self._reset_for_new_shape()

    def _reset_for_new_shape(self):
        """Shared tail of grow/compact: rebind shardings and bucket
        capacity to the new physical size, invalidate every jit."""
        self._sharding = NamedSharding(self.mesh, P(self.axes))
        self._replicated = NamedSharding(self.mesh, P())
        cap = int(self.cfg.batch_size * self.cfg.exchange_slack
                  / self.n_shards)
        self.cap_per_dest = max(8, cap)
        self._build_tick_programs()
        self._plan_fn = None
        self._migrate_fns = {}
        self._read_fns = {}

    def _compact_physical(self, host):
        """Physical slot compaction (DESIGN.md 14.2): renumber the
        active shards onto a smaller mesh — the inverse of
        ``_host_grow``, and the move that actually frees parked HBM
        (deactivation alone keeps the full-size arrays allocated).
        The ring is rebuilt at the new size (weights carried).

        Tables and queues are left at the *old* physical size here:
        dead slots may still hold slate rows (deactivation re-homes
        ownership, not residency, on the device path), so the host
        migrators the caller runs next scan every old slice and rebuild
        at the new shard count.  Per-slot *lifetime* counters — the
        count-min sketch's counts/total/sample_n, ``processed``,
        ``exchange_dropped``, ``throttle_hits``, the queues' ``dropped``
        and the tables' ``tbl.TALLIES`` — are
        folded from the dead slots into the first survivor before
        slicing, so ``TelemetryReport`` lifetime counts stay exact
        across a compaction (the sketch key-sample ring is positional,
        not a counter: it is sliced, not summed).
        Returns ``(host, slot_map)`` where ``slot_map[d]`` is the old
        slot renumbered to new slot ``d``; durability shrinks its WAL
        set via ``resize`` after the flush barrier that preceded us."""
        actives = self.active_shards
        k, old_n = len(actives), self.n_shards
        lead = self._lead_axis_size()
        shape = tuple(int(self.mesh.shape[a])
                      for a in self.axes[:-1]) + (k // lead,)
        self.mesh = Mesh(np.asarray(jax.devices()[:k]).reshape(shape),
                         self.axes)
        self.n_shards = k
        self.ring = HashRing(k, vnodes=self.ring.vnodes,
                             weights=self.ring.weights[actives],
                             seed=self.ring.seed)
        self._reset_for_new_shape()
        idx = np.asarray(actives, np.int64)
        dead = np.asarray(sorted(set(range(old_n)) - set(
            int(a) for a in actives)), np.int64)

        def sel(leaf):
            if hasattr(leaf, "ndim") and leaf.ndim >= 1 \
                    and leaf.shape[0] == old_n:
                return np.asarray(leaf)[idx]
            return leaf

        def fold(leaf):
            """Dead slots' tallies accumulate into survivor 0, then
            slice — lifetime sums are invariant under compaction."""
            a = np.asarray(leaf).copy()
            if dead.size and a.ndim >= 1 and a.shape[0] == old_n:
                a[idx[0]] += a[dead].sum(axis=0).astype(a.dtype)
            return a[idx] if a.ndim >= 1 and a.shape[0] == old_n \
                else leaf

        counters = {"exchange_dropped", "throttle_hits", "deferred",
                    "processed"}
        out = {}
        for key, val in host.items():
            if key in ("tables", "queues"):
                out[key] = val
            elif key in counters:
                out[key] = jax.tree.map(fold, val)
            elif key == "sketch":
                out[key] = {nm: (fold(lf) if nm != "sample" else
                                 sel(lf))
                            for nm, lf in val.items()}
            else:
                out[key] = jax.tree.map(sel, val)
        # table/queue tallies stay at the old size for the host
        # migrators, which inherit ``dropped[slot_map[d]]`` (and the
        # tables' other ``tbl.TALLIES``) — park the dead slots' counts on
        # the first survivor so they carry
        if dead.size:
            def park(leaf):
                a = np.asarray(leaf).copy()
                a[idx[0]] += a[dead].sum(axis=0).astype(a.dtype)
                a[dead] = 0
                return a

            for name, t in host["tables"].items():
                out["tables"][name] = replace(
                    t, **{f: park(getattr(t, f)) for f in tbl.TALLIES})
            for name, q in host["queues"].items():
                out["queues"][name] = replace(q, dropped=park(q.dropped))
        tick = int(np.asarray(host["tick"]).max())
        out["tick"] = np.full((k,), tick, np.int32)
        return out, [int(a) for a in actives]

    def _host_grow(self, host, old_n: int):
        """Pad every [old_n, ...] leaf to the new physical size: fresh
        queues/tables/counters for the new slots, tick carried over."""
        pad_n = self.n_shards - old_n

        def pad(leaf, fill=0):
            if not (hasattr(leaf, "ndim") and leaf.ndim >= 1
                    and leaf.shape[0] == old_n):
                return leaf
            ext = np.full((pad_n,) + leaf.shape[1:], fill, leaf.dtype)
            return np.concatenate([np.asarray(leaf), ext])

        out = jax.tree.map(pad, host)
        tick = int(np.asarray(host["tick"]).max())
        out["tick"] = pad(host["tick"], fill=tick)
        new_tables = {}
        for name, t in out["tables"].items():
            keys = np.asarray(t.keys)
            keys[old_n:] = -1                   # new slots start empty
            new_tables[name] = replace(t, keys=keys)
        out["tables"] = new_tables
        return out

    def _migrate_tables_host(self, tables,
                             slot_map=None) -> Dict[str, int]:
        """Re-home slate rows whose ring owner changed (host-side).

        Every shard's table is rebuilt from scratch rather than patched
        in place: deleting moved-out rows from an open-addressing table
        would punch holes in probe chains, making rows behind a freed
        slot invisible to later lookups.  Row *values* move bit-exactly;
        ``ts``/``dirty`` are preserved; same-key rows converging on one
        shard (two-choice partials) merge via the updater's combine
        (else last-ts-wins).  Rows a destination table cannot place are
        dropped and counted — the paper's bounded-resource semantics.

        The input table's leading dim may exceed ``self.n_shards``
        (slot compaction): all old slices are scanned, the rebuild is
        stacked at the new count, and ``slot_map[d]`` names the old
        slot whose ``tbl.TALLIES`` new slot ``d`` inherits."""
        moved: Dict[str, int] = {}
        n = self.n_shards
        for up in self.wf.updaters():
            t = tables[up.name]
            keys = np.array(t.keys)
            smap = np.asarray(slot_map if slot_map is not None
                              else range(n), np.int64)
            old2new = np.full(keys.shape[0], -1, np.int64)
            old2new[smap] = np.arange(n)
            sh, slot = np.nonzero(keys != -1)
            moved[up.name] = 0
            tallies = {f: np.array(getattr(t, f)) for f in tbl.TALLIES}

            def inherited(d):
                return {f: int(v[smap[d]]) for f, v in tallies.items()}

            if len(sh) == 0:
                if keys.shape[0] != n:
                    out = []
                    for d in range(n):
                        loc = tbl.make_table(up.table_capacity,
                                             up.slate_spec(),
                                             key_dtype=self.key_dtype)
                        out.append(jax.device_get(replace(
                            loc, **{f: jnp.asarray(v, jnp.int32)
                                    for f, v in inherited(d).items()})))
                    tables[up.name] = jax.tree.map(
                        lambda *xs: np.stack(xs), *out)
                continue
            ts = np.asarray(t.ts)[sh, slot]
            dirty = np.asarray(t.dirty)[sh, slot]
            vals = jax.tree.map(lambda v: np.asarray(v)[sh, slot],
                                t.vals)
            rkeys = keys[sh, slot]
            owner = self.ring.owners(rkeys, _salt(up.name))
            moved[up.name] = int((owner != old2new[sh]).sum())
            out = [None] * n
            for d in range(n):
                pick = np.nonzero(owner == d)[0]
                loc = self._build_local_table(
                    up, inherited(d),
                    rkeys[pick], ts[pick],
                    dirty[pick],
                    jax.tree.map(lambda v: v[pick], vals))
                out[d] = jax.device_get(loc)
            tables[up.name] = jax.tree.map(
                lambda *xs: np.stack(xs), *out)
        return moved

    def _build_local_table(self, up, tallies0: Dict[str, int],
                           in_keys, in_ts, in_dirty, in_vals
                           ) -> tbl.SlateTable:
        """One shard's fresh table from migrated rows (dup keys folded
        with the updater's combine, clean rows stay clean); its
        ``tbl.TALLIES`` continue from ``tallies0`` (rows it cannot place
        count as ``dropped``)."""
        combine = getattr(up, "combine", None)
        # fold duplicate keys (two-choice partials converging here)
        first: Dict[int, int] = {}
        in_ts = np.array(in_ts)
        in_dirty = np.array(in_dirty)
        in_vals = jax.tree.map(np.array, in_vals)
        for i, k in enumerate(in_keys.tolist()):
            if k in first:
                j = first[k]
                a = jax.tree.map(lambda v: v[j], in_vals)
                b = jax.tree.map(lambda v: v[i], in_vals)
                row = combine(a, b) if combine is not None else \
                    (b if in_ts[i] >= in_ts[j] else a)
                for lf, rw in zip(jax.tree.leaves(in_vals),
                                  jax.tree.leaves(row)):
                    lf[j] = np.asarray(rw)
                in_ts[j] = max(in_ts[j], in_ts[i])
                in_dirty[j] = True
            else:
                first[k] = i
        uniq = np.asarray(sorted(first.values()), np.int64)
        in_keys = np.asarray(in_keys)[uniq]
        in_ts, in_dirty = in_ts[uniq], in_dirty[uniq]
        in_vals = jax.tree.map(lambda v: v[uniq], in_vals)

        local = replace(
            tbl.make_table(up.table_capacity, up.slate_spec(),
                           key_dtype=self.key_dtype),
            **{f: jnp.asarray(v, jnp.int32) for f, v in tallies0.items()})
        for i in range(0, len(in_keys), 256):
            k = jnp.asarray(in_keys[i:i + 256], self.key_dtype)
            valid = jnp.ones(k.shape, bool)
            local, slot, _, placed = tbl.insert_or_find(local, k, valid)
            local = tbl.write_slates(
                local, slot, placed,
                jax.tree.map(lambda v: jnp.asarray(v[i:i + 256]),
                             in_vals),
                jnp.asarray(in_ts[i:i + 256], jnp.int32))
            # write_slates marks landed rows dirty; rows flushed before
            # the move stay clean (they still match the store)
            keep_clean = jnp.asarray(~in_dirty[i:i + 256]) & placed
            safe = jnp.where(keep_clean, slot, local.capacity)
            local = replace(
                local, dirty=local.dirty.at[safe].set(False, mode="drop"))
        return local

    def _migrate_queues_host(self, queues,
                             slot_map=None) -> Dict[str, int]:
        """Re-home in-flight queued events (anything the drain barrier
        could not retire) through the new ring, rebuilding each queue
        compacted at head 0.  ``dropped`` counters carry; ``peak``
        restarts at the post-migration backlog (it is the rebalance
        window's load signal).  Like the table migrator, the input may
        have more slices than ``self.n_shards`` (compaction): every old
        slice is scanned and the rebuild is stacked at the new count,
        with ``slot_map`` naming the old slot each new ``dropped``
        tally carries from."""
        moved: Dict[str, int] = {}
        n = self.n_shards
        for op in self.wf.operators:
            q = queues[op.name]
            sizes = np.asarray(q.size)
            heads = np.asarray(q.head)
            cap = q.buf.key.shape[1]
            moved[op.name] = 0
            total = int(sizes.sum())
            smap = np.asarray(slot_map if slot_map is not None
                              else range(n), np.int64)
            old2new = np.full(len(sizes), -1, np.int64)
            old2new[smap] = np.arange(n)
            new_sizes = np.zeros(n, np.int32)
            new_drop = np.asarray(q.dropped)[smap].copy()
            if total == 0:
                queues[op.name] = q_mod.QueueState(
                    buf=jax.tree.map(lambda x: np.asarray(x)[smap],
                                     q.buf),
                    head=np.zeros(n, np.int32),
                    size=new_sizes, dropped=new_drop,
                    peak=np.zeros(n, np.int32))
                continue
            ev = {"sid": [], "ts": [], "key": [], "valid": [], "src": []}
            leaves, treedef = jax.tree.flatten(
                jax.tree.map(np.asarray, q.buf.value))
            ev_leaves: List[list] = [[] for _ in leaves]
            for s in range(len(sizes)):
                idx = (heads[s] + np.arange(sizes[s])) % cap
                ev["sid"].append(np.asarray(q.buf.sid)[s][idx])
                ev["ts"].append(np.asarray(q.buf.ts)[s][idx])
                ev["key"].append(np.asarray(q.buf.key)[s][idx])
                ev["valid"].append(np.asarray(q.buf.valid)[s][idx])
                ev["src"].append(np.full(len(idx), s, np.int32))
                for li, lf in enumerate(leaves):
                    ev_leaves[li].append(lf[s][idx])
            cat = {k: np.concatenate(v) for k, v in ev.items()}
            cat_leaves = [np.concatenate(v) for v in ev_leaves]
            dest = self.ring.owners(cat["key"], _salt(op.name))
            moved[op.name] = int((dest != old2new[cat["src"]]).sum())
            # rebuild each destination queue: stayers + movers, FIFO
            buf_sid = np.zeros((n, cap), np.int32)
            buf_ts = np.zeros((n, cap), np.int32)
            buf_key = np.zeros((n, cap), self.key_dtype)
            buf_valid = np.zeros((n, cap), bool)
            buf_leaves = [np.zeros((n, cap) + lf.shape[2:], lf.dtype)
                          for lf in leaves]
            for d in range(n):
                pick = np.nonzero(dest == d)[0]
                k = len(pick)
                if k > cap:
                    new_drop[d] += k - cap
                    pick = pick[:cap]
                    k = cap
                buf_sid[d, :k] = cat["sid"][pick]
                buf_ts[d, :k] = cat["ts"][pick]
                buf_key[d, :k] = cat["key"][pick]
                buf_valid[d, :k] = cat["valid"][pick]
                for bl, cl in zip(buf_leaves, cat_leaves):
                    bl[d, :k] = cl[pick]
                new_sizes[d] = k
            value = jax.tree.unflatten(treedef, buf_leaves)
            queues[op.name] = q_mod.QueueState(
                buf=EventBatch(sid=buf_sid, ts=buf_ts, key=buf_key,
                               value=value, valid=buf_valid),
                head=np.zeros(n, np.int32), size=new_sizes,
                dropped=new_drop, peak=new_sizes.copy())
        return moved

    def stats(self, state):
        g = lambda x: np.asarray(jax.device_get(x))
        return {
            "tick": int(g(state["tick"]).max()),
            "exchange_dropped": int(g(state["exchange_dropped"]).sum()),
            "throttle_hits": int(g(state["throttle_hits"]).sum()),
            "deferred": int(g(state["deferred"]).sum()),
            "processed": {k: int(g(v).sum())
                          for k, v in state["processed"].items()},
            "queue_dropped": {k: int(g(q.dropped).sum())
                              for k, q in state["queues"].items()},
            "table_occupancy": {k: int(g(t.occupancy()).sum())
                                for k, t in state["tables"].items()},
            **(self.dur.counters() if self.dur is not None else {}),
        }

    def read_slate(self, state, updater: str, key: int, *, merge=None):
        """Read a slate by key; with two-choice enabled — or the key in
        the live hot-key split set — merges the (<=2) partial
        aggregates (primary + secondary shard).  Holds ``read_lock`` so
        the ring/table pair is a consistent pre- or post-migration
        snapshot."""
        with self.read_lock:
            rh, rs = self.ring.table()
            karr = jnp.asarray([key], self.key_dtype)
            shards = [int(route(karr, _salt(updater), rh, rs)[0])]
            is_hot = bool(np.any(self._hot_valid
                                 & (self._hot_keys == key)))
            if self.cfg.two_choice_threshold or is_hot:
                shards.append(int(route_secondary(karr, _salt(updater),
                                                  rh, rs)[0]))
            vals = []
            t = state["tables"][updater]
            for s in dict.fromkeys(shards):
                local = jax.tree.map(lambda x: x[s], t)
                slot, found = tbl.lookup(local, karr)
                if bool(found[0]):
                    vals.append(jax.tree.map(
                        lambda v: jax.device_get(v[int(slot[0])]),
                        local.vals))
        if not vals:
            return None
        if len(vals) == 1:
            return vals[0]
        # merge the two partial aggregates via the updater's combine
        op = self.wf.by_name[updater]
        combine = merge or op.combine
        out = vals[0]
        for v in vals[1:]:
            out = combine(jax.tree.map(np.asarray, out),
                          jax.tree.map(np.asarray, v))
        return out

    def _make_read_fn(self, tables, updater: str, with_sec: bool,
                      impl: str):
        """Compile the batched distributed read (DESIGN.md 15): every
        shard runs the device lookup over its local table for the whole
        [Q] key vector, tags each hit with the ring roles it owns
        (bitmask: 1 = primary, 2 = effective secondary), and one
        ``all_gather`` ships the per-shard partials — role mask + local
        rows, gathered *once* — back replicated; the host selects the
        owning shard's row per (key, role).  Replaces the former
        psum-per-role select (two masked psum sweeps over every value
        leaf): the rows cross the interconnect once instead of twice,
        and the select is an O(Q) host argmax instead of a summed
        zero-masked reduction.  Result parity with the psum path is
        exact — at most one shard contributes per (key, role), so
        sum-of-masked equals select-of-owner (asserted in tests against
        the per-key ``read_slate`` loop).  Returns replicated
        ``(role_mask [n_shards, Q], rows [n_shards, Q, ...])``."""
        from repro.kernels.slate_lookup import ops as lk_ops
        rep = P()
        tspec = self._spec_like(tables)
        salt = _salt(updater)
        two = bool(self.cfg.two_choice_threshold)
        axes = self.axes

        def local(tb, karr, rh_, rs_, hk_, hv_):
            me = _linear_shard_index(axes)
            t = jax.tree.map(lambda x: x[0], tb)
            found, rows = lk_ops.lookup_tree(t.keys, t.vals, karr,
                                             impl=impl)
            prim = route(karr, salt, rh_, rs_)
            mask = (found & (prim == me)).astype(jnp.int32)
            if with_sec:
                sec = route_secondary(karr, salt, rh_, rs_)
                is_hot = jnp.any((karr[:, None] == hk_[None, :])
                                 & hv_[None, :], axis=1)
                use_sec = (jnp.bool_(two) | is_hot) & (sec != prim)
                sec_eff = jnp.where(use_sec, sec, jnp.int32(-1))
                mask = mask | (
                    (found & (sec_eff == me)).astype(jnp.int32) << 1)

            def gath(x):
                return jax.lax.all_gather(x, axes, tiled=False)

            return gath(mask), jax.tree.map(gath, rows)

        def run(tb, karr, rh_, rs_, hk_, hv_):
            fn = jax.shard_map(local, mesh=self.mesh,
                               in_specs=(tspec, rep, rep, rep, rep, rep),
                               out_specs=(rep, rep), check_vma=False)
            return fn(tb, karr, rh_, rs_, hk_, hv_)

        return jax.jit(run)

    def read_slates(self, state, updater: str, keys, *,
                    impl: str = "auto"):
        """Batched point reads through the ring: one sharded device
        dispatch + one host sync for a [Q] key vector, bitwise identical
        to Q ``read_slate`` calls (two-choice / hot-split partials merge
        primary-then-secondary via the updater's combine).  Returns a
        list aligned with ``keys`` (``None`` for missing)."""
        keys_np = np.asarray(keys, self.key_dtype).reshape(-1)
        if keys_np.size == 0:
            return []
        with self.read_lock:
            with_sec = (bool(self.cfg.two_choice_threshold)
                        or bool(self._hot_valid.any()))
            cache_key = (updater, with_sec, impl)
            fn = self._read_fns.get(cache_key)
            if fn is None:
                fn = self._make_read_fn(state["tables"][updater],
                                        updater, with_sec, impl)
                self._read_fns[cache_key] = fn
            res = jax.device_get(fn(state["tables"][updater],
                                    jnp.asarray(keys_np),
                                    *self._tick_inputs()))
        # host select over the gathered partials: at most one shard's
        # mask bit is set per (key, role), so argmax IS the owner
        mask, rows = np.asarray(res[0]), res[1]
        q = np.arange(keys_np.size)
        pm = (mask & 1).astype(bool)                    # [n_shards, Q]
        pf, psh = pm.any(axis=0), pm.argmax(axis=0)
        pr = jax.tree.map(lambda v: np.asarray(v)[psh, q], rows)
        if with_sec:
            sm = (mask & 2).astype(bool)
            sf, ssh = sm.any(axis=0), sm.argmax(axis=0)
            sr = jax.tree.map(lambda v: np.asarray(v)[ssh, q], rows)
        else:
            sf, sr = np.zeros_like(pf), None
        op = self.wf.by_name[updater]
        combine = getattr(op, "combine", None)
        out = []
        for i in range(keys_np.size):
            a = (jax.tree.map(lambda v: v[i], pr) if pf[i] else None)
            b = (jax.tree.map(lambda v: v[i], sr)
                 if sr is not None and sf[i] else None)
            if a is not None and b is not None:
                out.append(combine(a, b))
            elif a is not None:
                out.append(a)
            else:
                out.append(b)
        return out
