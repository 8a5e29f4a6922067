"""Single-shard MapUpdate engine: one jitted tick over the whole workflow.

Execution model (DESIGN.md section 2): every tick each operator dequeues up
to ``batch_size`` events, applies its (vectorized) function, and emitted
events are enqueued at their subscribers for the next tick.  End-to-end
latency = graph depth x tick latency, mirroring Muppet's pipeline; there is
no master on the data path.

Two dispatch granularities (DESIGN.md section 2.2):
  - ``step``: one jitted tick per host call (lowest latency to observe
    state, one host<->device round-trip per tick);
  - ``run_chunk``: N ticks rolled into a single ``jax.lax.scan`` over
    pre-staged (stacked) sources — state, outputs, and the throttle
    signal stay device-resident for the whole chunk, so the host pays
    one dispatch + one sync per N ticks instead of per tick.

The distributed engine (``core/distributed.py``) runs this same tick
per-shard under ``shard_map`` with an all_to_all key-routing exchange in
front of every enqueue.
"""
from __future__ import annotations

import threading
import time
from collections import deque
from contextlib import nullcontext
from dataclasses import dataclass, field
from functools import partial
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import apply as apply_mod
from repro.core import queues as q_mod
from repro.core.durability import DurabilityConfig, EngineDurability
from repro.core.event import EventBatch, concat
from repro.core.operators import (AssociativeUpdater, Mapper,
                                  SequentialUpdater, Updater)
from repro.core.queues import OverflowPolicy
from repro.core.workflow import Workflow
from repro.slates import flush as flush_mod
from repro.slates import table as tbl
from repro.telemetry import latency as lat_mod
from repro.telemetry import sketch as sk_mod
from repro.telemetry.metrics import MetricsRegistry, TelemetryConfig
from repro.telemetry.trace import Tracer, span, tracer_for


@dataclass
class EngineConfig:
    batch_size: int = 256
    queue_capacity: int = 1024
    overflow: Dict[str, OverflowPolicy] = field(default_factory=dict)
    overflow_stream: Dict[str, str] = field(default_factory=dict)
    default_policy: OverflowPolicy = OverflowPolicy.DROP
    # fused slate-update backend for sum_mergeable updaters:
    # "auto" (Pallas on TPU, generic path elsewhere), "pallas",
    # "interpret", "jnp", "ref", or "off" (always the generic path).
    # See core/apply.apply_associative.
    fused: str = "auto"
    # key plane width, end-to-end: "int32" (default) or "int64".
    # int64 widens tables, queues, the sketch sample ring, WAL frames
    # and every kernel entry point, and requires jax_enable_x64 (the
    # engine refuses to construct otherwise — JAX silently demotes
    # int64 arrays without it).  Under int64 the hotspot split window
    # covers the whole 32-bit band (DESIGN.md 12.5 closed).
    key_dtype: str = "int32"
    # ticks per device-resident scan in run(); 1 = per-tick dispatch
    chunk_size: int = 8
    # durable runtime (WAL + slate flush + crash recovery, DESIGN.md 10);
    # None = fast-but-amnesiac (the seed behavior)
    durability: Optional[DurabilityConfig] = None
    # device-side telemetry (DESIGN.md 13): a count-min key-heat sketch
    # updated inside the jitted tick + a windowed metrics registry read
    # at chunk boundaries.  None = no sketch state, no readings.
    telemetry: Optional[TelemetryConfig] = None

    def policy_for(self, op_name: str) -> OverflowPolicy:
        return self.overflow.get(op_name, self.default_policy)


def stack_sources(per_tick: Sequence[Dict[str, "EventBatch"]]
                  ) -> Dict[str, "EventBatch"]:
    """Stack T per-tick source dicts into one dict of EventBatches with
    a leading tick axis [T, B, ...] — the pre-staged input format of
    ``run_chunk`` (scanned over axis 0 on device).

    Ticks may feed different stream subsets (including ``{}``) and
    different batch capacities: missing streams are padded with
    all-invalid batches and smaller batches are padded to the chunk's
    max capacity, so a bursty ``source_fn`` stacks the same way it
    would step.
    """
    assert per_tick, "need at least one tick of sources"
    caps: Dict[str, int] = {}
    templates: Dict[str, "EventBatch"] = {}
    for d in per_tick:
        for s, b in d.items():
            if s not in caps or b.capacity > caps[s]:
                caps[s], templates[s] = b.capacity, b

    def get(d, s):
        if s in d:
            return d[s].pad_to(caps[s])
        tmpl = templates[s]
        return tmpl.mask(jnp.zeros_like(tmpl.valid))

    return {s: jax.tree.map(lambda *xs: jnp.stack(xs),
                            *[get(d, s) for d in per_tick])
            for s in templates}


def _limit_ingest(batch: "EventBatch", ingest) -> "EventBatch":
    """Keep only the first ``ingest`` valid events (device-side source
    throttling inside a chunk)."""
    rank = jnp.cumsum(batch.valid.astype(jnp.int32)) - 1
    return batch.mask(rank < ingest)


def resolve_key_dtype(name) -> np.dtype:
    """Validate an ``EngineConfig.key_dtype`` / ``DistConfig`` key plane
    request: int32 or int64, with int64 demanding ``jax_enable_x64``
    up front (JAX silently demotes int64 arrays without it, which would
    corrupt keys instead of failing)."""
    dt = np.dtype(name)
    if dt not in (np.dtype(np.int32), np.dtype(np.int64)):
        raise ValueError(f"key_dtype must be int32 or int64, got {name!r}")
    if dt.itemsize > 4 and not jax.config.jax_enable_x64:
        raise RuntimeError(
            "key_dtype=int64 requires jax_enable_x64: set "
            "JAX_ENABLE_X64=1 (or jax.config.update('jax_enable_x64', "
            "True)) before building the engine")
    return dt


@partial(jax.jit, static_argnames=("impl",))
def _batched_lookup(table_keys, table_vals, query, *, impl: str):
    """One fused device program for a [Q] read batch: probe-walk +
    per-leaf row gather (kernels/slate_lookup.lookup_tree)."""
    from repro.kernels.slate_lookup import ops as lk_ops
    return lk_ops.lookup_tree(table_keys, table_vals, query, impl=impl)


class StateHandle:
    """Live view of ``(engine, state)`` for concurrent readers.

    The engine is functional — ``run()``/``step()`` thread an immutable
    state value — but live slate reads (paper section 4.4: the HTTP
    slate server answers *while the stream flows*) need the *current*
    state.  Drivers used to hand the server a mutable
    ``box = {"state": state}`` and rebind it every tick; instead,
    ``Engine.run(..., handle=h)`` republishes ``h.state`` after every
    chunk, and the server binds ``h.read_slate`` / ``h.stats`` directly.
    Works for :class:`~repro.core.distributed.DistributedEngine` too
    (same ``read_slate(state, ...)`` / ``stats(state)`` shape).
    """

    def __init__(self, engine, state=None, cache=None):
        self.engine = engine
        self.state = state
        # optional slates.replica.HotKeyCache: consulted before touching
        # device state, warmed from telemetry heavy hitters, invalidated
        # whenever the flush frontier advances (DESIGN.md section 15)
        self.cache = cache

    def _lock(self):
        return getattr(self.engine, "read_lock", None) or nullcontext()

    def read_slate(self, updater: str, key: int):
        c = self.cache
        if c is not None:
            hit, val = c.get(updater, key)
            if hit:
                return val
        with self._lock():
            val = self.engine.read_slate(self.state, updater, key)
        if c is not None and val is not None:
            c.put(updater, key, val)
        return val

    def read_slates(self, updater: str, keys):
        """Batched point reads (one device dispatch); list aligned with
        ``keys``, ``None`` for missing."""
        with self._lock():
            return self.engine.read_slates(self.state, updater, keys)

    def stats(self) -> Dict[str, Any]:
        with self._lock():
            return self.engine.stats(self.state)

    # -- driver hooks (Engine.run calls these at chunk boundaries) --
    def on_telemetry(self, report):
        if self.cache is not None and report is not None:
            self.cache.warm([k for k, _, _ in report.heavy_hitters])

    def on_frontier_advance(self):
        """Flush frontier moved: cached rows may now disagree with the
        durable snapshot the replica tier serves — drop them."""
        if self.cache is not None:
            self.cache.invalidate()

    def metrics_text(self) -> str:
        """Prometheus text exposition of the engine's current counters,
        latest telemetry window, and cumulative latency histograms —
        rendered from snapshots the registry already holds plus one
        ``stats()`` read (no hot-path cost beyond that)."""
        from repro.telemetry.prom import render_prometheus
        reg = getattr(self.engine, "telemetry", None)
        return render_prometheus(
            stats=self.stats(),
            report=reg.last if reg is not None else None,
            hist=reg.hist_cum if reg is not None else None,
            n_buckets=(reg.cfg.latency_buckets
                       if reg is not None else lat_mod.N_BUCKETS))

    def serve(self, port: int = 0):
        """Start an HTTP slate server bound to this handle."""
        from repro.slates.http import SlateServer
        return SlateServer(read_fn=self.read_slate, stats_fn=self.stats,
                           read_many_fn=self.read_slates,
                           metrics_fn=self.metrics_text, port=port)


class Engine:
    """Host-side wrapper owning the jitted tick."""

    def __init__(self, workflow: Workflow, config: EngineConfig = None):
        self.wf = workflow
        self.cfg = config or EngineConfig()
        self.key_dtype = resolve_key_dtype(self.cfg.key_dtype)
        # serializes concurrent readers against the donating dispatches
        # in run(): donated state buffers are deleted the moment a chunk
        # is dispatched, so a read racing the chunk would touch freed
        # arrays.  RLock: read_split_slate holds it across its sub-key
        # loop while read_slate re-acquires.
        self.read_lock = threading.RLock()
        self._step = jax.jit(self._tick, donate_argnums=(0,))
        self._chunk = jax.jit(self._chunk_impl, donate_argnums=(0,),
                              static_argnames=("n_ticks", "adapt",
                                               "throttle_floor"))
        self.telemetry: Optional[MetricsRegistry] = None
        self.tracer: Optional[Tracer] = tracer_for(self.cfg.telemetry)
        self.dur: Optional[EngineDurability] = None
        if self.cfg.durability is not None:
            self.dur = EngineDurability(self.cfg.durability, workflow,
                                        self.cfg.queue_capacity,
                                        self.cfg.batch_size,
                                        tracer=self.tracer)
        # what the last recover() restored and replayed
        self.last_recovery: Optional[Dict[str, Any]] = None
        if self.cfg.telemetry is not None:
            self.telemetry = MetricsRegistry(
                self.cfg.telemetry, batch_size=self.cfg.batch_size)
            self._salts = self.telemetry.salts

    @property
    def key_bits(self) -> int:
        return int(self.key_dtype.itemsize) * 8

    # ---- state ----
    def init_state(self) -> Dict[str, Any]:
        kd = self.key_dtype
        queues = {}
        for op in self.wf.operators:
            queues[op.name] = q_mod.make_queue(self.cfg.queue_capacity,
                                               op.in_value_spec,
                                               key_dtype=kd)
        tables = {}
        for up in self.wf.updaters():
            tables[up.name] = tbl.make_table(up.table_capacity,
                                             up.slate_spec(),
                                             key_dtype=kd)
        z = jnp.zeros((), jnp.int32)
        state = {
            "queues": queues,
            "tables": tables,
            "tick": z,
            "throttle_hits": z,
            "deferred": z,
            "processed": {op.name: z for op in self.wf.operators},
        }
        if self.cfg.telemetry is not None:
            tc = self.cfg.telemetry
            state["sketch"] = sk_mod.make_sketch(tc.depth, tc.width,
                                                 tc.sample, key_dtype=kd)
            if tc.latency_buckets > 0:
                state["lat_hist"] = lat_mod.make_hist(
                    [u.name for u in self.wf.updaters()],
                    tc.latency_buckets)
        # constants are interned by XLA; donation needs distinct buffers
        return jax.tree.map(lambda x: jnp.array(x, copy=True), state)

    # ---- one tick (jit) ----
    def _tick(self, state, sources: Dict[str, EventBatch]):
        cfg, wf = self.cfg, self.wf
        queues = dict(state["queues"])
        tables = dict(state["tables"])
        processed = dict(state["processed"])
        throttle_hits = state["throttle_hits"]
        deferred_total = state["deferred"]
        tick = state["tick"]
        sketch = state.get("sketch")
        lat_hist = dict(state["lat_hist"]) if "lat_hist" in state \
            else None
        outputs: Dict[str, List[EventBatch]] = {}

        def deliver_all(items: List[Tuple[str, EventBatch]]):
            """Route batches to subscriber queues; overflow-stream policy
            may chain (bounded — cycles are a config error)."""
            nonlocal throttle_hits
            work = deque(items)
            for _ in range(len(work) + 64):
                if not work:
                    return
                stream, batch = work.popleft()
                subs = wf.dests_of(stream)
                if not subs:
                    outputs.setdefault(stream, []).append(batch)
                    continue
                for dest in subs:
                    nq, ovf = q_mod.enqueue(queues[dest], batch)
                    pol = cfg.policy_for(dest)
                    if pol is OverflowPolicy.DROP:
                        nq = q_mod.count_drop(nq, ovf)
                    elif pol is OverflowPolicy.OVERFLOW_STREAM:
                        work.append((cfg.overflow_stream[dest], ovf))
                    elif pol is OverflowPolicy.THROTTLE:
                        throttle_hits = throttle_hits + ovf.count()
                        nq = q_mod.count_drop(nq, ovf)
                    queues[dest] = nq
            raise RuntimeError("overflow-stream routing did not converge "
                               "(cycle in overflow_stream config?)")

        # The tick's phases carry named scopes (DESIGN.md 18.2): they
        # name the device operations of each phase in the compiled
        # program's metadata and the profiler's trace, and change no
        # computation.  The updaters' phases are scoped in core/apply.
        # 1. deliver sources (visible to operators this tick; operator
        #    emissions become visible next tick — pipelined execution).
        with jax.named_scope("tick.queues"):
            deliver_all(list(sources.items()))
        emitted_now: List[Tuple[str, EventBatch]] = []

        # 2. apply operators on their queues
        for op in wf.operators:
            with jax.named_scope("tick.queues"):
                queues[op.name], batch = q_mod.dequeue(queues[op.name],
                                                       cfg.batch_size)
            if sketch is not None and isinstance(op, Updater):
                # key-heat telemetry: observe the keys each updater
                # actually processes (post-routing) — pure extra state,
                # never read by the tick itself (parity contract)
                with jax.named_scope("tick.telemetry"):
                    sketch = sk_mod.sketch_update(
                        sketch, batch.key, batch.valid, self._salts,
                        impl=cfg.telemetry.impl)
            if lat_hist is not None and isinstance(op, Updater):
                # event-latency telemetry (DESIGN.md 18): the event's
                # age at dequeue, binned into this arc's power-of-two
                # histogram — same parity contract as the sketch
                with jax.named_scope("tick.telemetry"):
                    lat_hist[op.name] = lat_mod.hist_update(
                        lat_hist[op.name], tick, batch.ts, batch.valid,
                        n_buckets=cfg.telemetry.latency_buckets,
                        impl=cfg.telemetry.impl)
            if isinstance(op, Mapper):
                with jax.named_scope("tick.map"):
                    outs = op.map_batch(batch)
                    for s, b in outs.items():
                        emitted_now.append(
                            (s, b.mask(batch.valid & b.valid)))
                processed[op.name] = processed[op.name] + batch.count()
            elif isinstance(op, AssociativeUpdater):
                tables[op.name], ems, n = apply_mod.apply_associative(
                    op, tables[op.name], batch, tick, impl=cfg.fused)
                emitted_now.extend(ems.items())
                processed[op.name] = processed[op.name] + n
            elif isinstance(op, SequentialUpdater):
                tables[op.name], ems, deferred, n = \
                    apply_mod.apply_sequential(op, tables[op.name], batch,
                                               tick)
                emitted_now.extend(ems.items())
                # hotspot backpressure: re-queue over-budget run tails
                deferred_total = deferred_total + deferred.count()
                with jax.named_scope("tick.queues"):
                    nq, ovf = q_mod.enqueue(queues[op.name], deferred)
                    queues[op.name] = q_mod.count_drop(nq, ovf)
                processed[op.name] = processed[op.name] + n
            else:
                raise TypeError(f"unknown operator type {type(op)}")

        # 3. TTL sweeps
        for up in wf.updaters():
            if up.ttl:
                with jax.named_scope("apply.write"):
                    tables[up.name] = tbl.expire_ttl(tables[up.name],
                                                     tick, up.ttl)

        # 4. route this tick's emissions (visible next tick)
        with jax.named_scope("tick.queues"):
            deliver_all(emitted_now)

        out_batches = {s: concat(bs) if len(bs) > 1 else bs[0]
                       for s, bs in outputs.items()}
        new_state = {
            "queues": queues,
            "tables": tables,
            "tick": tick + 1,
            "throttle_hits": throttle_hits,
            "deferred": deferred_total,
            "processed": processed,
        }
        if sketch is not None:
            new_state["sketch"] = sketch
        if lat_hist is not None:
            new_state["lat_hist"] = lat_hist
        return new_state, out_batches

    # ---- multi-tick chunk (jit: one dispatch, one sync per chunk) ----
    def _chunk_impl(self, state, stacked_sources, ingest, *,
                    n_ticks: int, adapt: bool, throttle_floor: int):
        """Roll the tick over a [T, ...] stack of sources with lax.scan.

        carry = (state, ingest).  With ``adapt`` the sources of each
        tick are masked down to the first ``ingest`` valid events and
        ingest halves/doubles *on device* from the tick's throttle-hits
        delta — the device-resident version of ``run``'s source
        throttling (paper section 5).  Without it the body is exactly
        ``_tick``, so a chunk is bitwise-identical to T ``step`` calls.
        """
        ing_max = jnp.maximum(ingest, jnp.int32(self.cfg.batch_size))

        def body(carry, src):
            st, ing = carry
            hits0 = st["throttle_hits"]
            if adapt:
                src = {s: _limit_ingest(b, ing) for s, b in src.items()}
            st, outs = self._tick(st, src)
            if adapt:
                delta = st["throttle_hits"] - hits0
                # halve under pressure; double back toward the ceiling
                # (the caller's initial limit, at least batch_size)
                ing = jnp.where(
                    delta > 0,
                    jnp.maximum(jnp.int32(throttle_floor), ing // 2),
                    jnp.minimum(ing_max, ing * 2))
            return (st, ing), (outs, st["throttle_hits"])

        (state, ingest), (outs, hits) = jax.lax.scan(
            body, (state, ingest), stacked_sources, length=n_ticks)
        return state, outs, {"throttle_hits": hits, "ingest": ingest}

    # ---- host API ----
    def step(self, state, sources: Dict[str, EventBatch]):
        return self._step(state, sources)

    def run_chunk(self, state, stacked_sources: Dict[str, EventBatch],
                  n_ticks: Optional[int] = None, *,
                  ingest: Optional[int] = None, throttle_floor: int = 8):
        """Run T ticks in one device-resident dispatch.

        ``stacked_sources``: dict of EventBatches with a leading tick
        axis [T, B, ...] (see ``stack_sources``).  Returns
        ``(state, stacked_outputs, info)`` where ``stacked_outputs``
        leaves have leading dim T and ``info`` holds the on-device
        per-tick ``throttle_hits`` trace plus the final ``ingest``.

        With ``ingest=None`` the chunk is bitwise-identical to T
        sequential ``step`` calls; passing an int enables on-device
        source throttling (events beyond the running ingest limit are
        masked before delivery).  An empty ``stacked_sources`` runs
        ``n_ticks`` source-less (drain) ticks.
        """
        lead = {s: jax.tree.leaves(b)[0].shape[0]
                for s, b in stacked_sources.items()}
        t_dim = next(iter(lead.values())) if lead else n_ticks
        if t_dim is None:
            raise ValueError("empty stacked_sources needs an explicit "
                             "n_ticks")
        if n_ticks is not None and lead and t_dim != n_ticks:
            raise ValueError(f"stacked sources have {t_dim} ticks, "
                             f"caller asked for {n_ticks}")
        adapt = ingest is not None
        ing0 = jnp.asarray(ingest if adapt else self.cfg.batch_size,
                           jnp.int32)
        return self._chunk(state, stacked_sources, ing0, n_ticks=t_dim,
                           adapt=adapt, throttle_floor=throttle_floor)

    def run(self, state, source_fn, n_ticks: int, *,
            throttle_floor: int = 8, chunk_size: Optional[int] = None,
            source_offset: int = 0,
            handle: Optional[StateHandle] = None):
        """Drive the engine; applies *source throttling* (paper section 5):
        if throttle hits grow, halve the ingest batch until queues drain.
        ``source_fn(tick, max_events) -> dict[stream, EventBatch]``.

        Ticks run in device-resident chunks of ``chunk_size`` (default
        ``cfg.chunk_size``); the host reads the throttle signal once per
        chunk — one sync per chunk, not per tick — and replays the
        per-tick halve/double rule over the on-device hits trace, so the
        ingest limit handed to ``source_fn`` reacts at chunk boundaries.
        ``chunk_size=1`` recovers exact per-tick backpressure.

        With ``cfg.durability`` set, every per-tick source dict is
        appended to the WAL *before* the chunk that consumes it, and at
        chunk boundaries the flush policy may trigger a durable slate
        flush + frontier advance (DESIGN.md section 10).  Durability
        drain ticks advance the engine tick counter, so ``source_fn``'s
        tick argument (the source index) and ``stats()['tick']`` diverge
        by the number of drain ticks.

        ``source_offset`` resumes an interrupted source stream:
        ``source_fn`` is called with absolute indices ``offset ..
        offset+n_ticks`` and chunk grouping stays aligned to the absolute
        index, so a recovered run flushes (and drains) at the same
        boundaries as the uninterrupted run — the bitwise-parity
        contract of ``recover()``.

        ``handle``: a :class:`StateHandle` republished with the current
        state after every chunk, so concurrent readers (the HTTP slate
        server) see live slates without the driver threading state.
        """
        chunk = chunk_size or self.cfg.chunk_size
        outputs = []
        ingest = None
        obs_mark = source_offset    # telemetry window cursor
        # throttle_hits is cumulative: resuming from prior state (second
        # run() call, or a recovered state) must not read old hits as a
        # fresh backpressure signal
        last_hits = int(jax.device_get(state["throttle_hits"]))
        t = source_offset
        end = source_offset + n_ticks
        eng_tick = int(jax.device_get(state["tick"])) if self.dur else 0
        # pipelined write path (DESIGN.md section 17): boundary work
        # splits into a cheap *begin* at the boundary (dirty-row gathers,
        # WAL epoch fence) and a blocking *commit* resolved right after
        # the NEXT chunk is dispatched, so store writes and telemetry
        # transfers overlap device compute instead of serializing the
        # tick path.
        pending_flush = None    # in-flight flush epoch (begin'd, not committed)
        pending_obs = None      # in-flight telemetry transfer
        while t < end:
            n = min(chunk - t % chunk, end - t)
            with span(self.tracer, "source_build", tick=t, n_ticks=n):
                per_tick = [source_fn(t + i, ingest) for i in range(n)]
            if self.dur:
                for i, srcs in enumerate(per_tick):
                    self.dur.append(eng_tick + i, srcs)  # async writer
            with span(self.tracer, "stack_sources"):
                stacked = stack_sources(per_tick)
            # the chunk dispatch donates (deletes) the buffers a handle
            # reader may be touching; hold the read lock from dispatch
            # until the fresh state is republished
            with self.read_lock:
                with span(self.tracer, "chunk_dispatch", tick=t, n_ticks=n):
                    state, outs, info = self.run_chunk(state, stacked, n)
                # chunk is in flight: resolve the previous boundary's
                # deferred work while the device computes
                if pending_flush is not None:
                    with span(self.tracer, "flush_commit"):
                        self._flush_commit(pending_flush)
                    pending_flush = None
                    if handle is not None:
                        handle.on_frontier_advance()
                if pending_obs is not None:
                    with span(self.tracer, "observe_finish"):
                        report = self.telemetry.finish_observe(
                            pending_obs)
                    pending_obs = None
                    if handle is not None:
                        handle.on_telemetry(report)
                for i in range(n):
                    outputs.append(jax.tree.map(lambda x, i=i: x[i],
                                                outs))
                with span(self.tracer, "chunk_sync"):
                    hits_trace = jax.device_get(
                        info["throttle_hits"])  # 1 sync
                for hits in (int(h) for h in hits_trace):
                    if hits > last_hits:     # backpressure signal
                        cur = (ingest if ingest is not None
                               else self.cfg.batch_size)
                        ingest = max(throttle_floor, cur // 2)
                    elif ingest is not None:
                        ingest = min(self.cfg.batch_size, ingest * 2)
                        if ingest == self.cfg.batch_size:
                            ingest = None
                    last_hits = hits
                t += n
                eng_tick += n
                if self.dur and self.dur.due(eng_tick, state["tables"]):
                    with span(self.tracer, "flush_begin", tick=t):
                        state, eng_tick, pending_flush = \
                            self._flush_begin(state, eng_tick,
                                              meta={"source_tick": t})
                if (self.telemetry is not None
                        and t - obs_mark >= self.cfg.telemetry.window):
                    # start the boundary transfer; the report resolves
                    # after the next chunk's dispatch (one-chunk lag)
                    with span(self.tracer, "observe_begin", tick=t):
                        pending_obs = self.telemetry.begin_observe(
                            self, state)
                    state = dict(state)
                    state["sketch"] = sk_mod.decay(
                        state["sketch"], self.cfg.telemetry.decay)
                    obs_mark = t
                if handle is not None:
                    handle.state = state
        # trailing deferred work: the run must not return with an
        # uncommitted frontier or an unresolved report
        if pending_flush is not None:
            with span(self.tracer, "flush_commit"):
                self._flush_commit(pending_flush)
            if handle is not None:
                handle.on_frontier_advance()
        if pending_obs is not None:
            with span(self.tracer, "observe_finish"):
                report = self.telemetry.finish_observe(pending_obs)
            if handle is not None:
                handle.on_telemetry(report)
        if self.dur:
            # run() is a durable unit: every source batch it consumed is
            # on disk (and append errors surface) before control returns
            with span(self.tracer, "wal_fence"):
                self.dur.fence()
        return state, outputs

    def drain(self, state, max_ticks: int = 64):
        """Run source-less ticks until every queue is empty (or
        ``max_ticks``) — flushes in-flight events through the remaining
        pipeline hops.  Returns ``(state, ticks_run)``."""
        return self._drain_queues(state, max_ticks)

    # ---- durability (DESIGN.md section 10) ----
    def _drain_queues(self, state, max_ticks: int):
        """Run source-less ticks until every queue is empty — the flush
        barrier.  Each probe costs one host sync; barriers are rare
        (flush boundaries only).  Returns (state, ticks_run)."""
        d = 0
        while d < max_ticks:
            sizes = jax.device_get({k: q.size
                                    for k, q in state["queues"].items()})
            if all(int(v) == 0 for v in sizes.values()):
                break
            state, _ = self._step(state, {})
            d += 1
        return state, d

    def _flush_begin(self, state, eng_tick: int, meta=None):
        """First half of a flush boundary: drain (per config), start the
        device->host snapshot of every dirty table (tables come back
        marked clean immediately), and fence the WAL writer to pin the
        frontier's replay point *before* any later tick appends.  The
        blocking store-side work lives in :meth:`_flush_commit`, which
        the driver calls after the next chunk's dispatch so it overlaps
        device compute.  Returns ``(state, eng_tick, pending)``."""
        dur = self.dur
        if dur.cfg.barrier:
            state, d = self._drain_queues(state, dur.cfg.drain_ticks_max)
            eng_tick += d
        state = dict(state)
        tables = dict(state["tables"])
        snaps = []
        for up in self.wf.updaters():
            token, cleared = flush_mod.begin_dirty_snapshot(
                tables[up.name])
            tables[up.name] = cleared
            snaps.append((up.name, up.ttl, token))
        state["tables"] = tables
        f_token = dur.begin_frontier(eng_tick)
        return state, eng_tick, (snaps, f_token, meta)

    def _flush_commit(self, pending):
        """Second half: resolve the snapshots to host rows, hand them to
        the flusher, and commit the frontier once the store writes are
        durable (raises :class:`FlushError` without saving otherwise).
        ``meta`` is the driver cursor stored with the frontier (run()
        records the source index so a --recover driver can resume its
        stream even after full WAL truncation)."""
        snaps, f_token, meta = pending
        dur = self.dur
        for name, ttl, token in snaps:
            keys, ts, vals = flush_mod.finish_dirty_snapshot(token)
            dur.flusher.flush_rows(name, keys, ts, vals, ttl=ttl)
        dur.commit_frontier(f_token, meta=meta)

    def _flush_boundary(self, state, eng_tick: int, meta=None):
        """Synchronous flush boundary (checkpoint / shutdown / tests):
        begin + commit back to back — no overlap, identical durability
        semantics."""
        state, eng_tick, pending = self._flush_begin(state, eng_tick,
                                                     meta=meta)
        self._flush_commit(pending)
        return state, eng_tick

    def checkpoint(self, state):
        """Force a flush boundary now (shutdown / test hook); returns the
        new state (flushed tables are marked clean)."""
        assert self.dur is not None, "engine has no durability config"
        eng_tick = int(jax.device_get(state["tick"]))
        state, _ = self._flush_boundary(state, eng_tick)
        return state

    def recover(self, store=None, wal=None, *, frontier=None):
        """Rebuild engine state after a crash: restore flushed slates
        from the KV store, then replay the WAL suffix from the flush
        frontier through the jitted chunk path (DESIGN.md section 10).

        ``store`` / ``wal`` / ``frontier`` default to the engine's own
        durability runtime (``cfg.durability.dir``).  Returns the
        recovered state, positioned at the last WAL tick; resume with
        ``run()``/``step()`` as usual.  Stats counters (processed,
        drops) restart at the frontier — only slates and the tick
        counter are recovered state.
        """
        dur = self.dur
        store = store if store is not None else (dur and dur.store)
        wal = wal if wal is not None else (dur and dur.wal)
        if frontier is None:
            frontier = dur.frontier if dur else flush_mod.FlushFrontier()
        assert store is not None and wal is not None, \
            "recover() needs a store + wal (or cfg.durability)"
        f_tick = int(frontier.tick)
        f_off = frontier.wal_offset
        f_off = f_off[0] if isinstance(f_off, (list, tuple)) else f_off

        t_recover = time.perf_counter()
        state = self.init_state()
        state["tick"] = jnp.asarray(f_tick, jnp.int32)
        restored = {}
        with span(self.tracer, "recover_restore", frontier=f_tick):
            for up in self.wf.updaters():
                keys, ts, slates = store.scan_columns(
                    up.name, now=f_tick if up.ttl else None)
                restored[up.name] = int(keys.size)
                if keys.size:
                    state["tables"][up.name] = flush_mod.restore_into(
                        state["tables"][up.name],
                        keys.astype(self.key_dtype), slates,
                        ts.astype(np.int32))
        t_replay = time.perf_counter()

        # replay, preserving the per-tick batch structure (gaps in the
        # log — drain ticks, empty-source ticks — replay as empty ticks)
        chunk = self.cfg.chunk_size
        pending: List[Dict[str, EventBatch]] = []
        replayed = 0

        def flush_pending():
            nonlocal state, pending, replayed
            while pending:
                group, pending = pending[:chunk], pending[chunk:]
                state, _, _ = self.run_chunk(
                    state, stack_sources(group), len(group))
                replayed += len(group)

        with span(self.tracer, "recover_replay", frontier=f_tick) as sp:
            cur = f_tick
            for tk, srcs in wal.replay(from_offset=f_off):
                if tk < f_tick:
                    continue
                while cur < tk:
                    pending.append({})
                    cur += 1
                pending.append(srcs)
                cur += 1
                if len(pending) >= 4 * chunk:
                    flush_pending()
            flush_pending()
            sp["replayed_ticks"] = replayed
        t_end = time.perf_counter()
        self.last_recovery = {"frontier": f_tick, "restored": restored,
                              "restore_s": t_replay - t_recover,
                              "replay_s": t_end - t_replay,
                              "replayed_ticks": replayed}
        # the migration path measures pause_s around _reconfigure; the
        # crash path surfaces its restore+replay wall time the same way
        if self.telemetry is not None:
            self.telemetry.note_recovery(t_end - t_recover)
        return state

    def close(self):
        if self.dur is not None:
            self.dur.close()

    # ---- introspection (paper section 4.4: reading slates live) ----
    def read_slate(self, state, updater: str, key: int):
        """Fetch one slate from the device table (the HTTP slate-read
        path reuses this)."""
        table = state["tables"][updater]
        slot, found = tbl.lookup(table,
                                 jnp.asarray([key], self.key_dtype))
        if not bool(found[0]):
            return None
        s = int(slot[0])
        return jax.tree.map(lambda v: jax.device_get(v[s]), table.vals)

    def read_slates(self, state, updater: str, keys, *,
                    impl: str = "auto"):
        """Batched point reads: one device dispatch + one host sync for
        a whole [Q] key vector, bitwise identical to Q ``read_slate``
        calls.  Returns a list aligned with ``keys`` of per-key slate
        dicts (``None`` for missing keys).  ``impl`` picks the lookup
        backend (kernels/slate_lookup: "auto"/"pallas"/"interpret"/
        "jnp")."""
        keys = np.asarray(keys, self.key_dtype).reshape(-1)
        if keys.size == 0:
            return []
        table = state["tables"][updater]
        found, rows = _batched_lookup(table.keys, table.vals,
                                      jnp.asarray(keys), impl=impl)
        found = np.asarray(jax.device_get(found))
        rows = jax.device_get(rows)
        return [jax.tree.map(lambda v, i=i: v[i], rows)
                if found[i] else None for i in range(keys.size)]

    def stats(self, state) -> Dict[str, Any]:
        g = jax.device_get
        return {
            "tick": int(g(state["tick"])),
            "throttle_hits": int(g(state["throttle_hits"])),
            "deferred": int(g(state["deferred"])),
            "processed": {k: int(g(v))
                          for k, v in state["processed"].items()},
            "queue_dropped": {k: int(g(q.dropped))
                              for k, q in state["queues"].items()},
            "queue_peak": {k: int(g(q.peak))
                           for k, q in state["queues"].items()},
            "queue_size": {k: int(g(q.size))
                           for k, q in state["queues"].items()},
            "table_occupancy": {k: int(g(t.occupancy()))
                                for k, t in state["tables"].items()},
            "table_dropped": {k: int(g(t.dropped))
                              for k, t in state["tables"].items()},
            "table_claim_rounds": {k: int(g(t.claim_rounds))
                                   for k, t in state["tables"].items()},
            "table_window_stalls": {k: int(g(t.window_stalls))
                                    for k, t in state["tables"].items()},
            **(self.dur.counters() if self.dur is not None else {}),
        }
