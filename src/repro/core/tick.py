"""The MapUpdate tick: one function for one shard and for many.

Every tick (DESIGN.md section 2) delivers the sources to their
subscribers' queues, lets each operator dequeue up to ``batch_size``
events and map or update them, re-queues a sequential updater's
deferred run tails, sweeps TTLs, and routes this tick's emissions to
the queues they read next tick.

``Engine`` jits this function as it stands.  ``DistributedEngine`` runs
it per shard under ``shard_map`` and passes ``send``: the one seam
between the engines.  ``send(dest, batch)`` is called once per (stream,
subscriber) before the enqueue and returns the batch that this shard's
queue of ``dest`` receives; the sharded engine routes the batch by its
ring and trades it with one ``all_to_all`` there.  The tick itself
knows nothing of rings or shards.
"""
from __future__ import annotations

from collections import deque
from typing import Callable, Dict, List, Optional, Tuple

import jax
import numpy as np

from repro.core import apply as apply_mod
from repro.core import queues as q_mod
from repro.core.event import EventBatch, concat
from repro.core.operators import (AssociativeUpdater, Mapper,
                                  SequentialUpdater, Updater)
from repro.core.queues import OverflowPolicy
from repro.core.workflow import Workflow
from repro.slates import table as tbl
from repro.telemetry import latency as lat_mod
from repro.telemetry import sketch as sk_mod

Send = Callable[[str, EventBatch], EventBatch]


def tick(wf: Workflow, cfg, tele_cfg, salts: Optional[np.ndarray],
         state, sources: Dict[str, EventBatch],
         send: Optional[Send] = None):
    """One tick over one shard's ``state``; returns ``(state, outputs)``.

    ``cfg`` is the engine's ``EngineConfig`` (batch size, overflow
    policies, fused backend); ``tele_cfg``/``salts`` configure the
    sketch and latency histograms when ``state`` holds them.  With no
    ``send`` a batch goes straight to its subscriber's queue.
    """
    queues = dict(state["queues"])
    tables = dict(state["tables"])
    processed = dict(state["processed"])
    throttle_hits = state["throttle_hits"]
    deferred_total = state["deferred"]
    tick_no = state["tick"]
    sketch = state.get("sketch")
    lat_hist = dict(state["lat_hist"]) if "lat_hist" in state else None
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
                recv = batch if send is None else send(dest, batch)
                nq, ovf = q_mod.enqueue(queues[dest], recv)
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

    # The tick's phases carry named scopes (DESIGN.md 18.2): they name
    # the device operations of each phase in the compiled program's
    # metadata and the profiler's trace, and change no computation.
    # The updaters' phases are scoped in core/apply.
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
            # key-heat telemetry: observe the keys each updater actually
            # processes (post-routing, so per shard the per-arc signal
            # rebalance wants) — pure extra state, never read by the
            # tick itself (parity contract)
            with jax.named_scope("tick.telemetry"):
                sketch = sk_mod.sketch_update(
                    sketch, batch.key, batch.valid, salts,
                    impl=tele_cfg.impl)
        if lat_hist is not None and isinstance(op, Updater):
            # event-latency telemetry (DESIGN.md 18): the event's age at
            # dequeue, binned into this arc's power-of-two histogram —
            # same parity contract as the sketch
            with jax.named_scope("tick.telemetry"):
                lat_hist[op.name] = lat_mod.hist_update(
                    lat_hist[op.name], tick_no, batch.ts, batch.valid,
                    n_buckets=tele_cfg.latency_buckets,
                    impl=tele_cfg.impl)
        if isinstance(op, Mapper):
            with jax.named_scope("tick.map"):
                outs = op.map_batch(batch)
                for s, b in outs.items():
                    emitted_now.append((s, b.mask(batch.valid & b.valid)))
            processed[op.name] = processed[op.name] + batch.count()
        elif isinstance(op, AssociativeUpdater):
            tables[op.name], ems, n = apply_mod.apply_associative(
                op, tables[op.name], batch, tick_no, impl=cfg.fused)
            emitted_now.extend(ems.items())
            processed[op.name] = processed[op.name] + n
        elif isinstance(op, SequentialUpdater):
            tables[op.name], ems, deferred, n = apply_mod.apply_sequential(
                op, tables[op.name], batch, tick_no)
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
                tables[up.name] = tbl.expire_ttl(tables[up.name], tick_no,
                                                 up.ttl)

    # 4. route this tick's emissions (visible next tick)
    with jax.named_scope("tick.queues"):
        deliver_all(emitted_now)

    out_batches = {s: concat(bs) if len(bs) > 1 else bs[0]
                   for s, bs in outputs.items()}
    new_state = {
        "queues": queues,
        "tables": tables,
        "tick": tick_no + 1,
        "throttle_hits": throttle_hits,
        "deferred": deferred_total,
        "processed": processed,
    }
    if sketch is not None:
        new_state["sketch"] = sketch
    if lat_hist is not None:
        new_state["lat_hist"] = lat_hist
    return new_state, out_batches
