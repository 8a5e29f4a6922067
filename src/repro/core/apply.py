"""Updater execution paths: the TPU-native updater hot loop.

- ``apply_associative``: sort by key -> segmented associative scan
  pre-combines every key's events into one delta -> single slate
  gather/merge/scatter.  O(B log B) with batch-wide parallelism.
  Updaters declaring ``sum_mergeable`` (and no output streams) skip the
  generic scan entirely: their deltas and slate table are packed into
  lane-aligned [B, D] / [C, D] f32 buffers (``core/packing.py``) and the
  whole combine+scatter runs as one fused ``kernels/slate_update`` call
  (Pallas on TPU, segment-sum oracle elsewhere), in-place via
  ``input_output_aliases``.

- ``apply_sequential``: sort by (key, ts) -> padded-run scan preserving
  the paper's strict per-key timestamp order: vmap over key runs, scan
  over run positions.  Run length is statically bounded (``max_run``);
  events beyond the bound are *deferred* back to the caller (re-queued
  next tick), which is how a hotspot manifests here — and what the
  two-choice + key-splitting mitigations relieve.

Every path names its phases with ``jax.named_scope`` (DESIGN.md 18.2),
which changes only the operations' metadata: ``apply.sort`` (sort,
run boundaries, lift, pre-combine), ``apply.probe`` (``insert_or_find``),
``apply.pack`` (the packed path's fresh-slot zeroing, pack and unpack)
and ``apply.write`` (slate read/merge/write, the ts/dirty scatter).  The
``slate_update`` kernel call sits in none of them.
"""
from __future__ import annotations

from dataclasses import replace
from typing import Dict, Tuple

import jax
import jax.numpy as jnp

from repro.core import packing
from repro.core.event import EventBatch
from repro.core.operators import AssociativeUpdater, SequentialUpdater
from repro.kernels.slate_update import ops as slate_ops
from repro.kernels.slate_update import ref as slate_ref
from repro.slates import table as tbl


def _bshape(mask, like):
    return mask.reshape(mask.shape + (1,) * (like.ndim - 1))


def _last_valid_of_run(key, valid):
    """Per-key write point: the last *valid* row of each sorted run.

    Invalid rows are rewritten to the sink key 2**31-1 by
    ``sort_by_key_ts`` and ordered behind valid rows; a genuine event
    with that key shares the sink run, so the run's write point must be
    its last valid row — marking the run's final row would either drop
    the key (final row invalid) or leak invalid rows' lift deltas into
    its slate."""
    next_key = jnp.concatenate([key[1:], jnp.full((1,), -3, key.dtype)])
    next_valid = jnp.concatenate([valid[1:], jnp.zeros((1,), bool)])
    return (key != next_key) | (valid & ~next_valid)


def _segmented_combine(updater, deltas, boundary):
    """Inclusive segmented scan: each row ends up holding the combine of
    its run's prefix; run-last rows hold run totals."""

    def op(a, b):
        fa, va = a
        fb, vb = b
        combined = updater.combine(va, vb)
        v = jax.tree.map(
            lambda c, y: jnp.where(_bshape(fb, y), y, c), combined, vb)
        return (fa | fb, v)

    _, scanned = jax.lax.associative_scan(op, (boundary, deltas))
    return scanned


def merge_monoid(updater: AssociativeUpdater) -> str:
    """The elementwise monoid the fused path may run this updater under:
    "sum" (``sum_mergeable`` or ``monoid="sum"``), "max"
    (``monoid="max"``, non-negative leaves), or "" (generic combine —
    fused path ineligible)."""
    if getattr(updater, "sum_mergeable", False):
        return "sum"
    return getattr(updater, "monoid", "") or ""


def fused_eligible(updater: AssociativeUpdater) -> bool:
    """The fused slate_update path handles updaters whose combine/merge
    are an elementwise monoid the kernel implements (sum or non-negative
    max) and that emit nothing (the packed path never materializes
    old/new slates per key)."""
    return (merge_monoid(updater) in ("sum", "max")
            and not updater.out_streams)


def apply_associative(updater: AssociativeUpdater, table: tbl.SlateTable,
                      batch: EventBatch, tick, *, impl: str = "auto"
                      ) -> Tuple[tbl.SlateTable, Dict[str, EventBatch],
                                 jnp.ndarray]:
    """Returns (table, emissions, n_processed).

    ``impl`` selects the backend for ``fused_eligible`` updaters:
      - "off":  always the generic scan/gather/merge/scatter below
      - "auto": Pallas kernel on TPU (where the in-place [C, D] alias
                pays off); the generic path elsewhere
      - "pallas" / "interpret": force the kernel (packed [C, D] table,
        in-place via input_output_aliases; interpret runs on CPU)
      - "jnp":  packed segment-sum + direct scatter-add, no table pack —
        the portable fused fallback
      - "ref": force the packed-table jnp oracle
        (``kernels/slate_update/ref``) — exercises the same [C, D]
        buffer layout as the kernel without Pallas
    """
    if impl != "off" and fused_eligible(updater):
        if impl != "auto" or jax.default_backend() == "tpu":
            return _apply_associative_fused(updater, table, batch, tick,
                                            impl=impl)
    with jax.named_scope("apply.sort"):
        batch = batch.sort_by_key_ts()
        key = batch.key
        prev_key = jnp.concatenate([jnp.full((1,), -2, key.dtype),
                                    key[:-1]])
        boundary = key != prev_key                       # run starts
        run_last = _last_valid_of_run(key, batch.valid)  # run totals here

        deltas = updater.lift(batch)
        scanned = _segmented_combine(updater, deltas, boundary)
        unique = run_last & batch.valid

    with jax.named_scope("apply.probe"):
        table, slot, found, placed = tbl.insert_or_find(table, key, unique)
    with jax.named_scope("apply.write"):
        ok = unique & placed
        old = tbl.read_slates(table, slot, found & ok, updater.init_slate)
        new = updater.merge(old, scanned)
        table = tbl.write_slates(table, slot, ok, new, tick)

        emissions = updater.emit(key, old, new, batch.ts)
        emissions = {s: eb.mask(ok) for s, eb in emissions.items()}
    return table, emissions, batch.count()


def _apply_associative_fused(updater: AssociativeUpdater,
                             table: tbl.SlateTable, batch: EventBatch,
                             tick, *, impl: str
                             ) -> Tuple[tbl.SlateTable,
                                        Dict[str, EventBatch],
                                        jnp.ndarray]:
    """Counter-style hot path: pack deltas/table to [B,D]/[C,D] f32 and
    run the fused segmented-combine + in-place scatter.  Requires
    ``fused_eligible(updater)`` — an elementwise sum or non-negative max
    combine/merge, zero init slates, no emissions — so skipping the
    generic gather/merge/scatter is exact (modulo f32 summation on the
    sum monoid, which the generic "sum" leaf already uses; max is
    order-independent and therefore bitwise-identical)."""
    op = merge_monoid(updater)
    spec = packing.pack_spec(updater.slate_spec())
    with jax.named_scope("apply.sort"):
        batch = batch.sort_by_key_ts()
        key = batch.key                   # invalid rows sorted to sink
        run_last = _last_valid_of_run(key, batch.valid)
        unique = run_last & batch.valid
        deltas = updater.lift(batch)
        # segment totals combine whole runs; invalid rows sharing the
        # sink run with a genuine key 2**31-1 must contribute the
        # identity — zero for sum, and zero again for max thanks to the
        # non-negative contract
        deltas = jax.tree.map(
            lambda d: jnp.where(_bshape(batch.valid, d), d,
                                jnp.zeros_like(d)), deltas)
    if (jax.tree.structure(deltas)
            != jax.tree.structure(updater.slate_spec(),
                                  is_leaf=_is_spec_leaf)):
        raise TypeError(
            f"sum_mergeable updater {updater.name!r}: lift() pytree must "
            "match slate_spec() structure for the packed path")
    with jax.named_scope("apply.probe"):
        table, slot, found, placed = tbl.insert_or_find(table, key, unique)
        ok = unique & placed
        slots = jnp.where(ok, slot, jnp.int32(-1))        # -1 = no write
        safe = jnp.where(ok, slot, table.capacity)

    with jax.named_scope("apply.pack"):
        # Newly placed keys may land in a slot freed by expire_ttl /
        # fail_shard, which clear the key but keep the dead occupant's
        # vals; the generic path masks them out via read_slates'
        # init_slate substitution, the additive path must zero them
        # before the add.
        safe_fresh = jnp.where(ok & ~found, slot, table.capacity)
        base_vals = jax.tree.map(
            lambda tv: tv.at[safe_fresh].set(0, mode="drop"), table.vals)

    stalls = 0                  # the kernel's window stalls (below)
    backend = impl
    if backend == "auto":
        backend = ("pallas" if jax.default_backend() == "tpu"
                   else "jnp")
    if backend == "jnp":
        # combine via one segment reduce, then scatter run totals into
        # the slate leaves directly — no [C, D] table pack and no lane
        # padding on this side, so the CPU/GPU fallback touches only B
        # rows at the exact slate width.
        with jax.named_scope("apply.pack"):
            packed_deltas = packing.pack(deltas, spec, pad=False)
        with jax.named_scope("apply.sort"):
            totals = slate_ref.run_totals(key, packed_deltas, op=op)
        with jax.named_scope("apply.pack"):
            total_tree = packing.unpack(totals, spec)      # [B, ...]
        with jax.named_scope("apply.write"):
            if op == "max":
                vals = jax.tree.map(
                    lambda tv, dv: tv.at[safe].max(dv.astype(tv.dtype),
                                                   mode="drop"),
                    base_vals, total_tree)
            else:
                vals = jax.tree.map(
                    lambda tv, dv: tv.at[safe].add(dv.astype(tv.dtype),
                                                   mode="drop"),
                    base_vals, total_tree)
    else:
        with jax.named_scope("apply.pack"):
            packed_deltas = packing.pack(deltas, spec)    # [B, D] aligned
            packed_vals = packing.pack(base_vals, spec)   # [C, D]
        packed_vals, stalls = slate_ops.slate_update(
            key, packed_deltas, slots, packed_vals, impl=backend, op=op)
        with jax.named_scope("apply.pack"):
            vals = packing.unpack(packed_vals, spec)

    with jax.named_scope("apply.write"):
        # bookkeeping scatter (ts / dirty), same slots write_slates hits
        ts = table.ts.at[safe].set(tick, mode="drop")
        dirty = table.dirty.at[safe].set(True, mode="drop")
        window_stalls = table.window_stalls + stalls
    table = replace(table, ts=ts, dirty=dirty, vals=vals,
                    window_stalls=window_stalls)
    return table, {}, batch.count()


def apply_sequential(updater: SequentialUpdater, table: tbl.SlateTable,
                     batch: EventBatch, tick
                     ) -> Tuple[tbl.SlateTable, Dict[str, EventBatch],
                                EventBatch, jnp.ndarray]:
    """Returns (table, emissions, deferred_events, n_processed).

    Deferred = valid events whose per-key run exceeded ``max_run`` this
    tick (hotspot backpressure); the engine re-queues them.
    """
    with jax.named_scope("apply.sort"):
        batch = batch.sort_by_key_ts()
        B = batch.capacity
        key, valid = batch.key, batch.valid
        first_idx = jnp.searchsorted(key, key,
                                     side="left").astype(jnp.int32)
        pos = jnp.arange(B, dtype=jnp.int32) - first_idx
        run_start = (pos == 0) & valid
        in_budget = pos < updater.max_run
        deferred = batch.mask(valid & ~in_budget)

    with jax.named_scope("apply.probe"):
        table, slot, found, placed = tbl.insert_or_find(table, key,
                                                        run_start)
    with jax.named_scope("apply.write"):
        table, emissions = _step_runs(updater, table, batch,
                                      run_start & placed, slot, found, tick)
    n_proc = jnp.sum(valid & in_budget, dtype=jnp.int32)
    return table, emissions, deferred, n_proc


def _step_runs(updater: SequentialUpdater, table: tbl.SlateTable,
               batch: EventBatch, ok, slot, found, tick):
    """The sequential path's slate read, per-run step scan and write:
    ``(table, emissions)``."""
    B = batch.capacity
    key, valid = batch.key, batch.valid
    slates = tbl.read_slates(table, slot, found & ok, updater.init_slate)

    # emission accumulators at sorted-row granularity
    out_specs = updater.out_streams
    em_vals = {s: jax.tree.map(
        lambda sp: jnp.zeros((B,) + tuple(sp[0]), sp[1]), spec,
        is_leaf=_is_spec_leaf) for s, spec in out_specs.items()}
    em_keys = {s: jnp.zeros((B,), key.dtype) for s in out_specs}
    em_flag = {s: jnp.zeros((B,), bool) for s in out_specs}

    idx_all = jnp.arange(B, dtype=jnp.int32)

    def body(carry, j):
        slates_c, em_vals_c, em_keys_c, em_flag_c = carry
        idx = jnp.clip(idx_all + j, 0, B - 1)
        active = (ok & (idx_all + j < B) & (key[idx] == key)
                  & valid[idx] & (j < updater.max_run))
        ev = {
            "sid": batch.sid[idx], "ts": batch.ts[idx], "key": key[idx],
            "value": jax.tree.map(lambda a: a[idx], batch.value),
        }
        new_slates, emits = jax.vmap(updater.step)(slates_c, ev)
        slates_c = jax.tree.map(
            lambda n, o: jnp.where(_bshape(active, n), n, o),
            new_slates, slates_c)
        for s in out_specs:
            if s not in emits:
                continue
            row = emits[s]
            flag = row["emit"] & active
            safe = jnp.where(flag, idx, B)
            em_vals_c = dict(em_vals_c)
            em_vals_c[s] = jax.tree.map(
                lambda acc, v: acc.at[safe].set(v.astype(acc.dtype),
                                                mode="drop"),
                em_vals_c[s], row["value"])
            em_keys_c = dict(em_keys_c)
            em_keys_c[s] = em_keys_c[s].at[safe].set(
                row["key"].astype(key.dtype), mode="drop")
            em_flag_c = dict(em_flag_c)
            em_flag_c[s] = em_flag_c[s].at[safe].set(True, mode="drop")
        return (slates_c, em_vals_c, em_keys_c, em_flag_c), None

    carry = (slates, em_vals, em_keys, em_flag)
    (slates, em_vals, em_keys, em_flag), _ = jax.lax.scan(
        body, carry, jnp.arange(updater.max_run, dtype=jnp.int32))

    table = tbl.write_slates(table, slot, ok, slates, tick)

    emissions = {}
    for s in out_specs:
        emissions[s] = EventBatch(
            sid=jnp.zeros((B,), jnp.int32),
            ts=batch.ts + 1,
            key=em_keys[s],
            value=em_vals[s],
            valid=em_flag[s],
        )
    return table, emissions


def _is_spec_leaf(x):
    return isinstance(x, tuple) and len(x) == 2 and isinstance(x[0], tuple)
