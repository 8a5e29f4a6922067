"""Device-resident slate table: a fixed-capacity open-addressing hash map.

One table per (updater, shard) holds that shard's slates — the "slate
cache in the memory of the machine running U" of paper section 4.2, kept
in HBM as struct-of-arrays so the updater hot loop is pure gather /
compute / scatter.

Collision handling is double hashing with a static probe budget; batch
inserts look every key up once, then resolve intra-batch slot races with
bounded claim rounds that run only while some key still lacks a slot, so
a batch whose keys are all present costs one lookup.  Keys that cannot
be placed are *dropped and counted* — bounded-resource loss semantics,
exactly how Muppet treats overload (sections 4.3, 5).  TTL and dirty bits
mirror the paper's flush / garbage-collection knobs.
"""
from __future__ import annotations

import dataclasses
from dataclasses import dataclass
from typing import Any, Callable, Dict, Tuple

import jax
import jax.numpy as jnp

from repro.core.hashing import hash_key

EMPTY = jnp.int32(-1)
PROBES = 8          # static probe budget per lookup
INSERT_ROUNDS = 4   # bounded claim rounds for batch insert
# the table's lifetime int32 [] counters, carried across every rebuild
TALLIES = ("dropped", "claim_rounds", "window_stalls")


@jax.tree_util.register_dataclass
@dataclass
class SlateTable:
    keys: jnp.ndarray      # int32/int64 [C], EMPTY = free
    ts: jnp.ndarray        # int32 [C] last-update tick (TTL)
    dirty: jnp.ndarray     # bool [C] updated since last flush
    vals: Any              # pytree, leaves [C, ...]
    dropped: jnp.ndarray   # int32 [] lifetime insert-failure count
    claim_rounds: jnp.ndarray  # int32 [] lifetime claim rounds run
    window_stalls: jnp.ndarray  # int32 [] lifetime slate_update stalls

    @property
    def capacity(self) -> int:
        return int(self.keys.shape[0])

    def occupancy(self):
        return jnp.sum((self.keys != EMPTY).astype(jnp.int32))


def make_table(capacity: int, value_spec: Dict[str, Any],
               key_dtype=jnp.int32) -> SlateTable:
    """value_spec: pytree of (shape_suffix tuple, dtype)."""
    vals = jax.tree.map(
        lambda s: jnp.zeros((capacity,) + tuple(s[0]), s[1]),
        value_spec, is_leaf=_is_spec_leaf)
    return SlateTable(
        keys=jnp.full((capacity,), EMPTY, key_dtype),
        ts=jnp.zeros((capacity,), jnp.int32),
        dirty=jnp.zeros((capacity,), bool),
        vals=vals,
        dropped=jnp.zeros((), jnp.int32),
        claim_rounds=jnp.zeros((), jnp.int32),
        window_stalls=jnp.zeros((), jnp.int32),
    )


def _is_spec_leaf(x):
    return isinstance(x, tuple) and len(x) == 2 and isinstance(x[0], tuple)


def _probe_seq(query, capacity: int):
    """[P, B] candidate slots (double hashing)."""
    h1 = hash_key(query, salt=0xA11CE) % jnp.uint32(capacity)
    h2 = hash_key(query, salt=0xB0B) % jnp.uint32(capacity - 1) + jnp.uint32(1)
    steps = jnp.arange(PROBES, dtype=jnp.uint32)[:, None]
    return ((h1[None] + steps * h2[None]) % jnp.uint32(capacity)
            ).astype(jnp.int32)


def lookup(table: SlateTable, query) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """query: int32 [B] -> (slot [B], found [B]).  slot is the matching
    slot if found, else the first empty probe slot (insertion point), else
    -1 (probe budget exhausted)."""
    cand = _probe_seq(query, table.capacity)              # [P,B]
    ck = table.keys[cand]                                 # [P,B]
    hit = ck == query[None]
    free = ck == EMPTY

    def first_true(mask, vals, default):
        # index of first True along axis 0
        any_ = jnp.any(mask, axis=0)
        idx = jnp.argmax(mask, axis=0)
        return jnp.where(any_, jnp.take_along_axis(
            vals, idx[None], axis=0)[0], default), any_

    hit_slot, found = first_true(hit, cand, jnp.int32(-1))
    free_slot, has_free = first_true(free, cand, jnp.int32(-1))
    slot = jnp.where(found, hit_slot,
                     jnp.where(has_free, free_slot, jnp.int32(-1)))
    return slot, found


def insert_or_find(table: SlateTable, query, valid) -> Tuple[
        SlateTable, jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Place unique ``query`` keys (masked by ``valid``).

    Returns (table, slot [B], found_existing [B], placed [B]).  Every key
    is looked up once.  A claim round — scatter the keys that need a slot
    into their empty candidate slots, read back who owns each, look up
    again — runs only while some pending key still needs one, at most
    INSERT_ROUNDS times; intra-batch races on one empty slot resolve over
    those rounds and stragglers are dropped (counted).  A round with
    nothing to claim settles the found keys and leaves every other
    pending key with a spent probe budget, so the rounds it would be
    followed by change nothing: the answer is the one INSERT_ROUNDS
    unconditional rounds give.  ``claim_rounds`` counts the rounds run.
    Caller must guarantee uniqueness of valid keys (dedup upstream).
    """
    cap = table.capacity

    def needs_slot(carry):
        rounds, _, _, _, pending, cand_slot, cand_found = carry
        return (rounds < INSERT_ROUNDS) & jnp.any(
            pending & (cand_slot >= 0) & ~cand_found)

    def claim_round(carry):
        rounds, keys_arr, slot, found, pending, cand_slot, cand_found = carry
        want = pending & (cand_slot >= 0)
        # claim: scatter key ids into candidate slots; later writers win,
        # so read back to see who actually owns the slot
        safe_slot = jnp.where(want & ~cand_found, cand_slot, cap)
        keys_arr = keys_arr.at[safe_slot].set(query, mode="drop")
        owner_ok = keys_arr[jnp.clip(cand_slot, 0, cap - 1)] == query
        success = want & (cand_found | owner_ok)
        slot = jnp.where(success, cand_slot, slot)
        found = found | (want & cand_found)
        pending = pending & ~success
        return (rounds + 1, keys_arr, slot, found, pending,
                *_lookup_keys(keys_arr, query, cap))

    carry = (jnp.int32(0), table.keys, jnp.full(query.shape, -1, jnp.int32),
             jnp.zeros(query.shape, bool), valid,
             *_lookup_keys(table.keys, query, cap))
    rounds, keys_arr, slot, found, pending, cand_slot, cand_found = (
        jax.lax.while_loop(needs_slot, claim_round, carry))
    # the round after the last claim, if one is left, only settles the
    # keys it finds: nothing needs a slot, so want == pending & found
    hit = pending & cand_found & (rounds < INSERT_ROUNDS)
    slot = jnp.where(hit, cand_slot, slot)
    found = found | hit
    pending = pending & ~hit

    new_table = dataclasses.replace(
        table, keys=keys_arr,
        dropped=table.dropped + jnp.sum(pending, dtype=jnp.int32),
        claim_rounds=table.claim_rounds + rounds)
    return new_table, slot, found, valid & ~pending


def _lookup_keys(keys_arr, query, capacity):
    cand = _probe_seq(query, capacity)
    ck = keys_arr[cand]
    hit = ck == query[None]
    free = ck == EMPTY
    stop = hit | free
    any_ = jnp.any(stop, axis=0)
    idx = jnp.argmax(stop, axis=0)
    slot = jnp.where(any_, jnp.take_along_axis(cand, idx[None], axis=0)[0],
                     jnp.int32(-1))
    found = jnp.take_along_axis(hit, idx[None], axis=0)[0] & any_
    return slot, found


def read_slates(table: SlateTable, slot, found, init_fn: Callable):
    """Gather slate values; missing keys get ``init_fn(batch)`` defaults.
    (Paper: 'the update function must set up and initialize the slate on
    first access'.)"""
    gathered = jax.tree.map(
        lambda v: v[jnp.clip(slot, 0, table.capacity - 1)], table.vals)
    fresh = init_fn(slot.shape[0])
    pick = lambda g, f: jnp.where(
        _bshape(found, g), g, f.astype(g.dtype))
    return jax.tree.map(pick, gathered, fresh)


def write_slates(table: SlateTable, slot, ok, new_vals, tick) -> SlateTable:
    safe = jnp.where(ok, slot, table.capacity)
    vals = jax.tree.map(
        lambda tv, nv: tv.at[safe].set(nv.astype(tv.dtype), mode="drop"),
        table.vals, new_vals)
    ts = table.ts.at[safe].set(tick, mode="drop")
    dirty = table.dirty.at[safe].set(True, mode="drop")
    return dataclasses.replace(table, ts=ts, dirty=dirty, vals=vals)


def expire_ttl(table: SlateTable, now, ttl: int) -> SlateTable:
    """Garbage-collect slates idle for > ttl ticks (paper section 4.2)."""
    dead = (table.keys != EMPTY) & (now - table.ts > ttl)
    keys = jnp.where(dead, jnp.asarray(EMPTY, table.keys.dtype),
                     table.keys)
    dirty = jnp.where(dead, False, table.dirty)
    return dataclasses.replace(table, keys=keys, dirty=dirty)


def _bshape(mask, like):
    return mask.reshape(mask.shape + (1,) * (like.ndim - 1))
