"""Muppet updater hot loop — fused segment-combine + pipelined slate
read-modify-writes.

One kernel invocation applies one microbatch of *sorted* (key, delta)
events to the slate table: a log-depth segmented prefix-sum combines every
key's deltas in VMEM, then each run-last row read-modify-writes its slate
in HBM — the access Cassandra-backed Muppet pays per updated slate, minus
the network.  The table buffer is aliased in/out so the update is
in-place.  Slates lie along the lanes of the [D, C] table view, and HBM
moves only by DMA in whole lane tiles, so a slate's update moves the
128-lane window that holds it: read into VMEM, combine the run total into
the slot's lane, write back.

The batch is walked in tiles of ``TILE_B`` rows (one grid step each, in
order): a run that crosses a tile edge carries its running total into
the next tile, so a run total is the same segmented combine whatever the
batch size, and the per-step VMEM/SMEM footprint stays fixed.

The window moves are software-pipelined.  Per tile, one scalar pass lists
the live rows (slot >= 0) in row order.  A ring of ``RING`` VMEM window
buffers then keeps the reads of the next ``AHEAD`` live rows in flight
while a row is merged and its write-back started, and a write is waited
on only when its buffer comes round again, ``RING - AHEAD`` rows later.
So the rows' DMA round trips overlap instead of adding up.

Window hazard: two live rows whose slots share a window must never be in
flight together.  A read started before an earlier same-window write has
landed would see the window without that row's update, and its own
write-back would then undo it.  So a live row whose window matches that
of one of the ``RING - 1`` live rows before it in the tile is not read
ahead: at its turn every write still in flight is waited on, and only
then is it read.  The kernel counts those rows (its second output, the
tables' ``window_stalls``).  Every write of a tile is waited on before
the tile ends, so the hazard never crosses tiles.

Covers sum-mergeable (counter-style) associative updaters — the flagship
Muppet workload (Examples 1/2/4/5 are all counters).  General combine fns
keep the jnp path (core/apply.py).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# rows per grid step (a multiple of the 128 lanes): the [D, TILE_B]
# scan operands and the SMEM slot block stay small at any batch size
TILE_B = 1024
LANES = 128
# window buffers in the ring, and the live rows read ahead of the one
# being merged (the rest of the ring gives the writes time to land).
# 8 was the fastest of 8/16/32/64 on a TPU v5e: four reads ahead cover
# the round trip, and a deeper ring only adds hazard compares a row
RING = 8
AHEAD = RING // 2


def _combine(op, a, b):
    return jnp.maximum(a, b) if op == "max" else a + b


def _slate_kernel(keys_ref, deltas_ref, slots_ref, table_in_ref,
                  table_ref, stalls_ref, tot_ref, ckey_ref, cval_ref,
                  bufs, live_ref, wins_ref, ahead_ref, waited_ref, sems,
                  *, tb: int, steps: int, op: str):
    keys = keys_ref[...]                        # [1, TB] sorted
    vals = deltas_ref[...].astype(jnp.float32)  # [D, TB]
    idx = jax.lax.broadcasted_iota(jnp.int32, keys.shape, 1)

    # segmented inclusive prefix combine (doubling): vals[:, i]
    # accumulates the run prefix ending at i.  For "max" the masked-out
    # lanes inject 0.0, the identity on the kernel's non-negative max
    # domain.
    for d in range(steps):
        sh = 1 << d
        rolled = pltpu.roll(vals, sh, 1)
        ok = (idx >= sh) & (keys == pltpu.roll(keys, sh, 1))
        vals = _combine(op, vals, jnp.where(ok, rolled, 0.0))

    # the leading run continues the previous tile's last run: fold in
    # its carried total
    cont = (keys == ckey_ref[...]) & (pl.program_id(0) > 0)
    vals = _combine(op, vals, jnp.where(cont, cval_ref[...], 0.0))
    ckey_ref[...] = keys[:, tb - 1:tb]
    cval_ref[...] = vals[:, tb - 1:tb]
    tot_ref[...] = vals

    @pl.when(pl.program_id(0) == 0)
    def _():
        stalls_ref[0] = 0

    # the tile's live rows, in row order: live_ref[:n]
    def list_live(i, n):
        live_ref[n] = i
        return n + jnp.where(slots_ref[i] >= 0, 1, 0)

    n = jax.lax.fori_loop(0, tb, list_live, jnp.int32(0))

    # live row m: its slot, and the HBM window (128 lanes) holding it
    def slot_of(m):
        return slots_ref[live_ref[m]]

    def window(m):
        at = pl.multiple_of(slot_of(m) // LANES * LANES, LANES)
        return table_ref.at[:, pl.ds(at, LANES)]

    def read(m):
        b = m % RING
        return pltpu.make_async_copy(window(m), bufs.at[b], sems.at[0, b])

    def write(m):
        b = m % RING
        return pltpu.make_async_copy(bufs.at[b], window(m), sems.at[1, b])

    def land(end):
        """Wait, in order, for the writes of live rows [waited, end)."""
        def one(m, c):
            write(m).wait()
            return c
        w0 = waited_ref[0]
        jax.lax.fori_loop(w0, end, one, 0)
        waited_ref[0] = jnp.maximum(w0, end)

    def read_ahead(r):
        """Consider live row r, in row order: read it ahead into buffer
        r % RING, unless one of the RING - 1 rows before it (the other
        buffers' rows) shares its window.  The buffer's last row, r -
        RING, has landed: ``step`` waited for it first."""
        b = r % RING
        w = slot_of(r) // LANES
        clash = jnp.bool_(False)
        for k in range(RING):
            clash = clash | ((wins_ref[k] == w) & (b != k))
        wins_ref[b] = w
        ahead_ref[b] = jnp.where(clash, 0, 1)

        @pl.when(jnp.logical_not(clash))
        def _():
            read(r).start()

    for k in range(RING):
        wins_ref[k] = -1
    waited_ref[0] = 0

    def prologue(r, c):
        read_ahead(r)
        return c

    jax.lax.fori_loop(0, jnp.minimum(n, AHEAD), prologue, 0)

    # merge: a dynamic lane rotate carries total column i onto the slot's
    # lane of the window
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, LANES), 1)

    def step(m, c):
        b = m % RING

        @pl.when(ahead_ref[b] == 0)
        def _():
            # a same-window row may still be in flight: land every
            # write before this row's read
            land(m)
            stalls_ref[0] += 1
            read(m).start()

        read(m).wait()
        i = live_ref[m]
        slot = slots_ref[i]
        chunk = tot_ref[:, pl.ds(
            pl.multiple_of(i // LANES * LANES, LANES), LANES)]
        moved = pltpu.roll(chunk, (slot - i) & (LANES - 1), 1)
        cur = bufs[b]
        bufs[b] = jnp.where(
            lane == slot % LANES,
            _combine(op, cur, moved.astype(cur.dtype)), cur)
        write(m).start()

        r = m + AHEAD

        @pl.when(r < n)
        def _():
            land(r - RING + 1)
            read_ahead(r)
        return c

    jax.lax.fori_loop(0, n, step, 0)
    land(n)


def supported(deltas) -> bool:
    return deltas.ndim == 2 and deltas.shape[1] % 8 == 0


def _round_up(n: int, m: int) -> int:
    return -(-n // m) * m


@functools.partial(jax.jit, static_argnames=("interpret", "op"))
def slate_update(keys_sorted, deltas, slots, table_vals, *,
                 interpret: bool = False, op: str = "sum"):
    """keys_sorted: [B] int32 (invalid rows = int32.max, sorted);
    deltas: [B, D]; slots: [B] int32 (slate row for run-LAST rows, -1
    elsewhere); table_vals: [C, D].  ``op`` is the elementwise combine
    monoid: "sum" or "max" (non-negative domain — 0 is the identity
    injected for masked lanes).  Returns ``(table_vals, stalls)``: the
    updated table and the int32 count of live rows whose window read
    waited for an earlier same-window write (module docstring).

    The kernel works on the transposed [D, C] table — the layout XLA
    already gives a narrow [C, D] array on TPU, so the transposes are
    free there — with slates along the 128 lanes."""
    if op not in ("sum", "max"):
        raise ValueError(f"unknown slate_update op {op!r}")
    B, D = deltas.shape
    C = table_vals.shape[0]
    tb = min(TILE_B, _round_up(B, LANES))
    bp = _round_up(B, tb)
    cp = _round_up(C, LANES)
    steps = max((tb - 1).bit_length(), 1)
    # pad to whole tiles behind every real row: a pad row only ever
    # reads backwards into real rows' prefixes, never the other way,
    # and has no slot, so any key works for it
    pad = bp - B
    keys = jnp.pad(keys_sorted.astype(jnp.int32), (0, pad),
                   constant_values=jnp.iinfo(jnp.int32).max)[None, :]
    deltas_t = jnp.pad(deltas, ((0, pad), (0, 0))).T
    slots = jnp.pad(slots.astype(jnp.int32), (0, pad), constant_values=-1)
    table_t = jnp.pad(table_vals, ((0, cp - C), (0, 0))).T
    kernel = functools.partial(_slate_kernel, tb=tb, steps=steps, op=op)
    out, stalls = pl.pallas_call(
        kernel,
        grid=(bp // tb,),
        in_specs=[
            pl.BlockSpec((1, tb), lambda i: (0, i)),           # keys
            pl.BlockSpec((D, tb), lambda i: (0, i)),           # deltas
            pl.BlockSpec((tb,), lambda i: (i,),
                         memory_space=pltpu.SMEM),             # slots
            pl.BlockSpec(memory_space=pl.ANY),                 # table
        ],
        out_specs=[pl.BlockSpec(memory_space=pl.ANY),
                   pl.BlockSpec(memory_space=pltpu.SMEM)],     # stalls
        out_shape=[jax.ShapeDtypeStruct(table_t.shape, table_t.dtype),
                   jax.ShapeDtypeStruct((1,), jnp.int32)],
        scratch_shapes=[
            pltpu.VMEM((D, tb), jnp.float32),        # tile run totals
            pltpu.VMEM((1, 1), jnp.int32),           # carried run key
            pltpu.VMEM((D, 1), jnp.float32),         # carried run total
            pltpu.VMEM((RING, D, LANES), table_vals.dtype),  # windows
            pltpu.SMEM((tb,), jnp.int32),            # live rows
            pltpu.SMEM((RING,), jnp.int32),          # each buffer's window
            pltpu.SMEM((RING,), jnp.int32),          # each buffer read ahead?
            pltpu.SMEM((1,), jnp.int32),             # first unlanded write
            pltpu.SemaphoreType.DMA((2, RING)),      # reads, writes
        ],
        input_output_aliases={3: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name="slate_update",
    )(keys, deltas_t, slots, table_t)
    return out.T[:C], stalls[0]
