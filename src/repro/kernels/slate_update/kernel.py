"""Muppet updater hot loop — fused segment-combine + slate scatter.

One kernel invocation applies one microbatch of *sorted* (key, delta)
events to the slate table: a log-depth segmented prefix-sum combines every
key's deltas in VMEM, then run-last rows read-modify-write their slate in
HBM (the innermost loop is a per-slate DMA scatter — the same access
pattern Cassandra-backed Muppet pays per updated slate, minus the network).
The table buffer is aliased in/out so the update is in-place.  Slates lie
along the lanes of the [D, C] table view, so a slate moves inside the
128-lane window that holds it.

The batch is walked in tiles of ``TILE_B`` rows (one grid step each, in
order): a run that crosses a tile edge carries its running total into
the next tile, so a run total is the same segmented combine whatever the
batch size, and the per-step VMEM/SMEM footprint stays fixed.

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


def _combine(op, a, b):
    return jnp.maximum(a, b) if op == "max" else a + b


def _slate_kernel(keys_ref, deltas_ref, slots_ref, table_in_ref,
                  table_ref, tot_ref, ckey_ref, cval_ref, buf_ref, *,
                  tb: int, steps: int, op: str):
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

    # scatter run totals into slate columns (read-modify-write of the
    # 128-lane window holding the slot: HBM moves only by DMA, in whole
    # lane tiles).  A dynamic lane rotate carries total column i onto
    # the slot's lane.
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, LANES), 1)

    def body(i, _):
        slot = slots_ref[i]

        @pl.when(slot >= 0)
        def _():
            win = table_ref.at[:, pl.ds(
                pl.multiple_of(slot // LANES * LANES, LANES), LANES)]
            pltpu.sync_copy(win, buf_ref)
            chunk = tot_ref[:, pl.ds(
                pl.multiple_of(i // LANES * LANES, LANES), LANES)]
            moved = pltpu.roll(chunk, (slot - i) & (LANES - 1), 1)
            cur = buf_ref[...]
            buf_ref[...] = jnp.where(
                lane == slot % LANES,
                _combine(op, cur, moved.astype(cur.dtype)), cur)
            pltpu.sync_copy(buf_ref, win)
        return 0

    jax.lax.fori_loop(0, tb, body, 0)


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
    injected for masked lanes).  Returns updated table_vals.

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
    out = pl.pallas_call(
        kernel,
        grid=(bp // tb,),
        in_specs=[
            pl.BlockSpec((1, tb), lambda i: (0, i)),           # keys
            pl.BlockSpec((D, tb), lambda i: (0, i)),           # deltas
            pl.BlockSpec((tb,), lambda i: (i,),
                         memory_space=pltpu.SMEM),             # slots
            pl.BlockSpec(memory_space=pl.ANY),                 # table
        ],
        out_specs=pl.BlockSpec(memory_space=pl.ANY),
        out_shape=jax.ShapeDtypeStruct(table_t.shape, table_t.dtype),
        scratch_shapes=[
            pltpu.VMEM((D, tb), jnp.float32),        # tile run totals
            pltpu.VMEM((1, 1), jnp.int32),           # carried run key
            pltpu.VMEM((D, 1), jnp.float32),         # carried run total
            pltpu.VMEM((D, LANES), table_vals.dtype),  # slate window
        ],
        input_output_aliases={3: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name="slate_update",
    )(keys, deltas_t, slots, table_t)
    return out.T[:C]
