"""Count-min sketch update — the telemetry hot-path kernel.

One invocation folds a microbatch of hashed event keys into the
[depth, width] sketch held in VMEM: for each hash row a tile of
``TILE_B`` events is expanded to a [TILE_B, width] one-hot mask and
reduced over the tile — a VPU-friendly histogram (no scalar scatter in
the inner loop, unlike the slate kernel whose rows are too wide to
one-hot).  The grid walks the batch tile by tile with the sketch block
resident, so the one-hot stays a few MiB at any batch size.  The sketch
is aliased in/out so the update is in-place; column hashing stays
outside the kernel (plain jnp on the already-resident keys), mirroring
how ``slate_update`` receives pre-computed slots.

Everything inside the kernel is rank-2 (TPU-native layouts): columns
arrive transposed as [B, depth] so each row's slice is a natural
[TILE_B, 1] block, and masked-out events — and the pad of the last
tile — are folded into a sink column (``width``, which no iota lane
matches) before the call, so the kernel carries no validity plumbing.

depth is small (2-8) and width a multiple of 128 (lane-aligned), so
the whole sketch is ~16 KB — it lives in VMEM for the duration of the
call and costs the tick no HBM traffic beyond the aliased buffer.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


# events per grid step: keeps the [TB, width] one-hot (2 MiB at the
# default width 2048) and the lane-padded [TB, depth] column block well
# inside the default scoped VMEM at any batch size
TILE_B = 256


def _cm_kernel(cols_ref, counts_in_ref, counts_ref, *,
               depth: int, tb: int, width: int):
    # the output block stays resident across the grid (constant index
    # map) and accumulates; the aliased input seeds it once
    @pl.when(pl.program_id(0) == 0)
    def _():
        counts_ref[...] = counts_in_ref[...]

    for d in range(depth):                      # static, small
        cols = cols_ref[:, d:d + 1]             # [TB, 1]
        iota = jax.lax.broadcasted_iota(jnp.int32, (tb, width), 1)
        hit = (iota == cols).astype(jnp.int32)  # sink column never hits
        counts_ref[d:d + 1, :] = counts_ref[d:d + 1, :] + \
            jnp.sum(hit, axis=0, keepdims=True)


def supported(counts, cols) -> bool:
    return (counts.ndim == 2 and cols.ndim == 2
            and counts.shape[1] % 128 == 0
            and cols.shape[0] == counts.shape[0])


@functools.partial(jax.jit, static_argnames=("interpret", "name"))
def onehot_update(counts, cols, add, *, name: str,
                  interpret: bool = False):
    """counts: [depth, width] int32 (aliased in/out); cols: [depth, B]
    int32 column per row and event; add: [B] int32 0/1 increment per
    event.  Returns the updated counts.  ``name`` labels the kernel in
    the compiled program (the latency histogram reuses this body)."""
    depth, width = counts.shape
    B = cols.shape[1]
    tb = min(TILE_B, -(-B // 8) * 8)
    bp = -(-B // tb) * tb
    # fold the increment mask into a sink column, pad B to whole tiles
    # with it, and transpose to [B, depth] so the kernel stays rank-2
    cols_t = jnp.where(add[None, :] > 0, cols,
                       jnp.int32(width)).T.astype(jnp.int32)
    cols_t = jnp.pad(cols_t, ((0, bp - B), (0, 0)),
                     constant_values=width)
    kernel = functools.partial(_cm_kernel, depth=depth, tb=tb, width=width)
    return pl.pallas_call(
        kernel,
        grid=(bp // tb,),
        in_specs=[
            pl.BlockSpec((tb, depth), lambda i: (i, 0)),      # cols (T)
            pl.BlockSpec((depth, width), lambda i: (0, 0)),   # sketch in
        ],
        out_specs=pl.BlockSpec((depth, width), lambda i: (0, 0)),
        out_shape=jax.ShapeDtypeStruct(counts.shape, counts.dtype),
        input_output_aliases={1: 0},
        interpret=interpret,
        name=name,
    )(cols_t, counts)


def countmin_update(counts, cols, add, *, interpret: bool = False):
    """The count-min sketch update: ``onehot_update`` over the sketch's
    ``depth`` hash rows."""
    return onehot_update(counts, cols, add, interpret=interpret,
                         name="countmin_update")
