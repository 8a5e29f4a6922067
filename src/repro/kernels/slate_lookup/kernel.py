"""Muppet read-path hot loop — batched slate point-lookup.

One kernel invocation answers a [Q] vector of point reads against the
open-addressing slate table: per query, walk the (precomputed) probe
chain until the key matches, then DMA that slate out of HBM — the same
slot-at-a-time access pattern the write kernel's scatter uses, in
reverse.  The probe *candidates* are computed outside the kernel with
the table's own double-hash sequence, so the hash math exists in
exactly one place and the kernel is pure pointer-chasing: SMEM holds
the small int vectors (queries, candidate slots, results) one tile of
``TILE_Q`` queries at a time, the table stays in HBM (``ANY``) and only
the 128-lane windows holding probed slots cross into SMEM/VMEM.

The value table is read through its lane-dense [D, C] view (slates
along the lanes), the layout XLA already gives a narrow [C, D] array on
TPU.  Serving shape, not throughput shape: the walk is a scalar loop —
the win over the host path is collapsing Q round-trips into one
dispatch, not FLOPs.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# queries per grid step (a multiple of the 128 lanes, and of the 1-D
# SMEM tiling XLA uses): bounds the SMEM blocks at any read-batch size
TILE_Q = 1024
LANES = 128


def _window(ref, c):
    """The 128-lane window of a [rows, C] HBM ref holding column c."""
    return ref.at[:, pl.ds(pl.multiple_of(c // LANES * LANES, LANES),
                           LANES)]


def _lookup_kernel(*refs, n_planes: int, P: int, tq: int):
    """Keys arrive as ``n_planes`` int32 planes — one for int32 keys,
    (lo, hi) for int64 (TPU SMEM scalars are 32-bit) — and a hit is
    equality on every plane: bit-exact without wide ints in the kernel.
    """
    n = n_planes
    q_refs, (cand_ref,), k_refs = refs[:n], refs[n:n + 1], \
        refs[n + 1:2 * n + 1]
    vals_ref, slot_ref, found_ref, rows_ref = refs[2 * n + 1:2 * n + 5]
    kbufs, (vbuf_ref, sem) = refs[2 * n + 5:3 * n + 5], refs[3 * n + 5:]
    rows_ref[...] = jnp.zeros(rows_ref.shape, rows_ref.dtype)
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, LANES), 1)

    def body(qi, _):
        # fetch the key window of every candidate at once, then compare
        # in probe order — the first hit wins (table.lookup's first_true)
        copies = [pltpu.make_async_copy(_window(k_refs[h], cand_ref[p, qi]),
                                        kbufs[h].at[pl.ds(p, 1), :],
                                        sem.at[h, p])
                  for p in range(P) for h in range(n)]
        for cp in copies:
            cp.start()
        for cp in copies:
            cp.wait()
        qs = [q_ref[qi] for q_ref in q_refs]
        slot, found = jnp.int32(-1), jnp.bool_(False)
        for p in range(P):
            c = cand_ref[p, qi]
            hit = functools.reduce(
                jnp.logical_and,
                [kbufs[h][p, c % LANES] == qs[h] for h in range(n)])
            slot = jnp.where(hit & ~found, c, slot)
            found = found | hit
        slot_ref[qi] = slot
        found_ref[qi] = found.astype(jnp.int32)

        # a hit rotates the slot's column onto the query's lane of the
        # [D, TILE_Q] row block; missing keys keep the block's zeros
        @pl.when(found)
        def _():
            pltpu.sync_copy(_window(vals_ref, slot), vbuf_ref)
            moved = pltpu.roll(vbuf_ref[...], (qi - slot) & (LANES - 1), 1)
            at = pl.ds(pl.multiple_of(qi // LANES * LANES, LANES), LANES)
            rows_ref[:, at] = jnp.where(lane == qi % LANES, moved,
                                        rows_ref[:, at])
        return 0

    jax.lax.fori_loop(0, tq, body, 0)


def supported(table_vals) -> bool:
    return table_vals.ndim == 2 and table_vals.shape[1] % 8 == 0


def _split_planes(a):
    """Integer [N] -> (lo, hi) int32 bit planes (exact for 64-bit)."""
    u = a.astype(jnp.uint64)
    lo = u.astype(jnp.uint32).astype(jnp.int32)
    hi = (u >> jnp.uint64(32)).astype(jnp.uint32).astype(jnp.int32)
    return lo, hi


def _round_up(n: int, m: int) -> int:
    return -(-n // m) * m


def _call(queries, cand, key_planes, table_vals, *, interpret: bool):
    """``queries``: [Q] int32 query planes; ``key_planes``: the matching
    [C] int32 table-key planes.  Pads Q to whole query tiles and C to
    whole lane tiles; returns ``(slot, found, rows)``."""
    n = len(queries)
    Q = queries[0].shape[0]
    P = cand.shape[0]
    C, D = table_vals.shape
    tq = min(TILE_Q, _round_up(Q, LANES))
    qp = _round_up(Q, tq)
    cp = _round_up(C, LANES)
    queries = [jnp.pad(q, (0, qp - Q)) for q in queries]
    cand = jnp.pad(cand.astype(jnp.int32), ((0, 0), (0, qp - Q)))
    key_planes = [jnp.pad(k, (0, cp - C), constant_values=-1)[None, :]
                  for k in key_planes]
    vals_t = jnp.pad(table_vals, ((0, cp - C), (0, 0))).T
    smem_q = pl.BlockSpec((tq,), lambda i: (i,), memory_space=pltpu.SMEM)
    hbm = pl.BlockSpec(memory_space=pl.ANY)
    slot, found, rows = pl.pallas_call(
        functools.partial(_lookup_kernel, n_planes=n, P=P, tq=tq),
        grid=(qp // tq,),
        in_specs=([smem_q] * n
                  + [pl.BlockSpec((P, tq), lambda i: (0, i),
                                  memory_space=pltpu.SMEM)]
                  + [hbm] * (n + 1)),
        out_specs=[smem_q, smem_q,
                   pl.BlockSpec((D, tq), lambda i: (0, i))],
        out_shape=[
            jax.ShapeDtypeStruct((qp,), jnp.int32),
            jax.ShapeDtypeStruct((qp,), jnp.int32),
            jax.ShapeDtypeStruct((D, qp), table_vals.dtype),
        ],
        scratch_shapes=([pltpu.SMEM((P, LANES), jnp.int32)] * n  # keys
                        + [pltpu.VMEM((D, LANES), table_vals.dtype),
                           pltpu.SemaphoreType.DMA((n, P))]),
        interpret=interpret,
        name="slate_lookup",
    )(*queries, cand, *key_planes, vals_t)
    return slot[:Q], found[:Q].astype(bool), rows.T[:Q]


@functools.partial(jax.jit, static_argnames=("interpret",))
def slate_lookup(table_keys, query, cand, table_vals, *,
                 interpret: bool = False):
    """``table_keys``: int32 [C]; ``query``: int32 [Q]; ``cand``:
    int32 [P, Q] probe candidates (``table._probe_seq``); ``table_vals``:
    [C, D].  Returns ``(slot [Q], found [Q] bool, rows [Q, D])`` with
    rows of missing keys zeroed."""
    return _call([query.astype(jnp.int32)], cand,
                 [table_keys.astype(jnp.int32)], table_vals,
                 interpret=interpret)


@functools.partial(jax.jit, static_argnames=("interpret",))
def slate_lookup_wide(table_keys, query, cand, table_vals, *,
                      interpret: bool = False):
    """64-bit-key entry: like :func:`slate_lookup` but ``table_keys`` /
    ``query`` are int64, compared inside the kernel as (lo, hi) int32
    bit planes."""
    return _call(list(_split_planes(query)), cand,
                 list(_split_planes(table_keys)), table_vals,
                 interpret=interpret)
