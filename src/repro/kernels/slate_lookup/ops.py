"""Dispatching wrapper for the batched slate point-lookup.

``impl``:
  - "auto":      Pallas on TPU, jnp oracle elsewhere
  - "pallas":    the kernel (raises if the value layout is unsupported)
  - "interpret": Pallas body in interpreter mode (CPU-testable; raises
                 like "pallas")
  - "jnp" / "ref": the pure-jnp probe-walk oracle
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels.slate_lookup import ref as _ref


def lookup_slots(table_keys, query):
    """Probe-walk only: ``(slot [Q], found [Q])``.  Always the jnp
    oracle — the walk is a [P, Q] gather-compare, already one fused
    XLA op; the kernel earns its keep on the row gather."""
    return _ref.lookup_slots(table_keys, query)


def slate_lookup(table_keys, query, table_vals, *, impl: str = "auto"):
    """Fused probe walk + row gather over one [C, D] value matrix.
    Returns ``(slot [Q], found [Q], rows [Q, D])`` with missing rows
    zeroed; bitwise identical across every backend."""
    if impl == "auto":
        impl = "pallas" if jax.default_backend() == "tpu" else "jnp"
    if impl in ("pallas", "interpret"):
        from repro.kernels.slate_lookup import kernel as _k
        if not _k.supported(table_vals):
            raise ValueError(
                f"slate_lookup impl={impl!r}: the kernel needs one [C, D] "
                f"value matrix with D % 8 == 0, got {table_vals.shape}")
        from repro.slates.table import _probe_seq
        cand = _probe_seq(query, int(table_keys.shape[0]))
        # 64-bit keys enter the plane-split variant (SMEM scalars are
        # 32-bit); same probe chain, bit-exact comparison
        if jnp.dtype(query.dtype).itemsize > 4:
            return _k.slate_lookup_wide(
                table_keys, query, cand, table_vals,
                interpret=(impl == "interpret"))
        return _k.slate_lookup(table_keys, query, cand, table_vals,
                               interpret=(impl == "interpret"))
    if impl not in ("jnp", "ref"):
        raise ValueError(f"unknown slate_lookup impl {impl!r}")
    return _ref.slate_lookup(table_keys, query, table_vals)


def lookup_tree(table_keys, table_vals, query, *, impl: str = "auto"):
    """Batched lookup over a whole slate-value *pytree*.  A tree of one
    kernel-eligible [C, D] leaf (D % 8 == 0) goes to :func:`slate_lookup`
    with ``impl``.  Every other tree — several leaves, a scalar leaf, an
    unaligned width — is the oracle's shape under every ``impl``: the
    probe walk runs once and each leaf is gathered with jnp (still one
    fused XLA program).  Returns ``(found [Q], rows)`` with ``rows``
    leaves [Q, ...], missing keys zeroed — the shared core of
    ``Engine.read_slates`` and the distributed per-shard read."""
    leaves, treedef = jax.tree.flatten(table_vals)
    from repro.kernels.slate_lookup import kernel as _k
    if len(leaves) == 1 and _k.supported(leaves[0]):
        _, found, rows = slate_lookup(table_keys, query, leaves[0],
                                      impl=impl)
        return found, jax.tree.unflatten(treedef, [rows])
    slot, found = lookup_slots(table_keys, query)
    return found, _ref.gather_rows(table_vals, slot, found)
