"""Dispatching wrapper for the fused slate update.

``impl``:
  - "auto":      Pallas on TPU, jnp oracle elsewhere
  - "pallas":    the kernel (raises if the shape is unsupported)
  - "interpret": Pallas body in interpreter mode (CPU-testable; raises
                 like "pallas")
  - "ref":       pure-jnp segment-sum oracle

Every backend returns ``(table_vals, stalls)``: the kernel's count of
live rows whose window read waited for an earlier same-window write
(``kernel.py``), 0 for the oracle, which moves no windows.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels.slate_update import ref as _ref


def _segment_ids(keys_sorted):
    """Map sorted wide keys to int32 segment ids.  The kernel consumes
    keys only through adjacent-equality (run boundaries), which segment
    ids over a sorted vector preserve exactly — so int64 keys ride the
    int32 kernel losslessly."""
    boundary = jnp.concatenate(
        [jnp.zeros((1,), jnp.int32),
         (keys_sorted[1:] != keys_sorted[:-1]).astype(jnp.int32)])
    return jnp.cumsum(boundary)


def slate_update(keys_sorted, deltas, slots, table_vals, *,
                 impl: str = "auto", op: str = "sum"):
    if impl == "auto":
        impl = "pallas" if jax.default_backend() == "tpu" else "ref"
    if impl in ("pallas", "interpret"):
        from repro.kernels.slate_update import kernel as _k
        if not _k.supported(deltas):
            raise ValueError(
                f"slate_update impl={impl!r}: the kernel needs [B, D] "
                f"deltas with D % 8 == 0, got {deltas.shape}")
        ks = keys_sorted
        if jnp.dtype(ks.dtype).itemsize > 4:
            ks = _segment_ids(ks)
        return _k.slate_update(ks, deltas, slots, table_vals,
                               interpret=(impl == "interpret"), op=op)
    if impl != "ref":
        raise ValueError(f"unknown slate_update impl {impl!r}")
    return (_ref.slate_update(keys_sorted, deltas, slots, table_vals,
                              op=op), jnp.int32(0))
