"""Dispatching wrapper for the latency-histogram update.

``impl`` (the same backend vocabulary as ``kernels/countmin``):
  - "auto":      Pallas on TPU, jnp oracle elsewhere
  - "pallas":    the kernel (raises if the shape is unsupported)
  - "interpret": Pallas body in interpreter mode (CPU-testable; raises
                 like "pallas")
  - "jnp" / "ref": pure-jnp scatter-add oracle

All backends are exact integer adds, so they agree bitwise.  ``add``
is the per-event 0/1 increment vector (invalid rows = 0) — the kernel
folds zeros into a sink column, the oracle scatter-adds them as-is.
"""
from __future__ import annotations

import jax

from repro.kernels.histogram import ref as _ref


def histogram_update(counts, cols, add, *, impl: str = "auto"):
    if impl == "auto":
        impl = "pallas" if jax.default_backend() == "tpu" else "ref"
    if impl in ("pallas", "interpret"):
        from repro.kernels.histogram import kernel as _k
        if not _k.supported(counts, cols):
            raise ValueError(
                f"histogram impl={impl!r}: the kernel needs [rows, width] "
                f"counts with width % 128 == 0 and [rows, B] columns, "
                f"got {counts.shape} and {cols.shape}")
        return _k.histogram_update(counts, cols, add,
                                   interpret=(impl == "interpret"))
    if impl not in ("ref", "jnp"):
        raise ValueError(f"unknown histogram impl {impl!r}")
    return _ref.histogram_update(counts, cols, add)
