"""Dispatching wrapper for the chunked SSD linear recurrence.

``impl="auto"`` takes the kernel on TPU where the shape is supported and
the ref elsewhere; an explicit ``impl="pallas"`` raises on an unsupported
shape.
"""
from __future__ import annotations

import jax

from repro.kernels.ssd import ref as _ref

ssd_step = _ref.ssd_step


def ssd(q, k, v, log_a, *, chunk: int = 256, initial_state=None,
        impl: str = "auto"):
    if impl == "auto" and jax.default_backend() != "tpu":
        impl = "ref"
    if impl in ("auto", "pallas"):
        from repro.kernels.ssd_scan import kernel as _k
        if _k.supported(q, k, v):
            return _k.ssd_scan(q, k, v, log_a, chunk=chunk,
                               initial_state=initial_state)
        if impl == "pallas":
            raise ValueError(
                f"ssd impl='pallas': unsupported q {q.shape}, v {v.shape}")
    return _ref.ssd(q, k, v, log_a, chunk=chunk, initial_state=initial_state)
