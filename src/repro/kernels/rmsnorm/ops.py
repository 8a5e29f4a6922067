"""Dispatching wrapper for fused RMSNorm.

``impl="auto"`` takes the kernel on TPU where the shape is supported and
the ref elsewhere; an explicit ``impl="pallas"`` raises on an unsupported
shape.
"""
from __future__ import annotations

import jax

from repro.kernels.rmsnorm import ref as _ref


def rmsnorm(x, w, *, eps: float = 1e-6, scale_offset: bool = False,
            impl: str = "auto"):
    if impl == "auto" and jax.default_backend() != "tpu":
        impl = "ref"
    if impl in ("auto", "pallas"):
        from repro.kernels.rmsnorm import kernel as _k
        if _k.supported(x):
            return _k.rmsnorm(x, w, eps=eps, scale_offset=scale_offset)
        if impl == "pallas":
            raise ValueError(
                f"rmsnorm impl='pallas': unsupported x {x.shape}")
    return _ref.rmsnorm(x, w, eps=eps, scale_offset=scale_offset)
