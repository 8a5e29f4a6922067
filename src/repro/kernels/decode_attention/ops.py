"""Dispatching wrapper for decode attention (flash-decoding on TPU).

``impl="auto"`` takes the kernel on TPU where the shape is supported and
the ref elsewhere; an explicit ``impl="pallas"`` raises on an unsupported
shape.
"""
from __future__ import annotations

import jax

from repro.kernels.decode_attention import ref as _ref


def decode_attend(q, k_cache, v_cache, lengths, *, window: int = 0,
                  impl: str = "auto"):
    if impl == "auto" and jax.default_backend() != "tpu":
        impl = "ref"
    if impl in ("auto", "pallas"):
        from repro.kernels.decode_attention import kernel as _k
        if _k.supported(q, k_cache, v_cache):
            return _k.decode_attention(q, k_cache, v_cache, lengths,
                                       window=window)
        if impl == "pallas":
            raise ValueError(
                f"decode_attend impl='pallas': unsupported q {q.shape}, "
                f"cache {k_cache.shape}")
    return _ref.decode_attend(q, k_cache, v_cache, lengths, window=window)
