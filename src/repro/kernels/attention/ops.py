"""Dispatching wrapper: flash-attention Pallas kernel on TPU, ref elsewhere.

The dry-run / CPU tests always take the ref path (Pallas does not target
CPU); on a real TPU backend ``impl="auto"`` resolves to the Pallas kernel
when the shape is supported (head_dim multiple of 128 tiling etc.) and to
the ref otherwise.  An explicit ``impl="pallas"`` raises on an
unsupported shape.
"""
from __future__ import annotations

import jax

from repro.kernels.attention import ref as _ref


def mha(q, k, v, *, causal: bool = True, window: int = 0, q_offset: int = 0,
        chunk: int = 512, impl: str = "auto"):
    if impl == "auto" and jax.default_backend() != "tpu":
        impl = "ref"
    if impl in ("auto", "pallas"):
        from repro.kernels.flash_attention import kernel as _k
        if _k.supported(q, k, v, causal=causal, window=window):
            return _k.flash_attention(q, k, v, causal=causal, window=window,
                                      q_offset=q_offset)
        if impl == "pallas":
            raise ValueError(
                f"mha impl='pallas': flash_attention does not support "
                f"q {q.shape}, v {v.shape}")
    return _ref.mha(q, k, v, causal=causal, window=window,
                    q_offset=q_offset, chunk=chunk)
