"""Latency-histogram update — the observability hot-path kernel.

One invocation folds a microbatch of power-of-two latency buckets into
the [rows, width] histogram: each row's bucket indices are expanded to
a one-hot mask and reduced over the batch — exactly the count-min
kernel's body (``kernels/countmin/kernel.onehot_update``), so the two
share it and differ only in the kernel's name.  The histogram is
aliased in/out so the update is in-place; *bucketizing* (the clz-based
power-of-two binning) stays outside the kernel, plain jnp on the
already-resident latencies, mirroring how ``countmin_update`` receives
pre-hashed columns.

Masked-out events are folded into a sink column (``width``, which no
iota lane matches) before the call, so the kernel carries no validity
plumbing.  rows is 1 in practice (one histogram per updater arc) and
width a lane-aligned multiple of 128 — the logical power-of-two
buckets occupy a prefix and the padded tail is never hit because the
bucket index saturates below it.
"""
from __future__ import annotations

from repro.kernels.countmin.kernel import onehot_update, supported

__all__ = ["histogram_update", "supported"]


def histogram_update(counts, cols, add, *, interpret: bool = False):
    """counts: [rows, width] int32 (aliased in/out); cols: [rows, B]
    int32 bucket indices; add: [B] int32 0/1 increment per event.
    Returns the updated histogram."""
    return onehot_update(counts, cols, add, interpret=interpret,
                         name="histogram_update")
