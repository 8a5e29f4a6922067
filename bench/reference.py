"""Plain numpy reference of the counting application (paper Examples
1/4): per key, the number of events (``U1``) and, per lane ``j``, the
number of events whose value has bit ``j`` set (``UV``).  It imports
nothing of the program and takes nothing the program made.

Every event adds at most 1 to any slate, so every sum is a count, and a
count below 2**24 is exact in the program's f32 lanes.  A reference sum
at or above that is the yardstick's fault, not the program's:
:func:`exactness_guard` raises then.
"""
from __future__ import annotations

import numpy as np

EXACT_LIMIT = 1 << 24


class YardstickError(RuntimeError):
    """The traffic left the range in which the comparison is exact."""


def lane_bits(bits: np.ndarray, lanes: int) -> np.ndarray:
    """[N] uint8 -> [N, lanes] 0/1: what each event adds to each lane."""
    return ((bits[:, None] >> np.arange(lanes, dtype=np.uint8)) & 1) \
        .astype(np.int32)


class Reference:
    """Slates of every key after the events ``keys``/``bits`` (in order).
    ``at(keys, n)`` gives them after only the first ``n`` events, for
    reads taken while the stream flows."""

    def __init__(self, keys: np.ndarray, bits: np.ndarray, lanes: int):
        self.keys = np.asarray(keys, np.int64)
        self.bits = np.asarray(bits, np.uint8)
        self.lanes = lanes
        self._order = None

    def final(self, query: np.ndarray):
        """``(count [Q], lanes [Q, L])`` over all events."""
        return self.at(query, self.keys.size)

    def at(self, query: np.ndarray, n: int):
        """Slates of ``query`` keys after the first ``n`` events."""
        if self._order is None:
            # events grouped by key, in delivery order within a key
            self._order = np.argsort(self.keys, kind="stable")
            # (key, position) as one sortable number per event
            self._span = self.keys.size + 1
            self._sorted = self.keys[self._order] * self._span + self._order
            self._cum = np.concatenate(
                [np.zeros((1, self.lanes), np.int32),
                 np.cumsum(lane_bits(self.bits[self._order], self.lanes),
                           axis=0, dtype=np.int32)])
        query = np.asarray(query, np.int64) * self._span
        lo = np.searchsorted(self._sorted, query, side="left")
        end = np.searchsorted(self._sorted, query + n, side="left")
        return end - lo, self._cum[end] - self._cum[lo]

    def max_sum(self) -> int:
        """Largest slate sum of any key over all events."""
        if self.keys.size == 0:
            return 0
        return int(np.bincount(self.keys).max())


def exactness_guard(ref: Reference):
    top = ref.max_sum()
    if top >= EXACT_LIMIT:
        raise YardstickError(
            f"a reference sum reached {top} >= 2**24: f32 lanes are no "
            f"longer exact; shorten the window or lower the rate")
