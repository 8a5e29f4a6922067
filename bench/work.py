"""Bytes and operations each kernel's algorithm needs, from shapes and
the delivered events: what any implementation has to move, not what
today's kernel moves.  A kernel's roofline share is the least time the
chip could take for this work over the kernel's traced time."""
from __future__ import annotations

import numpy as np

WORD = 4       # int32 keys, slots, counts; f32 lanes


def slate_update_bytes(events: int, distinct: int, lanes: int) -> int:
    """One fused slate update of ``events`` valid events over a ``[D]``
    slate (``lanes`` = D): the B keys and B slots in, the ``[B, D]``
    deltas in, and a read and a write of the ``distinct`` touched
    ``[D]`` rows."""
    return WORD * (2 * events + events * lanes + 2 * distinct * lanes)


def traced_slate_update_bytes(ticks, widths) -> int:
    """Bytes the fused slate updates of the traced ticks need: each tick's
    valid events ``(keys, n)`` go through one update per updater, of slate
    width ``widths[u]``."""
    total = 0
    for keys, n in ticks:
        u = np.unique(keys[:n]).size
        total += sum(slate_update_bytes(n, u, d) for d in widths)
    return total
