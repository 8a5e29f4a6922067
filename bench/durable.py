"""The durable path's host spans in a traced run, read once per trace.

The engine names its flush boundary on the drive thread (``flush_begin``,
``flush_commit``, and ``wal_fence`` as ``Engine.run`` returns), the
slate store's write of one flush on the flusher thread
(``store_write``) and each write-ahead append on the log's writer thread
(``wal_append``).  They reach the profiler as annotations, on the device
operations' clock.  The trace is found and decoded as ``bench/scopes.py``
does; a trace holding no such span gives no reading.
"""
from __future__ import annotations

import functools
import glob
import os
from typing import Optional

import numpy as np

from bench import scopes
from bench import trace_reduce as tr

# spans of the drive thread alone (Python threads share one line name in
# the trace, so the names tell the threads apart)
FLUSH = ("flush_begin", "flush_commit", "wal_fence")


@functools.lru_cache(maxsize=2)
def _load(path: str, size: int, mtime_ns: int) -> scopes.Space:
    return scopes.load(path)


def space(run) -> Optional[scopes.Space]:
    """The decoded trace of ``run``: the newest trace of its cell whose
    ``bench.traced`` span is ``[run.trace_lo, run.trace_hi)``."""
    if not run.ops or run.traced_ticks <= 0:
        return None
    paths = glob.glob(os.path.join(scopes.OUT, f"{run.cell['name']}-*",
                                   "trace", "**", "*.xplane.pb"),
                      recursive=True)
    for path in sorted(paths, key=os.path.getmtime, reverse=True):
        st = os.stat(path)
        sp = _load(os.path.abspath(path), st.st_size, st.st_mtime_ns)
        marks = [s for s in sp.spans if s.name == scopes.TRACED]
        if marks and (marks[0].start, marks[0].end) == (run.trace_lo,
                                                        run.trace_hi):
            return sp
    return None


def _per_tick(run, seconds: float) -> float:
    return 1e3 * seconds / run.traced_ticks


def span_ms(run, name: str) -> Optional[float]:
    """Time in spans named ``name``, clipped to the traced span, in ms
    per traced source tick."""
    sp = space(run)
    hits = [s for s in sp.spans if s.name == name] if sp else []
    if not hits:
        return None
    lo, hi = run.trace_lo, run.trace_hi
    ns = sum(max(0.0, min(s.end, hi) - max(s.start, lo)) for s in hits)
    return _per_tick(run, ns * 1e-9)


def idle_flush_ms(run) -> Optional[float]:
    """Device idle time whose midpoint lies in a ``FLUSH`` span, mean
    over chips, in ms per traced source tick."""
    sp = space(run)
    spans = [s for s in sp.spans if s.name in FLUSH] if sp else []
    if not spans:
        return None
    starts = np.asarray([s.start for s in spans], np.float64)
    ends = np.asarray([s.end for s in spans], np.float64)
    per_device = []
    for ops in run.ops.values():
        idle = 0.0
        for a, b in tr.gaps(ops, run.trace_lo, run.trace_hi):
            mid = 0.5 * (a + b)
            if np.any((starts <= mid) & (ends > mid)):
                idle += (b - a) * 1e-9
        per_device.append(idle)
    return _per_tick(run, float(np.mean(per_device)))
