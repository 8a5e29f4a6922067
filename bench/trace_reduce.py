"""From a JAX profiler trace (``.xplane.pb``) to per-device operation
intervals, their busy union, idle gaps, and time by operation name.

Device planes are ``/device:TPU:<n>``; their ``XLA Ops`` line holds one
event per operation run on the device.  Host planes (``/host:...``)
hold the host threads' spans, the benchmark's own
``jax.profiler.TraceAnnotation`` spans among them.  All times are
nanoseconds on the trace's one clock.
"""
from __future__ import annotations

import glob
import gzip
import os
import re
from dataclasses import dataclass
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
# a device event's name is its HLO text: "%slate_update.19 = f32[...]
# custom-call(...)"; the instruction name leads it
INSTR = re.compile(r"^%?([^\s=]+)\s*=")
LAYOUT = re.compile(r"\{[^{}]*\}")
# control flow whose events enclose the operations they run
CONTAINERS = re.compile(r"^(while|conditional|call)(\.\d+)?$")


def short(text: str) -> str:
    """``"slate_update.19"`` of an event named by its HLO text."""
    m = INSTR.match(text)
    return m.group(1) if m else text


def label(text: str) -> str:
    """Instruction, result shape and opcode: ``"fusion.526 = s32[65536]
    fusion"``."""
    head = LAYOUT.sub("", text.lstrip("%")).split("(")[0]
    return " ".join(head.split())[:120]


@dataclass
class Ops:
    """Operations of one device: instruction names, ``[start, end)`` in
    ns, and the HLO text each event was named by."""
    names: List[str]
    start: np.ndarray
    end: np.ndarray
    texts: Optional[List[str]] = None

    def time(self, pattern: str) -> float:
        """Seconds spent in operations whose name matches ``pattern``
        (``re.search``)."""
        rx = re.compile(pattern)
        sel = np.asarray([bool(rx.search(n)) for n in self.names], bool)
        if not sel.any():
            return 0.0
        return float((self.end[sel] - self.start[sel]).sum()) * 1e-9

    def clip(self, lo: float, hi: float) -> "Ops":
        keep = (self.end > lo) & (self.start < hi)
        texts = self.texts or self.names
        return Ops([n for n, k in zip(self.names, keep) if k],
                   np.maximum(self.start[keep], lo),
                   np.minimum(self.end[keep], hi),
                   [t for t, k in zip(texts, keep) if k])


@dataclass
class HostSpan:
    thread: str
    name: str
    start: float
    end: float


def find_xplane(trace_dir: str) -> str:
    hits = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                            recursive=True))
    if not hits:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return hits[-1]


def load(path: str):
    """ProfileData of an ``.xplane.pb`` (or a gzipped one)."""
    from jax.profiler import ProfileData
    if path.endswith(".gz"):
        with gzip.open(path, "rb") as f:
            return ProfileData.from_serialized_xspace(f.read())
    return ProfileData.from_file(path)


def device_ops(pd) -> Dict[int, Ops]:
    out: Dict[int, Ops] = {}
    for plane in pd.planes:
        m = DEVICE_PLANE.match(plane.name)
        if not m:
            continue
        names, st, en = [], [], []
        for line in plane.lines:
            if line.name != OPS_LINE:
                continue
            for ev in line.events:
                names.append(ev.name)
                st.append(ev.start_ns)
                en.append(ev.start_ns + ev.duration_ns)
        order = np.argsort(np.asarray(st, np.float64), kind="stable")
        out[int(m.group(1))] = Ops([short(names[i]) for i in order],
                                   np.asarray(st, np.float64)[order],
                                   np.asarray(en, np.float64)[order],
                                   [names[i] for i in order])
    return out


def host_spans(pd) -> List[HostSpan]:
    """Events of the host threads."""
    out = []
    for plane in pd.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                out.append(HostSpan(line.name, ev.name, ev.start_ns,
                                    ev.start_ns + ev.duration_ns))
    return out


def union(start: np.ndarray, end: np.ndarray
          ) -> List[Tuple[float, float]]:
    """Merged ``[start, end)`` intervals, sorted."""
    out: List[Tuple[float, float]] = []
    for s, e in sorted(zip(start.tolist(), end.tolist())):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def busy(ops: Ops, lo: float, hi: float) -> float:
    """Seconds in ``[lo, hi)`` in which some operation ran."""
    c = ops.clip(lo, hi)
    return sum(e - s for s, e in union(c.start, c.end)) * 1e-9


def gaps(ops: Ops, lo: float, hi: float) -> List[Tuple[float, float]]:
    """Idle ``[start, end)`` intervals of ``[lo, hi)``, in ns."""
    c = ops.clip(lo, hi)
    out, cur = [], lo
    for s, e in union(c.start, c.end):
        if s > cur:
            out.append((cur, s))
        cur = max(cur, e)
    if hi > cur:
        out.append((cur, hi))
    return out


SHORT_GAP_NS = 10_000      # gaps shorter than this are op-to-op overhead


def idle_by_host(gaps_ns: Sequence[Tuple[float, float]],
                 spans: Sequence[HostSpan]) -> Dict[str, float]:
    """Idle seconds by what the host was doing: each gap goes to the
    shortest host span covering its midpoint (``"(no host span)"`` if
    none); gaps under 10 us are op-to-op overhead, pooled."""
    starts = np.asarray([sp.start for sp in spans], np.float64)
    ends = np.asarray([sp.end for sp in spans], np.float64)
    out: Dict[str, float] = {}
    for a, b in gaps_ns:
        if b - a < SHORT_GAP_NS:
            lab = "(between ops, under 10 us)"
        else:
            mid = 0.5 * (a + b)
            cover = np.nonzero((starts <= mid) & (ends > mid))[0]
            lab = (spans[cover[np.argmin(ends[cover] - starts[cover])]].name
                   if cover.size else "(no host span)")
        out[lab] = out.get(lab, 0.0) + (b - a) * 1e-9
    return out


def top_ops(per_device: Dict[int, Ops], k: int = 10
            ) -> List[Tuple[str, float]]:
    """Operations taking most device time, summed over devices, each
    instruction by its name, result shape and opcode (a scan runs one
    instruction once per tick); loops and calls that only enclose other
    operations are left out."""
    tot: Dict[str, float] = {}
    for ops in per_device.values():
        texts = ops.texts or ops.names
        for n, t, s, e in zip(ops.names, texts, ops.start, ops.end):
            if CONTAINERS.match(n):
                continue
            lab = label(t)
            tot[lab] = tot.get(lab, 0.0) + (e - s) * 1e-9
    return sorted(tot.items(), key=lambda kv: -kv[1])[:k]
