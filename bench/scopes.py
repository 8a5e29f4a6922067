"""The engine's own names in a traced run: each device operation put
down to the named scope of the tick phase that produced it, and each
idle gap of the device to the drive-loop span the host was in.

The engine names its tick phases with ``jax.named_scope`` (``tick.*``
in ``core/engine.py``, ``apply.*`` in ``core/apply.py``); the profiler
carries the name stack of each operation in the ``tf_op`` stat of its
event metadata.  Its drive loop annotates ``source_build``,
``stack_sources``, ``chunk_dispatch`` and ``chunk_sync`` with profiler
annotations, which land on the host planes on the device's clock.

``jax.profiler.ProfileData`` exposes no event-metadata stats, so the
trace is decoded here with the protobuf runtime from a descriptor of
the few ``XSpace`` messages read (field numbers as in the profiler's
``xplane.proto``; other fields are skipped).  Times follow
``ProfileData``: whole nanoseconds, rounded down, so the operations
here are the ones ``trace_reduce.device_ops`` gives the other readers.

A metric reader finds the run's trace where the harness keeps it while
the readers run, ``<checkout>/.bench_out/<cell>-<seed>/trace``, and
takes it only if its ``bench.traced`` span is the run's traced span.
A trace holding none of the engine's scopes (or spans) gives no
reading.
"""
from __future__ import annotations

import functools
import glob
import gzip
import heapq
import os
import re
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from bench import trace_reduce as tr

OUT = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))), ".bench_out")

# the tick's phases, as the engine names them
SCOPES = ("tick.queues", "tick.telemetry", "tick.map", "apply.sort",
          "apply.probe", "apply.pack", "apply.write")
UNSCOPED = "unscoped"
KERNEL = "kernel"
# the tick's Pallas kernels, by the name tick_other_ms_per_tick excludes
KERNELS = re.compile(r"^(slate_update|countmin_update|histogram_update)")
# the drive loop's spans around one chunk, by the idle metric each feeds
SPANS = {"source_build": "source", "stack_sources": "dispatch",
         "chunk_dispatch": "dispatch", "chunk_sync": "sync"}
OUTSIDE = "outside"
TRACED = "bench.traced"


# ---- decoding ------------------------------------------------------
def _messages():
    """Message classes of the ``XSpace`` subset read here."""
    from google.protobuf import descriptor_pb2, descriptor_pool
    from google.protobuf import message_factory

    F = descriptor_pb2.FieldDescriptorProto
    one, rep = F.LABEL_OPTIONAL, F.LABEL_REPEATED
    i64, u64, s = F.TYPE_INT64, F.TYPE_UINT64, F.TYPE_STRING
    msg = F.TYPE_MESSAGE
    # name -> fields (name, number, label, type, message type)
    spec = {
        "XSpace": [("planes", 1, rep, msg, "XPlane")],
        "XPlane": [("name", 2, one, s, None),
                   ("lines", 3, rep, msg, "XLine"),
                   # map<int64, ...> fields, read as their entries
                   ("event_metadata", 4, rep, msg, "EventMetadataEntry"),
                   ("stat_metadata", 5, rep, msg, "StatMetadataEntry")],
        "EventMetadataEntry": [("key", 1, one, i64, None),
                               ("value", 2, one, msg, "XEventMetadata")],
        "StatMetadataEntry": [("key", 1, one, i64, None),
                              ("value", 2, one, msg, "XStatMetadata")],
        "XLine": [("name", 2, one, s, None),
                  ("timestamp_ns", 3, one, i64, None),
                  ("events", 4, rep, msg, "XEvent")],
        "XEvent": [("metadata_id", 1, one, i64, None),
                   ("offset_ps", 2, one, i64, None),
                   ("duration_ps", 3, one, i64, None)],
        "XEventMetadata": [("id", 1, one, i64, None),
                           ("name", 2, one, s, None),
                           ("stats", 5, rep, msg, "XStat")],
        "XStatMetadata": [("id", 1, one, i64, None),
                          ("name", 2, one, s, None)],
        "XStat": [("metadata_id", 1, one, i64, None),
                  ("str_value", 5, one, s, None),
                  ("ref_value", 7, one, u64, None)],
    }
    fd = descriptor_pb2.FileDescriptorProto(
        name="bench_xplane_subset.proto", package="bench_xplane",
        syntax="proto3")
    for name, fields in spec.items():
        m = fd.message_type.add(name=name)
        if name == "XStat":
            m.oneof_decl.add(name="value")    # the stat's one value
        for fname, num, label, typ, ref in fields:
            f = m.field.add(name=fname, number=num, label=label, type=typ)
            if ref:
                f.type_name = f".bench_xplane.{ref}"
            if name == "XStat" and fname != "metadata_id":
                f.oneof_index = 0
    pool = descriptor_pool.DescriptorPool()
    pool.Add(fd)
    return message_factory.GetMessageClass(
        pool.FindMessageTypeByName("bench_xplane.XSpace"))


@dataclass
class DeviceOps:
    """Operations of one device's ``XLA Ops`` line, by start: times in
    ns, instruction names (``trace_reduce.short``), and the ``tf_op``
    name stack of each ("" where absent)."""
    start: np.ndarray
    end: np.ndarray
    names: List[str]
    tf_ops: List[str]


@dataclass
class Space:
    devices: Dict[int, DeviceOps] = field(default_factory=dict)
    spans: List[tr.HostSpan] = field(default_factory=list)


def decode(raw: bytes) -> Space:
    """Device operations and host spans of a serialized ``XSpace``."""
    xs = _messages()()
    xs.ParseFromString(raw)
    out = Space()
    for plane in xs.planes:
        dev = tr.DEVICE_PLANE.match(plane.name)
        if not dev and not plane.name.startswith("/host:"):
            continue
        stat_names = {e.key: e.value.name for e in plane.stat_metadata}
        meta = {}                      # metadata id -> (name, tf_op)
        for e in plane.event_metadata:
            tf_op = ""
            for stat in e.value.stats:
                if stat_names.get(stat.metadata_id) == "tf_op":
                    # a string, or a reference to an interned one
                    tf_op = (stat.str_value
                             if stat.WhichOneof("value") == "str_value"
                             else stat_names.get(stat.ref_value, ""))
            meta[e.key] = (e.value.name, tf_op)
        for line in plane.lines:
            if dev and line.name != tr.OPS_LINE:
                continue
            base = line.timestamp_ns * 1000
            st, en, names, tf_ops = [], [], [], []
            for ev in line.events:
                name, tf_op = meta.get(ev.metadata_id, ("", ""))
                s = (base + ev.offset_ps) // 1000
                st.append(s)
                en.append(s + ev.duration_ps // 1000)
                names.append(name)
                tf_ops.append(tf_op)
            if not dev:
                out.spans.extend(tr.HostSpan(line.name, n, a, b)
                                 for n, a, b in zip(names, st, en))
                continue
            order = np.argsort(np.asarray(st, np.float64), kind="stable")
            out.devices[int(dev.group(1))] = DeviceOps(
                np.asarray(st, np.float64)[order],
                np.asarray(en, np.float64)[order],
                [tr.short(names[i]) for i in order],
                [tf_ops[i] for i in order])
    return out


def load(path: str) -> Space:
    opener = gzip.open if path.endswith(".gz") else open
    with opener(path, "rb") as f:
        return decode(f.read())


# ---- attribution ---------------------------------------------------
def scope_of(tf_op: str) -> str:
    """The innermost of the tick's scopes in a name stack, else
    ``"unscoped"``."""
    for part in reversed(tf_op.split("/")):
        if part in SCOPES:
            return part
    return UNSCOPED


def device_time(start: Sequence[float], end: Sequence[float],
                names: Sequence[str], tf_ops: Sequence[str]
                ) -> Dict[str, float]:
    """Busy seconds by what ran: each instant in which some operation
    ran goes to a Pallas kernel (``"kernel"``) if one ran, else to the
    scope of the innermost operation running (the latest started; a
    loop's own time between the operations it encloses is its own),
    else ``"unscoped"``.  The values add up to ``trace_reduce.busy``."""
    n = len(start)
    if n == 0:
        return {}
    cat = [KERNEL if KERNELS.search(nm) else scope_of(t)
           for nm, t in zip(names, tf_ops)]
    order = sorted(range(n), key=lambda i: (start[i], -end[i]))
    bounds = np.unique(np.concatenate([np.asarray(start, np.float64),
                                       np.asarray(end, np.float64)]))
    out: Dict[str, float] = {}
    kernels: List[float] = []               # ends of running kernels
    running: List[Tuple[float, float, int]] = []  # (-start, end, op)
    k = 0
    for a, b in zip(bounds[:-1].tolist(), bounds[1:].tolist()):
        while k < n and start[order[k]] <= a:
            i = order[k]
            if cat[i] == KERNEL:
                heapq.heappush(kernels, end[i])
            else:
                heapq.heappush(running, (-start[i], end[i], i))
            k += 1
        while kernels and kernels[0] <= a:
            heapq.heappop(kernels)
        while running and running[0][1] <= a:
            heapq.heappop(running)
        if kernels:
            c = KERNEL
        elif running:
            c = cat[running[0][2]]
        else:
            continue
        out[c] = out.get(c, 0.0) + (b - a) * 1e-9
    return out


def idle_time(gaps: Sequence[Tuple[float, float]],
              spans: Sequence[tr.HostSpan]) -> Dict[str, float]:
    """Idle seconds by the drive-loop span the host was in: each gap
    goes, by its midpoint, to the shortest covering span of ``SPANS``
    (``"source"``, ``"dispatch"``, ``"sync"``), else to ``"outside"``.
    The rule of ``trace_reduce.idle_by_host``, over every gap: the short
    ones it pools are op-to-op gaps within a running chunk, and the four
    must add up to the idle time."""
    loop = [sp for sp in spans if sp.name in SPANS]
    starts = np.asarray([sp.start for sp in loop], np.float64)
    ends = np.asarray([sp.end for sp in loop], np.float64)
    out = {c: 0.0 for c in (*sorted(set(SPANS.values())), OUTSIDE)}
    for a, b in gaps:
        mid = 0.5 * (a + b)
        cover = np.nonzero((starts <= mid) & (ends > mid))[0]
        c = (SPANS[loop[cover[np.argmin(ends[cover] - starts[cover])]].name]
             if cover.size else OUTSIDE)
        out[c] += (b - a) * 1e-9
    return out


# ---- one trace, reduced -------------------------------------------
@dataclass
class Reading:
    """Seconds per device by scope (``SCOPES``, ``"unscoped"``,
    ``"kernel"``) and idle seconds by drive-loop span over a trace's
    ``bench.traced`` span ``[lo, hi)``."""
    lo: float
    hi: float
    device: Dict[int, Dict[str, float]]
    idle: Dict[int, Dict[str, float]]
    scoped: bool       # some operation carries one of the scopes
    spanned: bool      # the drive loop's spans are in the trace


def reduce(space: Space) -> Optional[Reading]:
    marks = [s for s in space.spans if s.name == TRACED]
    if not marks or not space.devices:
        return None
    lo, hi = marks[0].start, marks[0].end
    device, idle = {}, {}
    scoped = False
    for d, ops in space.devices.items():
        keep = (ops.end > lo) & (ops.start < hi)
        st = np.maximum(ops.start[keep], lo)
        en = np.minimum(ops.end[keep], hi)
        names = [x for x, k in zip(ops.names, keep) if k]
        tf_ops = [x for x, k in zip(ops.tf_ops, keep) if k]
        scoped = scoped or any(scope_of(t) != UNSCOPED for t in tf_ops)
        device[d] = device_time(st.tolist(), en.tolist(), names, tf_ops)
        gaps = tr.gaps(tr.Ops(names, st, en), lo, hi)
        idle[d] = idle_time(gaps, space.spans)
    spanned = any(sp.name in SPANS for sp in space.spans)
    return Reading(lo, hi, device, idle, scoped, spanned)


def read_path(path: str) -> Optional[Reading]:
    """The reading of one trace file, parsed once per version of it."""
    st = os.stat(path)
    return _read(os.path.abspath(path), st.st_size, st.st_mtime_ns)


@functools.lru_cache(maxsize=2)
def _read(path: str, size: int, mtime_ns: int) -> Optional[Reading]:
    return reduce(load(path))


def reading(run) -> Optional[Reading]:
    """The reading of ``run``'s own trace: the newest trace of its cell
    whose ``bench.traced`` span is ``[run.trace_lo, run.trace_hi)``."""
    if not run.ops or run.traced_ticks <= 0:
        return None
    paths = glob.glob(os.path.join(OUT, f"{run.cell['name']}-*", "trace",
                                   "**", "*.xplane.pb"), recursive=True)
    for path in sorted(paths, key=os.path.getmtime, reverse=True):
        r = read_path(path)
        if r is not None and (r.lo, r.hi) == (run.trace_lo, run.trace_hi):
            return r
    return None


def _per_tick(run, per_device: Dict[int, Dict[str, float]], cat: str):
    return 1e3 * float(np.mean([per_device.get(d, {}).get(cat, 0.0)
                                for d in run.ops])) / run.traced_ticks


def device_ms(run, scope: str) -> Optional[float]:
    """Device ms per traced tick in ``scope`` (one of ``SCOPES`` or
    ``"unscoped"``), mean over chips."""
    r = reading(run)
    if r is None or not r.scoped:
        return None
    return _per_tick(run, r.device, scope)


def idle_ms(run, cat: str) -> Optional[float]:
    """Device idle ms per traced tick under the drive-loop spans of
    ``cat`` (``"source"``, ``"dispatch"``, ``"sync"``, ``"outside"``),
    mean over chips."""
    r = reading(run)
    if r is None or not r.spanned:
        return None
    return _per_tick(run, r.idle, cat)
