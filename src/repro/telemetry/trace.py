"""Host-side span tracing + the control-plane JSONL log.

``span`` wraps the phases the drive loops already split — source
building, stacking, chunk dispatch, the per-chunk sync, WAL fence,
flush begin/commit, telemetry observe, reconfigure/migration, recovery
restore/replay.  Every span enters a ``jax.profiler.TraceAnnotation``
of its name: nearly free without a profiler session, and with one it
lands in the device trace on the device operations' clock, so the
device's idle time can be put down to the phase the host was in.
With ``TelemetryConfig(trace=True)`` the span is also recorded in the
engine's ``Tracer``, a bounded ring exported as Chrome trace-event JSON
(``ph: "X"`` complete events) that loads directly in Perfetto /
``chrome://tracing``.  Everything here is host work around calls the
drivers make anyway — no device syncs, no effect on the jitted tick.

``ControlLog`` is the autoscaler's flight recorder: one JSON line per
observe→decide→act cycle (report summary, decision + reason, applied
action outcome), append-only so post-hoc analysis can replay exactly
what the controller saw and did.
"""
from __future__ import annotations

import dataclasses
import json
import threading
import time
from collections import deque
from contextlib import contextmanager
from typing import Any, Dict, List, Optional

import jax
import numpy as np


def json_safe(v: Any) -> Any:
    """Best-effort conversion to JSON-serializable values."""
    if isinstance(v, (str, bool, int, float)) or v is None:
        return v
    if isinstance(v, np.ndarray):
        return v.tolist()
    if isinstance(v, np.generic):
        return v.item()
    if isinstance(v, dict):
        return {str(k): json_safe(x) for k, x in v.items()}
    if isinstance(v, (list, tuple)):
        return [json_safe(x) for x in v]
    if dataclasses.is_dataclass(v) and not isinstance(v, type):
        return json_safe(dataclasses.asdict(v))
    try:                                   # 0-d device arrays etc.
        return v.item()
    except Exception:
        return str(v)


class Tracer:
    """Ring-buffered Chrome-trace span recorder (thread-safe)."""

    def __init__(self, capacity: int = 4096):
        self.capacity = capacity
        self._events: deque = deque(maxlen=capacity)
        self._t0 = time.perf_counter()
        self._lock = threading.Lock()

    def _now_us(self) -> float:
        return (time.perf_counter() - self._t0) * 1e6

    @contextmanager
    def span(self, name: str, cat: str = "engine", **args):
        """Record a complete ("X") event around the block.  Yields the
        mutable args dict so outcomes measured inside the span (e.g. a
        migration's ``pause_s``) land on the span itself."""
        t0 = self._now_us()
        a: Dict[str, Any] = dict(args)
        try:
            yield a
        finally:
            self._push({"name": name, "cat": cat, "ph": "X",
                        "ts": t0, "dur": self._now_us() - t0,
                        "pid": 0,
                        "tid": threading.get_ident() % 100000,
                        "args": json_safe(a)})

    def _push(self, ev: Dict[str, Any]):
        with self._lock:
            self._events.append(ev)

    def events(self) -> List[Dict[str, Any]]:
        with self._lock:
            return list(self._events)

    def spans(self, name: str) -> List[Dict[str, Any]]:
        """All recorded spans with the given name, oldest first."""
        return [e for e in self.events() if e["name"] == name]

    def export(self, path: str) -> str:
        """Write Chrome trace-event JSON (opens in Perfetto)."""
        doc = {"traceEvents": self.events(), "displayTimeUnit": "ms"}
        with open(path, "w") as f:
            json.dump(doc, f)
        return path


def tracer_for(telemetry) -> Optional[Tracer]:
    """The span ring of an engine with ``telemetry`` config: a
    ``Tracer`` when ``telemetry.trace`` is set, else None."""
    return Tracer() if telemetry is not None and telemetry.trace else None


@contextmanager
def span(tracer: Optional[Tracer], name: str, **args):
    """One phase of a drive loop: a profiler annotation named ``name``
    always, and a ``tracer`` span with ``args`` when ``tracer`` is set.
    Yields the mutable args dict either way, so outcomes measured
    inside the span (a migration's ``pause_s``) land on the span."""
    with jax.profiler.TraceAnnotation(name):
        if tracer is None:
            yield args
        else:
            with tracer.span(name, **args) as a:
                yield a


class ControlLog:
    """Append-only JSONL log of controller cycles (thread-safe)."""

    def __init__(self, path: str):
        self.path = path
        self._f = open(path, "a")
        self._lock = threading.Lock()

    def log(self, record: Dict[str, Any]):
        line = json.dumps(json_safe(record))
        with self._lock:
            self._f.write(line + "\n")
            self._f.flush()

    def close(self):
        with self._lock:
            if not self._f.closed:
                self._f.close()
