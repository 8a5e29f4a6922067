"""One run of one benchmark cell: build the cell's deployment through the
program's front door, warm it up, drive ``App.run`` in segments for the
window, then check what the window produced against the plain reference
and reduce the traced run to the per-layer metrics.

Everything that belongs to one configuration, traffic mix or per-layer
metric is found by name: ``bench/configs/<config>.json`` (the file
``BENCHMARK.json`` names), ``bench/apps/<app>.py`` (the app the
configuration names), ``bench/traffic/<mix>.json`` and
``bench/metrics/<metric>.py``.
"""
from __future__ import annotations

import gc
import importlib
import importlib.util
import json
import os
import resource
import shutil
import time
from dataclasses import dataclass, field
from typing import Callable, Dict, List, Optional

import jax
import numpy as np

from bench import peaks as peaks_mod
from bench import trace_reduce, traffic, work
from bench.reference import Reference, YardstickError, exactness_guard  # noqa: F401
from repro.core.engine import StateHandle

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
TRACE_S = 3.0              # least traced span of the window
HOT_KEYS = 1024            # hottest ids always in the read-back sample
ABSENT_KEYS = 64           # never-touched ids in the read-back sample


def profile_options():
    """Device ops and host TraceMe spans; no Python call tracing, which
    would slow the host side of the traced run many times over."""
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    return opts


# ---- what BENCHMARK.json names -------------------------------------
def load_spec(root: str = ROOT) -> dict:
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        return json.load(f)


def resolve(spec: dict, workload: str, root: str = ROOT):
    """``(cell, config, mix)`` of a workload name."""
    cells = {w["name"]: w for w in spec["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json")
    cell = cells[workload]
    entry = {c["name"]: c for c in spec["configs"]}[cell["config"]]
    with open(os.path.join(root, entry["file"])) as f:
        cfg = json.load(f)
    return cell, cfg, traffic.load_mix(cell["traffic"], BENCH)


def metric_reader(name: str) -> Callable:
    """``read(run) -> float | None`` of ``bench/metrics/<name>.py``."""
    path = os.path.join(BENCH, "metrics", f"{name}.py")
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + name.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


# ---- the state handle the window records through --------------------
class RecordingHandle(StateHandle):
    """``Engine.run`` sets ``handle.state`` after every chunk, once it has
    waited for the chunk; the setter records the host time."""

    def __init__(self, engine, state):
        self.times: List[float] = []
        super().__init__(engine, state)

    @property
    def state(self):
        return self._state

    @state.setter
    def state(self, s):
        self._state = s
        self.times.append(time.perf_counter())


@dataclass
class RunData:
    """What the per-layer metric readers read: the device operations of
    the traced part of the window and the work of its ticks."""
    cell: dict
    cfg: dict
    mix: dict
    device_kind: str
    ops: Dict[int, trace_reduce.Ops] = field(default_factory=dict)
    trace_lo: float = 0.0          # traced span, ns on the trace clock
    trace_hi: float = 0.0
    traced_ticks: int = 0
    slate_update_bytes: int = 0
    peaks: dict = field(default_factory=dict)

    @property
    def traced_s(self) -> float:
        return (self.trace_hi - self.trace_lo) * 1e-9

    def busy_s(self, device: int) -> float:
        return trace_reduce.busy(self.ops[device], self.trace_lo,
                                 self.trace_hi)

    def op_s(self, pattern: str) -> float:
        """Seconds in matching operations, mean over devices."""
        if not self.ops:
            return 0.0
        return float(np.mean([o.time(pattern) for o in self.ops.values()]))


def _peak_bytes(devices) -> int:
    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in devices)


def _longest_gaps(times: List[float], t0: float, t1: float, k: int = 3):
    """The ``k`` longest gaps between republished states in the window:
    ``[start - t0, seconds]``."""
    ts = [t0] + [t for t in times if t0 < t < t1] + [t1]
    gaps = sorted(((b - a, a - t0) for a, b in zip(ts, ts[1:])),
                  reverse=True)[:k]
    return [[round(a, 3), round(g, 4)] for g, a in gaps]


class HostClock:
    """What the host gave the process over the window: its CPU seconds,
    involuntary context switches, and the machine's steal time (CPU
    seconds a hypervisor gave to others)."""

    @staticmethod
    def _steal() -> float:
        try:
            with open("/proc/stat") as f:
                cpu = f.readline().split()
            return int(cpu[8]) / os.sysconf("SC_CLK_TCK")
        except (OSError, IndexError, ValueError):
            return float("nan")

    def __init__(self):
        r = resource.getrusage(resource.RUSAGE_SELF)
        self._cpu = r.ru_utime + r.ru_stime
        self._ivcsw = r.ru_nivcsw
        self._steal0 = self._steal()

    def since(self) -> dict:
        r = resource.getrusage(resource.RUSAGE_SELF)
        return {"cpu_s": round(r.ru_utime + r.ru_stime - self._cpu, 3),
                "involuntary_switches": r.ru_nivcsw - self._ivcsw,
                "steal_s": round(self._steal() - self._steal0, 3)}


def _sample(ref: Reference, n_ids: int, q: int, rng) -> np.ndarray:
    """``q`` distinct ids to read back: the hottest, then touched ids
    drawn from the seed, then ids no event carries (from ``n_ids ..
    2 * n_ids - 1``, outside the traffic's ids)."""
    counts = np.bincount(ref.keys, minlength=n_ids)
    touched = np.nonzero(counts)[0]
    hot = touched[np.argsort(-counts[touched],
                             kind="stable")[:min(HOT_KEYS, q // 4)]]
    absent = n_ids + rng.choice(n_ids, min(ABSENT_KEYS, q // 4),
                                replace=False)
    rest = np.setdiff1d(touched, hot)
    n_rest = min(rest.size, q - hot.size - absent.size)
    picked = rng.choice(rest, n_rest, replace=False)
    return np.concatenate([hot, picked, absent]).astype(np.int32)


def compare(slates, ref: Reference, keys) -> Dict[str, list]:
    """The numbers ``correct`` compares for read-back ``slates`` =
    ``(present [Q], torn [Q], count [Q], lanes [Q, L])`` of ``keys``,
    each as ``[value, limit]``: every one is an exact count, so every
    limit is 0."""
    present, torn, count, vec = slates
    rc, rl = ref.final(keys)
    return {
        "presence_mismatch": [int((present != (rc > 0)).sum()
                                  + torn.sum()), 0],
        "count_mismatch": [int((count != rc).sum()), 0],
        "lane_mismatch": [int((vec != rl).any(axis=1).sum()), 0],
    }


def judge(checks: Dict[str, list]) -> bool:
    """``correct``: every number compared within its limit."""
    return all(v <= lim for v, lim in checks.values())


class GcPauses:
    """Garbage-collector pauses while armed: ``(start, seconds,
    generation)``."""

    def __init__(self):
        self.armed = False
        self.pauses: List[tuple] = []
        self._t = 0.0
        gc.callbacks.append(self._on)

    def _on(self, phase, info):
        if phase == "start":
            self._t = time.perf_counter()
        elif self.armed:
            self.pauses.append((self._t, time.perf_counter() - self._t,
                                info.get("generation")))

    def close(self):
        gc.callbacks.remove(self._on)


class CompileCounter:
    """Counts JAX compilations (traces and backend compiles) while
    armed."""

    def __init__(self):
        self.armed = False
        self.n = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **kw):
        if self.armed and ("backend_compile" in event
                           or "jaxpr_trace" in event):
            self.n += 1


# ---- one run --------------------------------------------------------
def run_cell(workload: str, seed: int, seconds: float, trace: bool,
             t_start: float, *, root: str = ROOT, overrides=None,
             devices=None, keep_trace: Optional[str] = None) -> dict:
    """Run ``workload`` once; returns the result line's object plus the
    numbers compared, under ``"checks"`` (``name -> [value, limit]``)."""
    spec = load_spec(root)
    cell, cfg, mix = resolve(spec, workload, root)
    if overrides:
        overrides(cfg, mix)
    devices = devices or jax.devices()[:cell["chips"]]
    scratch = os.path.join(root, ".bench_out", f"{workload}-{seed}")
    shutil.rmtree(scratch, ignore_errors=True)
    os.makedirs(scratch)
    compiles = CompileCounter()
    pauses = GcPauses()
    try:
        return _run(spec, cell, cfg, mix, devices, seed, seconds, trace,
                    t_start, os.path.join(scratch, "trace"), compiles,
                    pauses)
    finally:
        pauses.close()
        if keep_trace and os.path.isdir(os.path.join(scratch, "trace")):
            shutil.copytree(os.path.join(scratch, "trace"),
                            os.path.join(root, keep_trace),
                            dirs_exist_ok=True)
        shutil.rmtree(scratch, ignore_errors=True)


def _run(spec, cell, cfg, mix, devices, seed, seconds, trace, t_start,
         trace_dir, compiles, pauses):
    app_mod = importlib.import_module(f"bench.apps.{cfg['app']}")
    updaters = list(app_mod.UPDATERS)
    lanes = int(cfg["lanes"])
    seg = int(cfg["segment_ticks"])
    rngs = traffic.streams(seed)
    gen = traffic.Generator(mix, rngs, batch=int(cfg["events_per_tick"]))
    app = app_mod.build(cfg)
    app.start(app_mod.runtime(cfg))
    handle = RecordingHandle(app.engine, app.handle.state)
    app.handle = handle
    cursor = {"t": 0}
    recording: List[tuple] = []       # (keys, n) of traced ticks
    rec_on = {"on": False}

    def source(t, max_events):
        with jax.profiler.TraceAnnotation("bench.source"):
            keys, bits, n = gen.take(max_events)
            if rec_on["on"]:
                recording.append((keys, n))
            return app_mod.batch(keys, bits, n, t)

    def segment(n_ticks):
        with jax.profiler.TraceAnnotation("bench.app_run"):
            app.run(source, n_ticks, source_offset=cursor["t"])
        cursor["t"] += n_ticks

    # ---- set-up: the load phase, then warm every shape the window uses
    while gen.loading:
        segment(seg)
    for _ in range(int(cfg["warmup_segments"])):
        segment(seg)
    jax.block_until_ready(handle.state)

    # ---- the window ----
    if trace:
        jax.profiler.start_trace(trace_dir, profiler_options=profile_options())
    compiles.armed = pauses.armed = True
    host = HostClock()
    t0 = time.perf_counter()
    setup_s = t0 - t_start
    p0 = app_mod.processed(handle.state)
    traced_ticks = 0
    trace_open = trace
    rec_on["on"] = trace
    ann = jax.profiler.TraceAnnotation("bench.traced")
    if trace:
        ann.__enter__()
    while True:
        segment(seg)
        if trace_open:
            traced_ticks += seg
            if time.perf_counter() - t0 >= TRACE_S:
                jax.block_until_ready(handle.state)
                ann.__exit__(None, None, None)
                rec_on["on"] = False
                trace_open = False
                jax.profiler.stop_trace()
        if time.perf_counter() - t0 >= seconds:
            break
    window_ticks = cursor["t"]
    jax.block_until_ready(handle.state)
    t1 = time.perf_counter()
    compiles.armed = pauses.armed = False
    host_use = host.since()
    if trace_open:
        ann.__exit__(None, None, None)
        rec_on["on"] = False
        jax.profiler.stop_trace()
    p1 = app_mod.processed(handle.state)
    handle_times = list(handle.times)

    # ---- after the window: drain, then check against the reference ----
    app.run(source, 0, drain=True)
    jax.block_until_ready(handle.state)
    memory_peak = _peak_bytes(devices)
    final = app_mod.processed(handle.state)
    live = app_mod.live_slates(handle.state)
    delivered = gen.delivered()
    ref = Reference(delivered.keys, delivered.bits, lanes)
    exactness_guard(ref)
    lost = delivered.keys.size - min(final.values())
    st = app.stats()
    drops = (sum(st["queue_dropped"].values())
             + sum(st.get("table_dropped", {}).values()))
    # the sketch's rows restart at each telemetry window, the last of
    # which closed with the window's last segment: they hold the drain
    cm_gap, hist_gap = app_mod.telemetry_gaps(
        handle.state, sum(final.values()) - sum(p1.values()))
    checks = {"lost_events": [lost, 0], "drops": [drops, 0],
              "countmin_gap": [cm_gap, 0], "histogram_gap": [hist_gap, 0]}
    sample = _sample(ref, gen.zipf.ids.size, int(cfg["check_keys"]),
                     rngs["sample"])
    checks.update(compare(app_mod.read(handle, sample, lanes), ref, sample))
    app.close()
    d0 = devices[0]
    out = {
        "correct": judge(checks),
        "attempted": int(gen.n_delivered),
        "failed": int(lost + drops),
        "metrics": {
            "events_per_s": {
                "value": min(p1[u] - p0[u] for u in updaters) / (t1 - t0),
                "unit": "events/s"},
            "setup_s": {"value": setup_s, "unit": "s"}},
        "device": {"platform": d0.platform, "kind": d0.device_kind,
                   "count": len(devices),
                   "memory_peak_bytes": memory_peak},
        "tables": {"slots": int(cfg["table_capacity"]), "live": live},
        "window_s": t1 - t0,
        "window_ticks": window_ticks,
        "compiles_in_window": compiles.n,
        # where the host stalled: the longest gaps between republished
        # states in the window, the collector's longest pauses, and what
        # the host gave the process
        "republish_gaps": _longest_gaps(handle_times, t0, t1),
        "gc_pauses": [[round(a - t0, 3), round(d, 4), g] for a, d, g in
                      sorted(pauses.pauses, key=lambda p: -p[1])[:3]],
        "gc_pause_total_s": round(sum(p[1] for p in pauses.pauses), 4),
        "host": host_use,
        "checks": checks,
    }
    if trace:
        run = RunData(cell=cell, cfg=cfg, mix=mix,
                      device_kind=d0.device_kind, traced_ticks=traced_ticks)
        _reduce_trace(spec, run, trace_dir, recording, lanes, out)
    return out


def _reduce_trace(spec: dict, run: RunData, trace_dir: str, recording,
                  lanes: int, out: dict):
    """Per-layer metrics, busy time and the breakdown of the traced
    part of the window."""
    pd = trace_reduce.load(trace_reduce.find_xplane(trace_dir))
    spans = trace_reduce.host_spans(pd)
    marks = [s for s in spans if s.name == "bench.traced"]
    if not marks:
        raise RuntimeError("the trace holds no bench.traced span")
    run.trace_lo, run.trace_hi = marks[0].start, marks[0].end
    run.ops = {d: o.clip(run.trace_lo, run.trace_hi)
               for d, o in trace_reduce.device_ops(pd).items()}
    if not run.ops:
        raise RuntimeError("the trace holds no TPU device plane")
    # every updater applies each tick's events once (U1: one lane)
    run.slate_update_bytes = work.traced_slate_update_bytes(recording,
                                                            (1, lanes))
    run.peaks = peaks_mod.peaks(run.device_kind)

    busy = [run.busy_s(d) for d in sorted(run.ops)]
    out["device"]["busy_s"] = float(np.mean(busy))
    out["device"]["window_s"] = run.traced_s
    vals = {}
    for m in per_layer(spec, run.cell["name"]):
        v = metric_reader(m["name"])(run)
        if v is not None:
            vals[m["name"]] = {"value": float(v), "unit": m["unit"]}
    out["metrics"] = vals
    # breakdown: top device operations, and idle time by host activity
    main = [s for s in spans if s.name.startswith("bench.")]
    # the driving thread: the line of the benchmark's own spans and the
    # runtime's C++ spans of the process's main thread
    lines = {s.thread for s in spans if s.name == "bench.app_run"}
    on_main = [s for s in spans
               if s.thread in lines or s.thread.startswith("main")]
    idle: Dict[str, float] = {}
    for d in sorted(run.ops):
        gaps = trace_reduce.gaps(run.ops[d], run.trace_lo, run.trace_hi)
        for lab, sec in trace_reduce.idle_by_host(gaps,
                                                  on_main or main).items():
            idle[lab] = idle.get(lab, 0.0) + sec / len(run.ops)
    out["breakdown"] = {
        "device_ops": [[n, s] for n, s in trace_reduce.top_ops(run.ops)],
        "idle_gaps": [[n, s] for n, s in sorted(
            idle.items(), key=lambda kv: -kv[1])[:10]]}


def per_layer(spec: dict, workload: str) -> List[dict]:
    """The per-layer metrics ``BENCHMARK.json`` lists for a cell."""
    return [m for m in spec["per_layer"]
            if workload in m.get("workloads", [workload])]
