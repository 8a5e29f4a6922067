"""``bench/scopes.py`` and the twelve metrics that read it: attribution
on hand-made operations, and the reduction of a trace recorded on a TPU
v5e with the engine's scopes and spans (``bench/testdata/
scoped.xplane.pb.gz``, written by ``bench/tests/record_trace.py``: the
counting app at 2^16 slots and 1024 events a tick, 16 traced ticks)."""
import gzip
import os
import shutil
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path[:0] = [ROOT]

from bench import harness, scopes  # noqa: E402
from bench import trace_reduce as tr  # noqa: E402

DATA = os.path.join(ROOT, "bench", "testdata")
SCOPED = os.path.join(DATA, "scoped.xplane.pb.gz")
UNSCOPED = os.path.join(DATA, "small.xplane.pb.gz")   # engine before scopes
TICKS = 16
CELL = "counting.flood"
DEVICE = ["tick_queues", "tick_telemetry", "tick_map", "tick_sort",
          "tick_probe", "tick_pack", "tick_write", "tick_unscoped"]
IDLE = ["idle_source", "idle_dispatch", "idle_sync", "idle_outside"]


def stack(*parts):
    return "jit(_chunk_impl)/while/body/closed_call/" + "/".join(parts)


def test_innermost_operation_and_kernels_take_the_time():
    # a loop encloses a probe gather, a kernel and an op with no name
    # stack; a copy overlaps the kernel
    ops = [("while.1", 0, 100, ""),
           ("fusion.2", 10, 30, stack("apply.probe", "gather:")),
           ("slate_update.3", 40, 60, stack("jit(slate_update)",
                                             "pallas_call:")),
           ("copy.4", 50, 70, ""),
           ("fusion.5", 70, 80, stack("apply.pack", "tick.map", "x:"))]
    got = scopes.device_time([o[1] for o in ops], [o[2] for o in ops],
                             [o[0] for o in ops], [o[3] for o in ops])
    assert got == pytest.approx({
        "apply.probe": 20e-9, "kernel": 20e-9, "tick.map": 10e-9,
        # the loop's own time, and the copy's after the kernel ended
        "unscoped": 40e-9 + 10e-9})
    o = tr.Ops([x[0] for x in ops], np.asarray([x[1] for x in ops], float),
               np.asarray([x[2] for x in ops], float))
    assert sum(got.values()) == pytest.approx(tr.busy(o, 0, 100))


def test_an_operation_with_no_name_stack_is_unscoped():
    assert scopes.scope_of("") == "unscoped"
    assert scopes.scope_of("jit(broadcast_in_dim)/broadcast_in_dim:") \
        == "unscoped"
    assert scopes.scope_of(stack("apply.sort", "jit(argsort)", "sort:")) \
        == "apply.sort"
    got = scopes.device_time([0, 5], [4, 9], ["copy.1", "fusion.2"],
                             ["", stack("tick.queues", "scatter:")])
    assert got == pytest.approx({"unscoped": 4e-9, "tick.queues": 4e-9})


def test_idle_goes_to_the_innermost_loop_span():
    spans = [tr.HostSpan("t", "bench.app_run", 0, 1000),
             tr.HostSpan("t", "source_build", 100, 200),
             tr.HostSpan("t", "bench.source", 120, 180),
             tr.HostSpan("t", "stack_sources", 200, 250),
             tr.HostSpan("t", "chunk_dispatch", 250, 300),
             tr.HostSpan("t", "chunk_sync", 300, 600),
             tr.HostSpan("t", "observe_begin", 600, 650)]
    got = scopes.idle_time([(110, 190), (240, 260), (400, 402),
                            (610, 640), (900, 950)], spans)
    assert got == pytest.approx({"source": 80e-9, "dispatch": 20e-9,
                                 "sync": 2e-9, "outside": 80e-9})


# ---- the recorded trace ------------------------------------------------
def _run(path, tmp_path, shift=0.0):
    """RunData as the harness builds it, with the trace where the
    harness keeps it while the readers run."""
    dest = tmp_path / f"{CELL}-1" / "trace" / "plugins" / "profile" / "t"
    dest.mkdir(parents=True)
    with gzip.open(path, "rb") as f, open(dest / "h.xplane.pb", "wb") as g:
        shutil.copyfileobj(f, g)
    pd = tr.load(path)
    mark = [s for s in tr.host_spans(pd) if s.name == scopes.TRACED][0]
    run = harness.RunData(cell={"name": CELL}, cfg={}, mix={},
                          device_kind="TPU v5 lite", traced_ticks=TICKS,
                          trace_lo=mark.start, trace_hi=mark.end + shift)
    run.ops = {d: o.clip(run.trace_lo, run.trace_hi)
               for d, o in tr.device_ops(pd).items()}
    return run


@pytest.fixture
def out_dir(tmp_path, monkeypatch):
    monkeypatch.setattr(scopes, "OUT", str(tmp_path))
    return tmp_path


def _read(run, names):
    return {n: harness.metric_reader(f"{n}_ms_per_tick")(run) for n in names}


def test_decoding_matches_profile_data():
    space = scopes.load(SCOPED)
    pd = tr.load(SCOPED)
    ops = tr.device_ops(pd)
    assert sorted(space.devices) == sorted(ops)
    for d, o in ops.items():
        mine = space.devices[d]
        assert mine.names == o.names
        assert np.array_equal(mine.start, o.start)
        assert np.array_equal(mine.end, o.end)
    assert [(s.name, s.start, s.end) for s in space.spans] == \
        [(s.name, s.start, s.end) for s in tr.host_spans(pd)]


def test_device_scopes_partition_tick_other(out_dir):
    run = _run(SCOPED, out_dir)
    got = _read(run, DEVICE)
    assert all(v is not None for v in got.values()), got
    for n in DEVICE[:-1]:
        assert got[n] > 0, n
    other = harness.metric_reader("tick_other_ms_per_tick")(run)
    assert sum(got.values()) == pytest.approx(other, rel=1e-9)
    # the probes and the pack run on every tick's whole batch
    assert got["tick_probe"] > got["tick_map"]


def test_idle_spans_partition_the_idle_time(out_dir):
    run = _run(SCOPED, out_dir)
    got = _read(run, IDLE)
    assert all(v is not None for v in got.values()), got
    share = harness.metric_reader("idle_share.flood")(run)
    idle_ms = share / 100 * run.traced_s * 1e3 / TICKS
    assert sum(got.values()) == pytest.approx(idle_ms, rel=1e-9)
    assert got["idle_source"] > 0 and got["idle_dispatch"] > 0


def test_a_trace_of_another_span_is_refused(out_dir):
    run = _run(SCOPED, out_dir, shift=1.0)
    assert _read(run, DEVICE + IDLE) == {n: None for n in DEVICE + IDLE}


def test_an_engine_without_scopes_gives_no_reading(out_dir):
    run = _run(UNSCOPED, out_dir)
    assert harness.metric_reader("tick_other_ms_per_tick")(run) > 0
    assert _read(run, DEVICE + IDLE) == {n: None for n in DEVICE + IDLE}
