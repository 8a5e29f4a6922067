"""``bench/trace_reduce.py``: interval arithmetic on hand-made operations,
and the reduction of a small trace recorded on a TPU v5e
(``bench/testdata/small.xplane.pb.gz``, written by
``bench/tests/record_trace.py``: the counting app at 2^16 slots and 1024
events a tick, 16 traced ticks)."""
import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path[:0] = [ROOT]

from bench import trace_reduce as tr  # noqa: E402

SMALL = os.path.join(ROOT, "bench", "testdata", "small.xplane.pb.gz")


def ops(*spans):
    texts = [f"%{s[0]} = f32[8]{{0:T(128)}} {s[0].split('.')[0]}(x)"
             for s in spans]
    return tr.Ops([tr.short(t) for t in texts],
                  np.asarray([s[1] for s in spans], float),
                  np.asarray([s[2] for s in spans], float), texts)


def test_busy_union_gaps_and_time_by_name():
    o = ops(("slate_update.1", 10, 30), ("fusion.2", 20, 45),
            ("slate_update.3", 60, 70), ("copy.4", 65, 68),
            ("while.5", 5, 75))
    assert tr.busy(o, 0, 100) == pytest.approx(70e-9)
    assert tr.gaps(o, 0, 100) == [(0, 5), (75, 100)]
    inner = tr.Ops(o.names[:4], o.start[:4], o.end[:4], o.texts[:4])
    assert tr.gaps(inner, 0, 100) == [(0, 10), (45, 60), (70, 100)]
    assert tr.busy(inner, 25, 65) == pytest.approx(25e-9)
    assert o.time(r"^slate_update") == pytest.approx(30e-9)
    assert o.time(r"^nothing") == 0.0
    # the loop that encloses the others is no operation of its own
    top = tr.top_ops({0: o, 1: o})
    assert top[0] == ("fusion.2 = f32[8] fusion", pytest.approx(50e-9))
    assert all(not name.startswith("while") for name, _ in top)


def test_idle_time_goes_to_the_shortest_covering_host_span():
    spans = [tr.HostSpan("t", "bench.app_run", 0, 100_000),
             tr.HostSpan("t", "bench.source", 40_000, 60_000)]
    idle = tr.idle_by_host([(45_000, 55_000), (70_000, 90_000),
                            (200_000, 300_000), (1_000, 2_000)], spans)
    assert idle == pytest.approx({
        "bench.source": 10e-6, "bench.app_run": 20e-6,
        "(no host span)": 100e-6, "(between ops, under 10 us)": 1e-6})


@pytest.fixture(scope="module")
def small():
    if not os.path.exists(SMALL):
        pytest.fail(f"missing recorded trace {SMALL}")
    return tr.load(SMALL)


def test_recorded_trace_reduces(small):
    per_dev = tr.device_ops(small)
    assert list(per_dev) == [0]
    o = per_dev[0]
    mark = [s for s in tr.host_spans(small) if s.name == "bench.traced"]
    assert len(mark) == 1
    lo, hi = mark[0].start, mark[0].end
    busy = tr.busy(o, lo, hi)
    idle = sum(b - a for a, b in tr.gaps(o, lo, hi)) * 1e-9
    assert 0 < busy < (hi - lo) * 1e-9
    assert busy + idle == pytest.approx((hi - lo) * 1e-9, rel=1e-9)
    # the tick's Pallas kernels, two updaters x 16 ticks each
    for k in ("slate_update", "countmin_update", "histogram_update"):
        n = sum(bool(__import__("re").match(k, x)) for x in o.names)
        assert n >= 32, (k, n)
        assert o.time("^" + k) > 0
    assert o.time("^slate_update") < busy
