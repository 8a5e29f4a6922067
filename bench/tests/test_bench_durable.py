"""The durable counting cell on the CPU at the tiny size of
``test_bench_faults.py``: a sound run is correct and its crash check
replays the log, each fault the durable path can have comes out not
correct, and set-up stops at its deadline.  Then the readers of the
durable path's spans, on a trace recorded on a TPU v5e
(``bench/testdata/durable.xplane.pb.gz``, written by
``bench/tests/record_durable_trace.py``: the counting app at 2^16 slots
and 1024 events a tick with a durability directory, 32 traced ticks, a
flush every 16)."""
import glob
import gzip
import os
import shutil
import subprocess
import sys
import time

import jax
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

from bench import durable, harness, scopes  # noqa: E402
from bench import trace_reduce as tr  # noqa: E402
from bench.apps import counting_durable  # noqa: E402
from bench.tests.test_bench_faults import SEED, tiny  # noqa: E402

CELL = "counting-durable.flood"
DATA = os.path.join(ROOT, "bench", "testdata")
DURABLE = os.path.join(DATA, "durable.xplane.pb.gz")
SCOPED = os.path.join(DATA, "scoped.xplane.pb.gz")   # no durability
TICKS = 32


def run(overrides=tiny):
    # one window segment: load (8 ticks), warm-up (8), window (8); the
    # crash check's tail leaves one chunk (8 ticks) past the frontier
    return harness.run_cell(CELL, SEED, 0.0, False, time.perf_counter(),
                            overrides=overrides, devices=jax.devices()[:1])


def test_sound_durable_run_is_correct_and_replays_the_log():
    out = run()
    assert out["correct"], out["checks"]
    assert out["compiles_in_window"] == 0
    rec = counting_durable._run.recovery
    assert rec["restored"] == {"U1": 1000, "UV": 1000}
    assert rec["unflushed"] >= 8 and rec["tail_ticks"] >= 8
    assert rec["replayed_ticks"] >= rec["unflushed"]


def _store_drops_rows(monkeypatch):
    from repro.slates.kvstore import KVStore
    monkeypatch.setattr(KVStore, "put_many", lambda self, *a, **k: 0)


def _wal_skips_a_tick(monkeypatch):
    from repro.slates.wal import WriteAheadLog
    orig = WriteAheadLog.append

    def skip(self, tick, sources):
        # one tick in every 8 consecutive ones: the log's suffix has 8
        return self.offset if tick % 8 == 5 else orig(self, tick, sources)
    monkeypatch.setattr(WriteAheadLog, "append", skip)


def _replay_skipped(monkeypatch):
    from repro.slates.wal import WriteAheadLog
    monkeypatch.setattr(WriteAheadLog, "replay",
                        lambda self, *a, **k: iter(()))


@pytest.mark.parametrize("fault", [_store_drops_rows, _wal_skips_a_tick,
                                   _replay_skipped],
                         ids=["store_drops_rows", "wal_skips_a_tick",
                              "replay_skipped"])
def test_durable_fault_is_not_correct(monkeypatch, fault):
    fault(monkeypatch)
    out = run()
    assert not out["correct"]
    assert out["checks"]["count_mismatch"][0] > 0


def test_setup_stops_at_its_deadline():
    # the deadline ends the process, so the run goes to a child
    code = (f"import sys, time; sys.path[:0] = {[ROOT, os.path.join(ROOT, 'src')]!r}\n"
            "import jax\n"
            "from bench import harness\n"
            "from bench.tests.test_bench_faults import SEED, tiny\n"
            "def short(cfg, mix):\n"
            "    tiny(cfg, mix)\n"
            "    cfg['setup_deadline_s'] = 0.05\n"
            f"harness.run_cell({CELL!r}, SEED, 0.0, False, time.perf_counter(),"
            " overrides=short, devices=jax.devices()[:1])\n"
            "print('ran past the deadline')\n")
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.Popen([sys.executable, "-c", code], cwd=ROOT, env=env,
                         stdout=subprocess.PIPE, stderr=subprocess.PIPE,
                         text=True)
    out, err = p.communicate(timeout=300)
    # the exit skips the harness's clean-up of its scratch directory
    shutil.rmtree(os.path.join(ROOT, ".bench_out", f"{CELL}-{SEED}"),
                  ignore_errors=True)
    assert p.returncode == 1, err[-2000:]
    assert "set-up passed 0.05 s" in err
    assert "ran past the deadline" not in out
    assert not glob.glob(os.path.join(ROOT, ".bench_out",
                                      f"durable-{p.pid}-*"))


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
    return run, tr.host_spans(pd)


@pytest.fixture
def out_dir(tmp_path, monkeypatch):
    monkeypatch.setattr(scopes, "OUT", str(tmp_path))
    return tmp_path


def _read(run, name):
    return harness.metric_reader(name)(run)


def test_flush_idle_is_part_of_the_idle_outside_the_chunks(out_dir):
    run, _ = _run(DURABLE, out_dir)
    flush = _read(run, "idle_flush_ms_per_tick")
    outside = _read(run, "idle_outside_ms_per_tick")
    assert flush is not None and outside is not None
    assert 0 < flush <= outside


@pytest.mark.parametrize("name", ["store_write", "wal_append"])
def test_span_metrics_sum_the_clipped_spans(out_dir, name):
    run, spans = _run(DURABLE, out_dir)
    mine = [s for s in spans if s.name == name]
    assert mine
    ns = sum(max(0.0, min(s.end, run.trace_hi) - max(s.start, run.trace_lo))
             for s in mine)
    got = _read(run, f"{name}_ms_per_tick")
    assert got == pytest.approx(ns * 1e-6 / TICKS, rel=1e-12)
    assert got > 0


def test_span_readers_find_one_trace_once(out_dir):
    run, _ = _run(DURABLE, out_dir)
    assert durable.space(run) is durable.space(run)


@pytest.mark.parametrize("shift", [1.0, None], ids=["another_span",
                                                   "no_durability"])
def test_readers_give_no_reading(out_dir, shift):
    run, _ = (_run(DURABLE, out_dir, shift=shift) if shift
              else _run(SCOPED, out_dir))
    for name in ("idle_flush_ms_per_tick", "store_write_ms_per_tick",
                 "wal_append_ms_per_tick"):
        assert _read(run, name) is None, name
    assert np.isfinite(_read(run, "idle_share.flood") or 0.0)
