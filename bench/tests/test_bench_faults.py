"""Whole runs of the harness on the CPU at a tiny size, with the chip
check skipped: a sound program comes out correct, and each fault the
cell can have, planted in the program underneath the timed path, comes
out not correct.  So does the control: the reference in the program's
place, summed in bfloat16, judged by the harness's own comparison."""
import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

from bench import harness  # noqa: E402
from bench.control import control  # noqa: E402
from bench.reference import Reference  # noqa: E402

SEED = 2**31 + 11


def tiny(cfg, mix):
    """The cell's shapes cut to what a test run holds."""
    cfg["table_capacity"] = 4096
    cfg["events_per_tick"] = 128
    cfg["runtime"]["batch_size"] = 128
    cfg["segment_ticks"] = 8
    cfg["check_keys"] = 256
    mix["keys"]["ids"] = 1000


def run(workload="counting.flood", seconds=0.5):
    return harness.run_cell(workload, SEED, seconds, False,
                            time.perf_counter(), overrides=tiny,
                            devices=jax.devices()[:1])


def test_sound_flood_run_is_correct():
    out = run()
    assert out["correct"], out["checks"]
    assert out["metrics"]["events_per_s"]["value"] > 0
    assert out["compiles_in_window"] == 0
    assert out["attempted"] > 1000 and out["failed"] == 0
    # the load phase gave every key its slate before the window
    assert out["tables"]["live"] == {"U1": 1000, "UV": 1000}


def _state_unchanged(monkeypatch):
    from repro.core.engine import Engine
    orig = Engine._chunk_impl

    def frozen(self, state, stacked, ingest, **kw):
        _, outs, info = orig(self, state, stacked, ingest, **kw)
        return state, outs, info
    monkeypatch.setattr(Engine, "_chunk_impl", frozen)


def _half_batch(monkeypatch):
    from repro.core import apply as apply_mod
    orig = apply_mod.apply_associative

    def half(op, table, batch, tick, **kw):
        keep = jnp.arange(batch.capacity) % 2 == 0
        return orig(op, table, batch.mask(batch.valid & keep), tick, **kw)
    monkeypatch.setattr(apply_mod, "apply_associative", half)


def _answer_altered(monkeypatch):
    from repro.core.engine import Engine
    orig = Engine.read_slates

    def altered(self, state, updater, keys, **kw):
        rows = orig(self, state, updater, keys, **kw)
        for r in rows:
            if r is not None:
                for k in r:
                    r[k] = r[k] + 1
                break
        return rows
    monkeypatch.setattr(Engine, "read_slates", altered)


@pytest.mark.parametrize("fault", [_state_unchanged, _half_batch,
                                   _answer_altered],
                         ids=["state_unchanged", "half_batch",
                              "answer_altered"])
def test_fault_is_not_correct(monkeypatch, fault):
    fault(monkeypatch)
    out = run()
    assert not out["correct"]


def test_control_in_bfloat16_is_not_correct():
    # hottest key ~13% of ~10k events: counts far past bfloat16's 256
    out = control("counting.flood", SEED, rate=10000.0, seconds=1.0,
                  overrides=tiny)
    assert not out["correct"]
    assert out["checks"]["count_mismatch"][0] > 0
    assert out["checks"]["lane_mismatch"][0] > 0


def test_the_reference_in_the_controls_place_is_correct():
    rng = np.random.default_rng(0)
    keys = np.concatenate([np.full(600, 7, np.int32),
                           rng.integers(0, 300, 3000).astype(np.int32)])
    bits = rng.integers(0, 256, keys.size).astype(np.uint8)
    ref = Reference(keys, bits, 8)
    q = np.arange(400, dtype=np.int32)
    rc, rl = ref.final(q)
    checks = harness.compare((rc > 0, np.zeros(q.size, bool), rc, rl),
                             ref, q)
    assert harness.judge(checks)
    # one key a slate short, or present in one updater's table only
    torn = np.zeros(q.size, bool)
    torn[3] = True
    assert not harness.judge(harness.compare((rc > 0, torn, rc, rl), ref, q))
    rc2 = rc.copy()
    rc2[7] -= 1
    assert not harness.judge(harness.compare((rc > 0, torn & False, rc2,
                                              rl), ref, q))


def test_read_back_sample_holds_the_hottest_and_absent_keys():
    rng = np.random.default_rng(5)
    keys = np.concatenate([np.full(50, 3), rng.integers(0, 500, 2000)])
    ref = Reference(keys.astype(np.int32), np.zeros(keys.size, np.uint8), 8)
    q = harness._sample(ref, 1000, 256, np.random.default_rng(1))
    assert q.size == 256 and np.unique(q).size == 256
    counts = np.bincount(ref.keys, minlength=2000)
    assert q[0] == 3 and counts[q[0]] == counts.max()
    assert (q >= 1000).sum() == 64 and (counts[q] == 0).sum() == 64
