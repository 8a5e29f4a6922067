"""Crash-recovery integration tests (DESIGN.md section 10).

The headline property: a durable run that crashes (in-memory state
discarded), recovers from the KV store + WAL, and runs to completion
produces slates **bitwise equal** to an uninterrupted run of the same
durable configuration — exactly-once-by-merge for associative updaters.
Sequential updaters under ``barrier=False`` get the documented
at-least-once semantics instead.
"""
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.durability import DurabilityConfig, WALAppendError
from repro.core.engine import Engine, EngineConfig
from repro.core.event import EventBatch
from repro.core.operators import AssociativeUpdater
from repro.core.workflow import Workflow
from repro.slates.flush import (FlushConfig, FlushError, FlushFrontier,
                                FlushPolicy, Flusher, restore_into)
from repro.slates import table as tbl
from repro.slates.wal import WriteAheadLog
from tests.conftest import (LastValueUpdater, PassThroughMapper, VSPEC,
                            make_batch)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


class SumCounter(AssociativeUpdater):
    """Counter eligible for the fused slate-update path."""
    name = "U1"
    subscribes = ("S2",)
    in_value_spec = VSPEC
    out_streams = {}
    table_capacity = 512
    sum_mergeable = True

    def slate_spec(self):
        return {"count": ((), jnp.int32), "sum": ((), jnp.float32)}

    def lift(self, batch):
        return {"count": jnp.ones_like(batch.key),
                "sum": batch.value["x"].astype(jnp.float32)}

    def combine(self, a, b):
        return {"count": a["count"] + b["count"], "sum": a["sum"] + b["sum"]}

    def merge(self, s, d):
        return {"count": s["count"] + d["count"], "sum": s["sum"] + d["sum"]}


def counting_source(t, ingest=None, n_keys=40, n=24):
    rng = np.random.default_rng(1000 + t)
    keys = rng.integers(0, n_keys, size=n).astype(np.int32)
    xs = rng.integers(0, 9, size=n).astype(np.int32)
    return {"S1": make_batch(keys, xs, ts=[t] * n)}


def table_dict(state, name):
    """{key: {leaf: np value}} for every occupied slot — slot-order
    independent (recovery re-inserts keys in a different order)."""
    t = state["tables"][name]
    keys = np.asarray(jax.device_get(t.keys))
    vals = jax.tree.map(lambda v: np.asarray(jax.device_get(v)), t.vals)
    out = {}
    for i, k in enumerate(keys):
        if k != -1:
            out[int(k)] = jax.tree.map(lambda v: v[i], vals)
    return out


def assert_tables_bitwise_equal(a, b):
    assert set(a) == set(b), (sorted(a), sorted(b))
    for k in a:
        la, lb = jax.tree.leaves(a[k]), jax.tree.leaves(b[k])
        for x, y in zip(la, lb):
            assert np.asarray(x).tobytes() == np.asarray(y).tobytes(), \
                (k, x, y)


def _counting_engine(d, fused, **dur_kw):
    wf = Workflow([PassThroughMapper(), SumCounter()],
                  external_streams=("S1",))
    dur_kw.setdefault("flush", FlushConfig(policy=FlushPolicy.EVERY_K,
                                           every_k=8))
    cfg = EngineConfig(batch_size=32, queue_capacity=128, chunk_size=4,
                       fused=fused,
                       durability=DurabilityConfig(dir=d, **dur_kw))
    return Engine(wf, cfg)


# ---------------------------------------------------------------------------
# the archetype headline: crash at tick k, recover, bitwise parity
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("fused", ["jnp", "interpret"])
def test_crash_recover_bitwise_parity(tmp_path, fused):
    n_total, n_crash = 24, 12
    # uninterrupted durable run
    ea = _counting_engine(str(tmp_path / "a"), fused)
    sa, _ = ea.run(ea.init_state(), counting_source, n_total)
    base = table_dict(sa, "U1")
    base_tick = int(jax.device_get(sa["tick"]))
    ea.close()

    # durable run crashed at source tick k: every in-memory buffer dropped
    eb = _counting_engine(str(tmp_path / "b"), fused)
    sb, _ = eb.run(eb.init_state(), counting_source, n_crash)
    assert eb.dur.frontier.tick > 0          # a flush boundary happened
    del sb                                    # the crash
    eb.close()

    # recover on a fresh engine (new process in real life)
    eb2 = _counting_engine(str(tmp_path / "b"), fused)
    s2 = eb2.recover()
    s2, _ = eb2.run(s2, counting_source, n_total - n_crash,
                    source_offset=n_crash)
    rec = table_dict(s2, "U1")
    rec_tick = int(jax.device_get(s2["tick"]))
    eb2.close()

    assert base_tick == rec_tick             # drain ticks replay too
    assert_tables_bitwise_equal(base, rec)


def test_halt_stops_every_thread_and_leaves_a_recoverable_directory(
        tmp_path):
    """``halt`` is a crash with the process still up: the log writer,
    flusher and merge threads stop without a flush, and a fresh engine
    recovers the halted one's slates from the directory while it stays
    open; its ``close`` afterwards writes nothing."""
    d = str(tmp_path / "h")
    ea = _counting_engine(d, "jnp")
    sa, _ = ea.run(ea.init_state(), counting_source, 14)
    assert 0 < ea.dur.frontier.tick < int(jax.device_get(sa["tick"]))
    base = table_dict(sa, "U1")
    ea.dur.halt()
    fl = ea.dur.flusher
    assert not any(t.is_alive() for t in (ea.dur._wthread, fl._thread,
                                          fl._merger))
    files = sorted(os.path.relpath(os.path.join(p, f), d)
                   for p, _, fs in os.walk(d) for f in fs)

    eb = _counting_engine(d, "jnp")
    sb = eb.recover()
    assert eb.last_recovery["replayed_ticks"] > 0
    assert_tables_bitwise_equal(base, table_dict(sb, "U1"))
    eb.close()
    ea.close()
    assert sorted(os.path.relpath(os.path.join(p, f), d)
                  for p, _, fs in os.walk(d) for f in fs) == files


def test_crash_between_store_write_and_frontier_save_is_exactly_once(
        tmp_path, monkeypatch):
    """A crash after a flush's blocks reached the store and before its
    frontier was saved: the rows of that flush must not be restored on
    top of a replay that applies their events again."""
    n_total = 24
    ea = _counting_engine(str(tmp_path / "a"), "jnp")
    sa, _ = ea.run(ea.init_state(), counting_source, n_total)
    base = table_dict(sa, "U1")
    ea.close()

    d = str(tmp_path / "b")
    eb = _counting_engine(d, "jnp")
    saves = []
    orig = FlushFrontier.save

    def save(self, path):
        saves.append(self.tick)
        if len(saves) == 2:              # the second flush: blocks written
            raise KeyboardInterrupt("crash before the frontier is saved")
        orig(self, path)
    monkeypatch.setattr(FlushFrontier, "save", save)
    sb, _ = eb.run(eb.init_state(), counting_source, 8)
    with pytest.raises(KeyboardInterrupt):
        # the flush at source tick 16 fences the log, writes its blocks
        # and crashes before its frontier is saved
        eb.run(sb, counting_source, 8, source_offset=8)
    eb.dur.halt()
    monkeypatch.setattr(FlushFrontier, "save", orig)
    assert eb.dur.store.last_seq > FlushFrontier.load(
        os.path.join(d, "FRONTIER.json")).store_seq

    ec = _counting_engine(d, "jnp")
    sc = ec.recover()
    sc, _ = ec.run(sc, counting_source, n_total - 16, source_offset=16)
    assert_tables_bitwise_equal(base, table_dict(sc, "U1"))
    ec.close()
    eb.close()


def test_recover_uses_store_not_only_wal(tmp_path):
    """After WAL truncation at the frontier, pre-frontier events exist
    only as flushed slates — recovery must come from the store."""
    d = str(tmp_path / "t")
    ea = _counting_engine(d, "jnp", truncate_wal=True)
    sa, _ = ea.run(ea.init_state(), counting_source, 16)
    base = table_dict(sa, "U1")
    frontier = ea.dur.frontier
    assert frontier.tick > 0
    # log was compacted: nothing before the frontier survives
    first = next(iter(ea.dur.wal.replay()), None)
    if first is not None:
        assert first[0] >= frontier.tick
    ea.close()

    eb = _counting_engine(d, "jnp", truncate_wal=True)
    rec = table_dict(eb.recover(), "U1")
    eb.close()
    assert_tables_bitwise_equal(base, rec)


# ---------------------------------------------------------------------------
# sequential updaters: documented at-least-once under barrier=False
# ---------------------------------------------------------------------------

def _seq_source(t, ingest=None):
    rng = np.random.default_rng(7 + t)
    keys = rng.integers(0, 6, size=8).astype(np.int32)
    xs = rng.integers(0, 100, size=8).astype(np.int32)
    return {"S1": make_batch(keys, xs, ts=[t] * 8)}


def _seq_engine(d=None):
    wf = Workflow([PassThroughMapper(), LastValueUpdater()],
                  external_streams=("S1",))
    dur = None if d is None else DurabilityConfig(
        dir=d, barrier=False,
        flush=FlushConfig(policy=FlushPolicy.EVERY_K, every_k=4))
    return Engine(wf, EngineConfig(batch_size=16, queue_capacity=64,
                                   chunk_size=2, durability=dur))


def test_sequential_at_least_once(tmp_path):
    """barrier=False backdates the frontier by replay_slack: replay
    re-applies events already in the snapshot.  Nothing is lost (n >=
    baseline, some keys over-counted), and order-dependent state
    converges (`last` exact) — DESIGN.md 10.3."""
    e0 = _seq_engine()
    s0, _ = e0.run(e0.init_state(), _seq_source, 16)
    base = table_dict(s0, "U2")

    d = str(tmp_path / "seq")
    eb = _seq_engine(d)
    sb, _ = eb.run(eb.init_state(), _seq_source, 10)
    del sb
    eb.close()

    e2 = _seq_engine(d)
    s2 = e2.recover()
    s2, _ = e2.run(s2, _seq_source, 6, source_offset=10)
    rec = table_dict(s2, "U2")
    e2.close()

    assert set(rec) == set(base)
    duplicated = 0
    for k in base:
        assert int(rec[k]["last"]) == int(base[k]["last"])   # converges
        assert int(rec[k]["n"]) >= int(base[k]["n"])         # no loss
        duplicated += int(rec[k]["n"]) - int(base[k]["n"])
    assert duplicated > 0    # replay really re-applied in-flight events


# ---------------------------------------------------------------------------
# async WAL writer (DESIGN.md section 17): torn tails, surfaced errors,
# and the barrier=False frontier under deferred appends
# ---------------------------------------------------------------------------

def test_crash_during_async_append_trims_torn_tail(tmp_path):
    """Kill the writer mid-frame: the reopened WAL trims the torn tail
    to the last whole record, and resuming from the surviving prefix
    replays to bitwise parity with an uninterrupted run."""
    n_total = 24
    ea = _counting_engine(str(tmp_path / "a"), "jnp")
    sa, _ = ea.run(ea.init_state(), counting_source, n_total)
    base = table_dict(sa, "U1")
    ea.close()

    eb = _counting_engine(str(tmp_path / "b"), "jnp")
    sb, _ = eb.run(eb.init_state(), counting_source, 12)
    n_recs = len(list(eb.dur.wal.replay()))
    assert n_recs == 12                  # every source tick made it out
    assert eb.dur.frontier.tick > 0
    del sb                               # the crash
    eb.close()

    # simulate the writer thread dying mid-append: the tail frame is
    # half-written (cut inside the last record's payload)
    wal_path = os.path.join(str(tmp_path / "b"), "wal.log")
    with open(wal_path, "r+b") as f:
        f.truncate(os.path.getsize(wal_path) - 7)

    eb2 = _counting_engine(str(tmp_path / "b"), "jnp")
    recs = list(eb2.dur.wal.replay())
    assert len(recs) == n_recs - 1       # torn frame dropped, no garbage
    # records are FIFO per source tick (drain ticks append nothing), so
    # the surviving count IS the number of source ticks fully on disk
    m = len(recs)
    s2 = eb2.recover()
    s2, _ = eb2.run(s2, counting_source, n_total - m, source_offset=m)
    rec = table_dict(s2, "U1")
    eb2.close()
    assert_tables_bitwise_equal(base, rec)


def test_async_append_error_surfaces_at_fence(tmp_path):
    """A failed background append must fail the run at the next epoch
    fence — before any frontier advance could certify the lost tick."""
    eng = _counting_engine(str(tmp_path / "e"), "jnp")

    def broken(tick, sources):
        raise IOError("disk gone")

    eng.dur.wals[0].append = broken
    with pytest.raises(WALAppendError, match="disk gone"):
        eng.run(eng.init_state(), counting_source, 12)
    assert eng.dur.frontier.tick == 0    # never advanced past the loss
    eng.close()


def test_sequential_frontier_covers_async_tail(tmp_path):
    """barrier=False with the async writer: the backdated frontier must
    still point at-or-before every tick whose append was in flight, so
    replay-from-frontier re-covers the whole unflushed suffix
    (at-least-once, never at-most-once)."""
    d = str(tmp_path / "seqf")
    eng = _seq_engine(d)
    s, _ = eng.run(eng.init_state(), _seq_source, 12)
    frontier = eng.dur.frontier
    assert frontier.tick > 0
    all_ticks = [t for t, _ in eng.dur.wal.replay()]
    ticks = [t for t, _ in eng.dur.wal.replay(
        from_offset=frontier.wal_offset)]
    eng.close()
    # backdated frontier: replay starts at-or-before the frontier tick
    assert ticks and min(ticks) <= frontier.tick
    # ...and the suffix is the exact unbroken tail of the log: nothing
    # appended after the frontier offset was lost while queue-resident
    assert ticks == all_ticks[len(all_ticks) - len(ticks):]
    assert max(ticks) == max(all_ticks)


# ---------------------------------------------------------------------------
# satellite fixes: per-slot TTL restore, flusher error re-raise
# ---------------------------------------------------------------------------

class TTLCounter(SumCounter):
    ttl = 6


def _ttl_source(t, ingest=None):
    # key 7 appears only at tick 0; keys 0/1 every tick
    keys = [0, 1] if t else [0, 1, 7]
    return {"S1": make_batch(np.asarray(keys, np.int32),
                             ts=[t] * len(keys))}


def _ttl_engine(d):
    wf = Workflow([PassThroughMapper(), TTLCounter()],
                  external_streams=("S1",))
    cfg = EngineConfig(batch_size=16, queue_capacity=64, chunk_size=2,
                       durability=DurabilityConfig(
                           dir=d, flush=FlushConfig(
                               policy=FlushPolicy.EVERY_K, every_k=4)))
    return Engine(wf, cfg)


def test_ttl_expiry_after_recover(tmp_path):
    """Recovery restores per-slot `ts`, so TTL eviction after a crash
    follows the same schedule as the uninterrupted run (the old
    ``ts.max()`` restore kept idle keys alive too long)."""
    ea = _ttl_engine(str(tmp_path / "a"))
    sa, _ = ea.run(ea.init_state(), _ttl_source, 14)
    base = table_dict(sa, "U1")
    ea.close()
    assert 7 not in base and {0, 1} <= set(base)   # idle key expired

    eb = _ttl_engine(str(tmp_path / "b"))
    sb, _ = eb.run(eb.init_state(), _ttl_source, 5)   # key 7 still live
    assert 7 in table_dict(sb, "U1")
    del sb
    eb.close()

    eb2 = _ttl_engine(str(tmp_path / "b"))
    s2 = eb2.recover()
    s2, _ = eb2.run(s2, _ttl_source, 9, source_offset=5)
    rec = table_dict(s2, "U1")
    eb2.close()
    assert 7 not in rec
    assert_tables_bitwise_equal(base, rec)


def test_restore_into_preserves_per_slot_ts():
    spec = {"count": ((), jnp.int32)}
    t = tbl.make_table(32, spec)
    t = restore_into(t, np.asarray([3, 5], np.int32),
                     {"count": np.asarray([30, 50], np.int32)},
                     np.asarray([2, 9], np.int32))
    slot, found = tbl.lookup(t, jnp.asarray([3, 5], jnp.int32))
    assert bool(found.all())
    ts = np.asarray(jax.device_get(t.ts))[np.asarray(slot)]
    assert ts.tolist() == [2, 9]
    # TTL sweep sees the restored clocks: key 3 (idle since tick 2) dies
    t = tbl.expire_ttl(t, now=jnp.int32(10), ttl=5)
    _, found = tbl.lookup(t, jnp.asarray([3, 5], jnp.int32))
    assert found.tolist() == [False, True]


class _FailingStore:
    def put_many(self, *a, **k):
        raise IOError("store down")

    def flush(self):
        pass


def test_flusher_reraises_store_errors():
    fl = Flusher(_FailingStore(), FlushConfig(policy=FlushPolicy.IMMEDIATE))
    t = tbl.make_table(16, {"count": ((), jnp.int32)})
    t, slot, _, placed = tbl.insert_or_find(
        t, jnp.asarray([1], jnp.int32), jnp.ones(1, bool))
    t = tbl.write_slates(t, slot, placed,
                         {"count": jnp.asarray([5], jnp.int32)}, 1)
    fl.flush_table("U1", t)
    with pytest.raises(FlushError) as ei:
        fl.drain()
    assert isinstance(ei.value.errors[0], IOError)
    # errors were consumed; a clean drain passes and close() still
    # terminates the worker thread
    fl.drain()
    fl.close()
    assert not fl._thread.is_alive()


def test_frontier_never_advances_past_failed_flush(tmp_path):
    eng = _counting_engine(str(tmp_path / "f"), "jnp")
    eng.dur.flusher.store = _FailingStore()   # store dies mid-run
    with pytest.raises(FlushError):
        eng.run(eng.init_state(), counting_source, 12)
    assert eng.dur.frontier.tick == 0         # replay covers everything
    eng.dur.flusher.close()


# ---------------------------------------------------------------------------
# WAL compaction
# ---------------------------------------------------------------------------

def test_wal_truncate_before_keeps_offsets(tmp_path):
    wal = WriteAheadLog(str(tmp_path / "w.log"))
    offs = []
    for t in range(5):
        offs.append(wal.append(t, counting_source(t)))
    wal.truncate_before(offs[1])              # drop ticks 0..1
    assert [t for t, _ in wal.replay()] == [2, 3, 4]
    # logical offsets recorded before compaction stay valid
    assert [t for t, _ in wal.replay(from_offset=offs[2])] == [3, 4]
    assert wal.offset == offs[4]
    wal.close()
    wal2 = WriteAheadLog(str(tmp_path / "w.log"))   # survives reopen
    assert [t for t, _ in wal2.replay(from_offset=offs[2])] == [3, 4]
    wal2.close()


def test_frontier_file_roundtrip(tmp_path):
    p = str(tmp_path / "FRONTIER.json")
    assert FlushFrontier.load(p) is None
    FlushFrontier(tick=17, wal_offset=[3, 4]).save(p)
    f = FlushFrontier.load(p)
    assert f.tick == 17 and list(f.wal_offset) == [3, 4]


# ---------------------------------------------------------------------------
# >= 2-shard DistributedEngine: shard loss + re-routed recovery
# (subprocess for the 8-device host platform, like test_multishard)
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_distributed_crash_recover_parity(tmp_path):
    code = textwrap.dedent("""
        import os, tempfile
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import Mesh
        from repro.core.event import EventBatch
        from repro.core.operators import AssociativeUpdater
        from repro.core.workflow import Workflow
        from repro.core.distributed import DistributedEngine, DistConfig
        from repro.core.durability import DurabilityConfig
        from repro.slates.flush import FlushConfig, FlushPolicy

        VSPEC = {'x': ((), jnp.int32)}

        class Counter(AssociativeUpdater):
            name = 'U1'; subscribes = ('S1',); in_value_spec = VSPEC
            out_streams = {}; table_capacity = 512
            def slate_spec(self):
                return {'count': ((), jnp.int32), 'sum': ((), jnp.int32)}
            def lift(self, b):
                return {'count': jnp.ones_like(b.key), 'sum': b.value['x']}
            def combine(self, a, b):
                return {'count': a['count'] + b['count'],
                        'sum': a['sum'] + b['sum']}
            def merge(self, s, d):
                return {'count': s['count'] + d['count'],
                        'sum': s['sum'] + d['sum']}

        mesh = Mesh(np.array(jax.devices()), ('data',))

        def src(t):
            rng = np.random.default_rng(50 + t)
            keys = rng.integers(0, 64, size=(8, 16)).astype(np.int32)
            return {'S1': EventBatch(
                sid=jnp.zeros((8, 16), jnp.int32),
                ts=jnp.full((8, 16), t, jnp.int32),
                key=jnp.asarray(keys),
                value={'x': jnp.asarray(keys % 7)},
                valid=jnp.ones((8, 16), bool))}

        def slates(eng, state):
            return {k: {lk: int(lv) for lk, lv in v.items()}
                    for k in range(64)
                    for v in [eng.read_slate(state, 'U1', k)]
                    if v is not None}

        def build(d):
            cfg = DistConfig(batch_size=32, queue_capacity=256,
                             durability=DurabilityConfig(
                                 dir=d, flush=FlushConfig(
                                     policy=FlushPolicy.EVERY_K,
                                     every_k=4)))
            wf = Workflow([Counter()], external_streams=('S1',))
            return DistributedEngine(wf, mesh, cfg)

        da, db = tempfile.mkdtemp(), tempfile.mkdtemp()
        ea = build(da)
        sa, _ = ea.run_durable(ea.init_state(), src, 12)
        base = slates(ea, sa)
        ea.dur.close()

        # crash at tick 10: store covers ticks < 8, WAL replay 8..9
        eb = build(db)
        sb, _ = eb.run_durable(eb.init_state(), src, 10)
        assert eb.dur.frontier.tick == 8
        del sb                              # crash: all shards lost
        eb.dur.close()

        eb2 = build(db)
        eb2.ring.fail(3)                    # machine 3 never comes back
        s2 = eb2.recover()
        tick2 = int(np.asarray(jax.device_get(s2['tick'])).max())
        assert tick2 == 10, tick2           # frontier 8 + 2 replayed
        s2, _ = eb2.run_durable(s2, src, 2, start_tick=tick2)
        rec = slates(eb2, s2)
        eb2.dur.close()

        assert set(base) == set(rec), (len(base), len(rec))
        bad = [k for k in base if base[k] != rec[k]]
        assert not bad, bad[:5]
        # the failed shard's keys really moved: its table is empty
        occ = np.asarray(jax.device_get(
            (s2['tables']['U1'].keys != -1).sum(axis=1)))
        assert occ[3] == 0 and occ.sum() == len(rec)
        print('DIST-RECOVERY-OK', len(rec))
    """)
    r = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True,
        env={**os.environ, "PYTHONPATH": os.path.join(ROOT, "src")},
        timeout=560)
    assert r.returncode == 0, r.stderr[-4000:]
    assert "DIST-RECOVERY-OK" in r.stdout


def test_resumed_run_does_not_rethrottle():
    """throttle_hits is cumulative: a second run() on carried-over state
    (the shape of every post-recover resume) must not read old hits as a
    fresh backpressure signal and spuriously halve the ingest limit."""
    from repro.core.queues import OverflowPolicy
    from tests.conftest import CountingUpdater

    wf = Workflow([PassThroughMapper(), CountingUpdater()],
                  external_streams=("S1",))
    cfg = EngineConfig(batch_size=16, queue_capacity=16, chunk_size=1,
                       overflow={"M1": OverflowPolicy.THROTTLE})
    eng = Engine(wf, cfg)

    def flood(t, ingest=None):     # 32 events into a 16-slot queue
        return {"S1": make_batch(np.arange(32, dtype=np.int32),
                                 ts=[t] * 32)}

    state, _ = eng.run(eng.init_state(), flood, 3)
    assert int(jax.device_get(state["throttle_hits"])) > 0

    seen = []

    def calm(t, ingest=None):      # 4 events: no overflow possible
        seen.append(ingest)
        return {"S1": make_batch(np.arange(4, dtype=np.int32),
                                 ts=[t] * 4)}

    state, _ = eng.run(state, calm, 4, source_offset=3)
    assert seen == [None] * 4, seen   # no spurious throttling
