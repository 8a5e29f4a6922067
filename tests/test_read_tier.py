"""High-QPS slate read tier (DESIGN.md section 15).

Covers the batched device lookup (kernels/slate_lookup) against the
looped ``read_slate`` oracle — bitwise, on jnp and interpret backends,
including two-choice partials, active hot-key splits, and TTL-expired
rows — plus the off-engine tiers: ``SlateReplica`` staleness bounds
(through crash recovery) and the telemetry-admitted ``HotKeyCache``.

Multi-shard coverage runs in subprocesses (same pattern as
test_elasticity) so the main pytest process keeps the real single
device."""
import dataclasses
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.engine import Engine, EngineConfig, StateHandle
from repro.core.workflow import Workflow
from repro.slates import table as tbl
from tests.conftest import (CountingUpdater, PassThroughMapper, VSPEC,
                            make_batch)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ---------------------------------------------------------------------------
# the lookup kernel against its oracle (tier-1, single device)
# ---------------------------------------------------------------------------

def _filled_table(n_rows=200, cap=512, d=8, seed=0):
    """Open-addressing table with one [C, D] value leaf (the layout the
    Pallas kernel accepts) holding ``n_rows`` random keys."""
    rng = np.random.default_rng(seed)
    keys = rng.choice(200_000, n_rows, replace=False).astype(np.int32)
    t = tbl.make_table(cap, {"v": ((d,), jnp.float32)})
    t, slot, _, placed = tbl.insert_or_find(
        t, jnp.asarray(keys), jnp.ones(n_rows, bool))
    vals = {"v": t.vals["v"].at[slot].set(
        rng.normal(size=(n_rows, d)).astype(np.float32))}
    t = dataclasses.replace(t, vals=vals)
    assert bool(np.asarray(placed).all())
    return t, keys


def test_lookup_kernel_interpret_matches_jnp_oracle():
    from repro.kernels.slate_lookup import ops as lk_ops
    t, keys = _filled_table()
    rng = np.random.default_rng(1)
    q = np.concatenate([rng.choice(keys, 64),
                        rng.integers(300_000, 400_000, 64)
                        ]).astype(np.int32)  # hits + guaranteed misses
    query = jnp.asarray(q)
    slot_r, found_r, rows_r = lk_ops.slate_lookup(
        t.keys, query, t.vals["v"], impl="jnp")
    slot_k, found_k, rows_k = lk_ops.slate_lookup(
        t.keys, query, t.vals["v"], impl="interpret")
    np.testing.assert_array_equal(np.asarray(found_r),
                                  np.asarray(found_k))
    np.testing.assert_array_equal(
        np.asarray(rows_r), np.asarray(rows_k))
    # found keys resolve to the exact live slot
    f = np.asarray(found_r)
    np.testing.assert_array_equal(
        np.asarray(t.keys)[np.asarray(slot_k)[f]], q[f])


def test_lookup_kernel_multi_tile_exact():
    """More queries than one kernel tile (padded last tile), against a
    table whose capacity is not a whole number of 128-lane windows."""
    from repro.kernels.slate_lookup import kernel as lk_kernel
    from repro.kernels.slate_lookup import ops as lk_ops
    t, keys = _filled_table(n_rows=300, cap=1000)
    rng = np.random.default_rng(3)
    n_hit = 2 * lk_kernel.TILE_Q
    q = jnp.asarray(np.concatenate([
        rng.choice(keys, n_hit),
        rng.integers(300_000, 400_000, 500)]).astype(np.int32))
    got = lk_ops.slate_lookup(t.keys, q, t.vals["v"], impl="interpret")
    want = lk_ops.slate_lookup(t.keys, q, t.vals["v"], impl="jnp")
    for g, w in zip(got, want):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))
    assert bool(np.asarray(got[1])[:n_hit].all())


def test_lookup_tree_multi_leaf_falls_back_bitwise():
    """Slate specs with several / scalar leaves can't use the kernel;
    lookup_tree must serve them through the jnp gather, same answers."""
    from repro.kernels.slate_lookup import ops as lk_ops
    from repro.kernels.slate_lookup import ref as lk_ref
    rng = np.random.default_rng(2)
    keys = rng.choice(10_000, 100, replace=False).astype(np.int32)
    t = tbl.make_table(256, {"count": ((), jnp.int32),
                             "sum": ((), jnp.float32)})
    t, slot, _, _ = tbl.insert_or_find(
        t, jnp.asarray(keys), jnp.ones(100, bool))
    vals = {"count": t.vals["count"].at[slot].set(
                jnp.arange(100, dtype=jnp.int32)),
            "sum": t.vals["sum"].at[slot].set(
                jnp.arange(100, dtype=jnp.float32) * 0.5)}
    q = np.concatenate([keys[:40],
                        np.arange(90_000, 90_024)]).astype(np.int32)
    found, rows = lk_ops.lookup_tree(t.keys, vals, jnp.asarray(q))
    slot_r, found_r = lk_ref.lookup_slots(t.keys, jnp.asarray(q))
    rows_r = lk_ref.gather_rows(vals, slot_r, found_r)
    np.testing.assert_array_equal(np.asarray(found),
                                  np.asarray(found_r))
    for k in rows:
        np.testing.assert_array_equal(np.asarray(rows[k]),
                                      np.asarray(rows_r[k]))


# ---------------------------------------------------------------------------
# engine.read_slates == looped read_slate (tier-1, single device)
# ---------------------------------------------------------------------------

class VecUpdater(CountingUpdater):
    """Single [8]-vector slate leaf: the layout the Pallas lookup
    kernel accepts, so impl="interpret" actually runs the kernel."""
    name = "UV"
    table_capacity = 256

    def slate_spec(self):
        return {"v": ((8,), jnp.float32)}

    def lift(self, batch):
        return {"v": jnp.broadcast_to(
            batch.value["x"].astype(jnp.float32)[:, None],
            (batch.key.shape[0], 8))}

    def combine(self, a, b):
        return {"v": a["v"] + b["v"]}

    def merge(self, s, d):
        return {"v": s["v"] + d["v"]}


def _run_engine(updaters, n_ticks=8, **cfg_kw):
    wf = Workflow([PassThroughMapper()] + updaters,
                  external_streams=("S1",))
    eng = Engine(wf, EngineConfig(batch_size=32, queue_capacity=256,
                                  **cfg_kw))
    state = eng.init_state()
    rng = np.random.default_rng(7)
    for t in range(n_ticks):
        keys = rng.integers(0, 60, 24).astype(np.int32)
        state, _ = eng.step(state, {"S1": make_batch(keys)})
    return eng, state


@pytest.mark.parametrize("impl", ["jnp", "interpret"])
def test_read_slates_bitwise_parity_with_looped(impl):
    eng, state = _run_engine([CountingUpdater(), VecUpdater()])
    keys = list(range(-4, 70))      # present, absent, negative
    for up in ("U1", "UV"):
        batched = eng.read_slates(state, up, keys, impl=impl)
        for k, b in zip(keys, batched):
            ref = eng.read_slate(state, up, k)
            if ref is None:
                assert b is None, (up, k)
            else:
                assert b is not None, (up, k)
                for leaf in ref:
                    np.testing.assert_array_equal(
                        np.asarray(ref[leaf]), np.asarray(b[leaf]))


def test_read_slates_ttl_expired_rows():
    """Rows past their TTL vanish from both read paths at the same
    tick; rows behind the freed slots stay visible (the probe-chain
    contract both paths share)."""
    class TTLCounter(CountingUpdater):
        ttl = 3

    eng, state = _run_engine([TTLCounter()], n_ticks=2)
    live = [k for k in range(60)
            if eng.read_slate(state, "U1", k) is not None]
    assert live
    # idle past the ttl: sweep evicts everything touched before
    for t in range(2, 8):
        state, _ = eng.step(
            state, {"S1": make_batch(np.asarray([500], np.int32))})
    batched = eng.read_slates(state, "U1", live)
    for k, b in zip(live, batched):
        assert eng.read_slate(state, "U1", k) is None
        assert b is None, k
    # the late key survives on both paths
    assert eng.read_slate(state, "U1", 500) is not None
    assert eng.read_slates(state, "U1", [500])[0] is not None


def test_read_slates_empty_and_unknown():
    eng, state = _run_engine([CountingUpdater()], n_ticks=1)
    assert eng.read_slates(state, "U1", []) == []
    with pytest.raises(KeyError):
        eng.read_slates(state, "nope", [1])


# ---------------------------------------------------------------------------
# hot-key cache (tier-1)
# ---------------------------------------------------------------------------

def test_hot_key_cache_admission_lru_ttl():
    from repro.slates.replica import HotKeyCache
    clock = [0.0]
    c = HotKeyCache(capacity=2, ttl_s=10.0, clock=lambda: clock[0])
    c.put("U1", 1, {"v": 1})            # not admitted -> dropped
    assert c.get("U1", 1) == (False, None)
    c.warm([1, 2, 3])
    c.put("U1", 1, {"v": 1})
    c.put("U1", 2, {"v": 2})
    assert c.get("U1", 1) == (True, {"v": 1})
    c.put("U1", 3, {"v": 3})            # evicts LRU (=2, 1 was touched)
    assert c.get("U1", 2) == (False, None)
    assert c.get("U1", 1) == (True, {"v": 1})
    clock[0] = 11.0                     # TTL expiry
    assert c.get("U1", 1) == (False, None)
    c.put("U1", 3, {"v": 3})
    c.invalidate()                      # frontier advanced
    assert len(c) == 0
    assert c.hot_keys() == [1, 2, 3]    # admission survives
    s = c.stats()
    assert s["invalidations"] == 1 and s["hits"] >= 2


def test_state_handle_serves_cached_hot_keys():
    from repro.slates.replica import HotKeyCache
    eng, state = _run_engine([CountingUpdater()])
    hot = next(k for k in range(60)
               if eng.read_slate(state, "U1", k) is not None)
    cache = HotKeyCache(capacity=8)
    cache.warm([hot])
    h = StateHandle(eng, state, cache=cache)
    first = h.read_slate("U1", hot)
    assert len(cache) == 1
    # cache now answers without touching the engine at all
    h.state = None
    assert h.read_slate("U1", hot) == first
    h.on_frontier_advance()             # invalidation hook
    assert len(cache) == 0


# ---------------------------------------------------------------------------
# replica tier: staleness bound through crash recovery (tier-1)
# ---------------------------------------------------------------------------

def test_replica_staleness_bound_across_crash_recovery(tmp_path):
    from repro.core.durability import DurabilityConfig
    from repro.slates.flush import FlushConfig, FlushPolicy
    from repro.slates.replica import SlateReplica, StaleReplicaError

    def build():
        wf = Workflow([PassThroughMapper(), CountingUpdater()],
                      external_streams=("S1",))
        return Engine(wf, EngineConfig(
            batch_size=32, queue_capacity=256,
            durability=DurabilityConfig(
                dir=str(tmp_path / "d"),
                flush=FlushConfig(policy=FlushPolicy.EVERY_K,
                                  every_k=4))))

    def src(t, ingest=None):
        rng = np.random.default_rng(300 + t)
        return {"S1": make_batch(
            rng.integers(0, 30, 24).astype(np.int32), ts=[t] * 24)}

    eng = build()
    state, _ = eng.run(eng.init_state(), src, 12)
    state = eng.checkpoint(state)
    rep = SlateReplica(eng.dur.store, eng.wf, max_staleness_ticks=8)
    with pytest.raises(StaleReplicaError):
        rep.read("U1", 0, now=0)        # never refreshed
    rep.refresh(eng.dur.frontier)
    tick = rep.snapshot_tick
    assert tick > 0
    # within the bound: snapshot values equal the live table
    live = [(k, eng.read_slate(state, "U1", k)) for k in range(30)]
    for k, lv in live:
        rv = rep.read("U1", k, now=tick)
        if lv is None:
            assert rv is None
        else:
            assert int(lv["count"]) == int(np.asarray(rv["count"]))
            assert float(lv["sum"]) == float(np.asarray(rv["sum"]))
    # beyond the bound: refused, not silently stale
    with pytest.raises(StaleReplicaError):
        rep.read("U1", 0, now=tick + 9)
    eng.close()

    # crash: memory gone.  A fresh engine recovers from the same store;
    # the replica keeps serving (its snapshot is the recovery source)
    eng2 = build()
    s2 = eng2.recover()
    rep2 = SlateReplica(eng2.dur.store, eng2.wf, max_staleness_ticks=8)
    rep2.refresh(eng2.dur.frontier)
    for k, lv in live:
        rv = rep2.read_many("U1", [k], now=rep2.snapshot_tick)[0]
        rlv = eng2.read_slate(s2, "U1", k)
        if rlv is None:
            assert rv is None
        else:
            assert int(np.asarray(rv["count"])) == int(rlv["count"])
    # the recovered engine runs on; the old snapshot ages out
    s2, _ = eng2.run(s2, src, 12, source_offset=12)
    s2 = eng2.checkpoint(s2)
    now = int(eng2.dur.frontier.tick)
    if now - rep2.snapshot_tick > 8:
        with pytest.raises(StaleReplicaError):
            rep2.read("U1", 0, now=now)
    rep2.refresh(eng2.dur.frontier)
    assert rep2.read("U1", 0, now=now) is not None or \
        eng2.read_slate(s2, "U1", 0) is None
    eng2.close()


def test_replica_incremental_refresh_matches_full_scan(tmp_path):
    """A delta-fed replica refreshed at every frontier must hold the
    same snapshot (keys, write ticks, values — bitwise) a fresh
    full-store scan at that frontier builds, including TTL pruning."""
    from repro.core.durability import DurabilityConfig
    from repro.slates.flush import FlushConfig, FlushPolicy
    from repro.slates.replica import SlateReplica

    class TtlCounting(CountingUpdater):
        name = "U2"
        ttl = 6

    wf = Workflow([PassThroughMapper(), CountingUpdater(), TtlCounting()],
                  external_streams=("S1",))
    eng = Engine(wf, EngineConfig(
        batch_size=32, queue_capacity=256,
        durability=DurabilityConfig(
            dir=str(tmp_path / "d"),
            flush=FlushConfig(policy=FlushPolicy.EVERY_K, every_k=4),
            track_flush_deltas=True)))

    def src(t, ingest=None):
        rng = np.random.default_rng(40 + t)
        return {"S1": make_batch(
            rng.integers(0, 50, 24).astype(np.int32), ts=[t] * 24)}

    state = eng.init_state()
    inc = SlateReplica(eng.dur.store, eng.wf, max_staleness_ticks=64,
                       flusher=eng.dur.flusher)
    for seg in range(3):
        state, _ = eng.run(state, src, 4, source_offset=seg * 4)
        state = eng.checkpoint(state)        # barrier: frontier advance
        inc.refresh(eng.dur.frontier)        # seg 0: scan; then deltas
        full = SlateReplica(eng.dur.store, eng.wf,
                            max_staleness_ticks=64)
        full.refresh(eng.dur.frontier)
        assert inc.snapshot_tick == full.snapshot_tick
        assert inc.stats()["rows"] == full.stats()["rows"]
        for up in ("U1", "U2"):
            for k in range(50):
                a = inc.read(up, k, now=inc.snapshot_tick)
                b = full.read(up, k, now=full.snapshot_tick)
                if b is None:
                    assert a is None, (up, k)
                else:
                    for leaf in b:
                        np.testing.assert_array_equal(
                            np.asarray(a[leaf]), np.asarray(b[leaf]))
    assert inc.stats()["rows"]["U1"] > 0
    eng.close()


# ---------------------------------------------------------------------------
# distributed batched reads (subprocess; slow)
# ---------------------------------------------------------------------------

PRELUDE = """
    import os
    os.environ["XLA_FLAGS"] = \
        "--xla_force_host_platform_device_count=%(devices)d"
    import jax, jax.numpy as jnp, numpy as np
    from jax.sharding import Mesh
    from repro.core.event import EventBatch
    from repro.core.operators import AssociativeUpdater
    from repro.core.workflow import Workflow
    from repro.core.distributed import DistConfig, DistributedEngine

    VSPEC = {'x': ((), jnp.float32)}

    class Counter(AssociativeUpdater):
        name = 'U1'; subscribes = ('S1',); in_value_spec = VSPEC
        out_streams = {}; table_capacity = 1024
        sum_mergeable = True
        def slate_spec(self):
            return {'count': ((), jnp.int32), 'sum': ((), jnp.float32)}
        def lift(self, b):
            return {'count': jnp.ones_like(b.key),
                    'sum': b.value['x']}
        def combine(self, a, b):
            return {'count': a['count'] + b['count'],
                    'sum': a['sum'] + b['sum']}
        def merge(self, s, d):
            return {'count': s['count'] + d['count'],
                    'sum': s['sum'] + d['sum']}

    class Vec(Counter):
        name = 'UV'
        def slate_spec(self):
            return {'v': ((8,), jnp.float32)}
        def lift(self, b):
            return {'v': jnp.broadcast_to(b.value['x'][:, None],
                                          (b.key.shape[0], 8))}
        def combine(self, a, b):
            return {'v': a['v'] + b['v']}
        def merge(self, s, d):
            return {'v': s['v'] + d['v']}

    def gb(keys, xs, t, n_sh):
        k = keys.reshape(n_sh, -1)
        return EventBatch(sid=jnp.zeros(k.shape, jnp.int32),
                          ts=jnp.full(k.shape, t, jnp.int32),
                          key=jnp.asarray(k),
                          value={'x': jnp.asarray(
                              xs.reshape(n_sh, -1))},
                          valid=jnp.ones(k.shape, bool))

    def check_parity(eng, state, updater, keys, impls):
        looped = [eng.read_slate(state, updater, int(k)) for k in keys]
        for impl in impls:
            batched = eng.read_slates(state, updater, keys, impl=impl)
            for k, a, b in zip(keys, looped, batched):
                assert (a is None) == (b is None), (impl, k, a, b)
                if a is None:
                    continue
                for leaf in a:
                    av, bv = np.asarray(a[leaf]), np.asarray(b[leaf])
                    assert np.array_equal(av, bv), (impl, k, leaf,
                                                    av, bv)
"""


def run_sub(body: str, devices: int = 4, timeout: int = 560):
    code = textwrap.dedent(PRELUDE % {"devices": devices}) + \
        textwrap.dedent(body)
    r = subprocess.run([sys.executable, "-c", code],
                       capture_output=True, text=True,
                       env={**os.environ, "PYTHONPATH":
                            os.path.join(ROOT, "src")},
                       timeout=timeout)
    assert r.returncode == 0, f"STDOUT:\n{r.stdout}\nSTDERR:\n{r.stderr}"
    return r.stdout


@pytest.mark.slow
def test_distributed_read_slates_parity_plain_and_partials():
    """Batched sharded reads == looped ring reads, bitwise, on jnp and
    interpret — plain routing, two-choice partials, and a live hot-key
    entry (secondary-shard merge paths)."""
    out = run_sub("""
        def drive(cfg):
            mesh = Mesh(np.array(jax.devices()[:4]), ('data',))
            wf = Workflow([Counter(), Vec()], external_streams=('S1',))
            eng = DistributedEngine(wf, mesh, cfg)
            state = eng.init_state()
            rng = np.random.default_rng(11)
            for t in range(6):
                keys = rng.integers(0, 64, 32).astype(np.int32)
                xs = rng.integers(0, 99, 32).astype(np.float32)
                state, _ = eng.step(state, {'S1': gb(keys, xs, t, 4)})
            state, _ = eng.drain(state)
            return eng, state

        keys = np.arange(-4, 72, dtype=np.int32)   # hits + misses

        # plain primary-only routing
        eng, state = drive(DistConfig(batch_size=32,
                                      queue_capacity=256, fused='off'))
        check_parity(eng, state, 'U1', keys, ['jnp', 'interpret'])
        check_parity(eng, state, 'UV', keys, ['jnp', 'interpret'])

        # two-choice: hot keys spill partials onto a secondary shard
        eng2, state2 = drive(DistConfig(batch_size=32,
                                        queue_capacity=256, fused='off',
                                        two_choice_threshold=4))
        check_parity(eng2, state2, 'U1', keys, ['jnp', 'interpret'])
        check_parity(eng2, state2, 'UV', keys, ['jnp', 'interpret'])

        # hot-key split set entry flips the secondary merge on for one
        # key even without two-choice
        eng.read_slates.__self__  # noqa (keep eng alive)
        eng._hot_keys[0] = np.int32(7)
        eng._hot_valid[0] = True
        eng._read_fns.clear()     # with_sec changed for the read path
        check_parity(eng, state, 'U1', keys, ['jnp', 'interpret'])
        print('DIST-PARITY-OK')
    """)
    assert "DIST-PARITY-OK" in out


@pytest.mark.slow
def test_distributed_batched_reads_of_split_keys():
    """Active split_keys: every sub-key of a split hot key reads the
    same through the batched path as the looped path, and their merge
    equals read_split_slate."""
    out = run_sub("""
        from repro.core.hotspot import (KeySplitMapper, read_split_slate,
                                        subkeys_of)
        WAYS = 4
        mesh = Mesh(np.array(jax.devices()[:4]), ('data',))
        wf = Workflow([KeySplitMapper('S1', 'S2', VSPEC, ways=WAYS),
                       type('C', (Counter,), {'subscribes': ('S2',)})()],
                      external_streams=('S1',))
        eng = DistributedEngine(wf, mesh, DistConfig(
            batch_size=32, queue_capacity=512, fused='off'))
        state = eng.init_state()
        rng = np.random.default_rng(5)
        HOT = 9
        for t in range(8):
            keys = np.where(rng.random(32) < 0.5, HOT,
                            rng.integers(0, 40, 32)).astype(np.int32)
            xs = rng.integers(0, 99, 32).astype(np.float32)
            state, _ = eng.step(state, {'S1': gb(keys, xs, t, 4)})
        state, _ = eng.drain(state)

        subs = subkeys_of(HOT, WAYS)
        looped = [eng.read_slate(state, 'U1', s) for s in subs]
        present = [s for s, v in zip(subs, looped) if v is not None]
        assert len(present) >= 2, (subs, looped)   # key really split
        check_parity(eng, state, 'U1', np.asarray(subs, np.int32),
                     ['jnp', 'interpret'])
        merged = read_split_slate(eng, state, 'U1', HOT, WAYS)
        batched = eng.read_slates(state, 'U1', subs)
        total_c = sum(int(np.asarray(b['count']))
                      for b in batched if b is not None)
        total_s = sum(float(np.asarray(b['sum']))
                      for b in batched if b is not None)
        assert int(np.asarray(merged['count'])) == total_c
        assert abs(float(np.asarray(merged['sum'])) - total_s) < 1e-3
        print('SPLIT-READ-OK')
    """)
    assert "SPLIT-READ-OK" in out
