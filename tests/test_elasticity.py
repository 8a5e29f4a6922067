"""Live elasticity (DESIGN.md section 12): weighted fixed-shape ring,
runtime shard join/leave with loss-free slate + queue migration, and the
load-aware rebalance.

Multi-shard coverage runs in SUBPROCESSES (like test_multishard) so the
main pytest process keeps the real single device; one fast parity test
stays in tier-1 on a 1-device mesh (it exercises the full migration
kernel — drain, host remap, table rebuild, device_put)."""
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.hashing import HashRing, route

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ---------------------------------------------------------------------------
# weighted fixed-shape ring (host-level, tier-1)
# ---------------------------------------------------------------------------

def test_ring_table_shape_is_fixed_across_membership_and_weights():
    ring = HashRing(8, vnodes=32)
    shape0 = ring.table()[0].shape
    ring.fail(3)
    assert ring.table()[0].shape == shape0
    ring.join(3)
    assert ring.table()[0].shape == shape0
    ring.set_weights(np.array([4.0, 1, 1, 1, 1, 1, 1, 0.25]))
    assert ring.table()[0].shape == shape0
    # pad entries alias the wrap target: routing still lands on active
    # shards only
    ring.fail(0)
    rh, rs = ring.table()
    dest = np.asarray(route(jnp.arange(20_000, dtype=jnp.int32), 5,
                            rh, rs))
    assert 0 not in set(np.unique(dest))


def test_ring_secondary_stays_distinct_across_pad_region():
    """Deactivating half the shards fills half the table with pad
    entries; the two-choice secondary walk must still find a distinct
    shard when it crosses them (pads cycle the real ring)."""
    from repro.core.hashing import route_secondary
    ring = HashRing(8, vnodes=64)
    for s in (4, 5, 6, 7):
        ring.fail(s)
    rh, rs = ring.table()
    keys = jnp.arange(100_000, dtype=jnp.int32)
    p = np.asarray(route(keys, 42, rh, rs))
    sec = np.asarray(route_secondary(keys, 42, rh, rs))
    assert (p == sec).mean() < 0.001
    assert set(np.unique(sec)) <= {0, 1, 2, 3}


def test_ring_vnode_budget_and_proportionality():
    ring = HashRing(8, vnodes=64)
    counts = ring.vnode_counts()
    assert counts.sum() == 8 * 64 and (counts == 64).all()
    ring.set_weights(np.array([2.0, 1, 1, 1, 1, 1, 1, 0.5]))
    counts = ring.vnode_counts()
    assert counts.sum() == 8 * 64           # fixed total budget
    assert counts[0] > 64 > counts[7] >= 1  # proportional, min 1
    ring.fail(2)
    counts = ring.vnode_counts()
    assert counts.sum() == 7 * 64 and counts[2] == 0


def test_ring_weight_shed_moves_arcs_directionally():
    keys = jnp.arange(60_000, dtype=jnp.int32)
    ring = HashRing(8, vnodes=64)
    before = np.asarray(route(keys, 9, *ring.table()))
    share0 = (before == 0).mean()
    ring.set_weights(np.array([0.25, 1, 1, 1, 1, 1, 1, 1]))
    after = np.asarray(route(keys, 9, *ring.table()))
    assert (after == 0).mean() < 0.5 * share0   # hot shard sheds arcs
    # the fixed vnode budget redistributes (others gain high-index
    # vnodes), so some third-party arcs move too — but the change stays
    # a rebalance, not a reshuffle
    moved = before != after
    assert moved.mean() < 0.35
    assert (after == 0).sum() < (before == 0).sum()


def test_ring_equal_weights_match_unweighted_construction():
    """All-alive equal-weight ring must be bit-identical to the classic
    per-shard-vnodes build (elasticity must not perturb existing
    routing)."""
    ring = HashRing(8, vnodes=64)
    real = ring.real_size
    assert real == 8 * 64                     # no padding when full
    ids = np.repeat(np.arange(8, dtype=np.uint32), 64)
    vix = np.tile(np.arange(64, dtype=np.uint32), 8)
    from repro.core.hashing import _mix32_np
    h = _mix32_np(ids * np.uint32(0x9E3779B9) ^ _mix32_np(
        vix + np.uint32(ring.seed)))
    order = np.argsort(h, kind="stable")
    assert np.array_equal(ring.ring_hashes, h[order])
    assert np.array_equal(ring.ring_shards, ids[order].astype(np.int32))


# ---------------------------------------------------------------------------
# fast tier-1 parity: the migration kernel end to end on a 1-device mesh
# ---------------------------------------------------------------------------

def test_migration_kernel_preserves_slates_bitwise():
    from jax.sharding import Mesh
    from repro.core.distributed import DistConfig, DistributedEngine
    from repro.core.event import EventBatch
    from repro.core.workflow import Workflow
    from tests.conftest import CountingUpdater

    class U(CountingUpdater):
        subscribes = ("S1",)

    mesh = Mesh(np.asarray(jax.devices()[:1]), ("data",))
    wf = Workflow([U()], external_streams=("S1",))
    eng = DistributedEngine(wf, mesh, DistConfig(batch_size=32,
                                                 queue_capacity=128))
    state = eng.init_state()
    rng = np.random.default_rng(0)
    for t in range(4):
        keys = rng.integers(0, 40, 24).astype(np.int32)
        b = EventBatch.of(key=keys,
                          value={"x": rng.integers(0, 9, 24).astype(
                              np.int32)},
                          ts=np.full(24, t, np.int32))
        state, _ = eng.step(state, {"S1": jax.tree.map(
            lambda x: x[None], b)})
    state, _ = eng.drain(state)
    before = {k: eng.read_slate(state, "U1", k) for k in range(40)}
    # reweight forces the full reconfigure path: drain barrier, host
    # remap, per-shard table rebuild, device_put with target sharding
    state, rep = eng._reconfigure(state, weights=np.array([3.0]))
    assert rep.moved_rows["U1"] == 0
    after = {k: eng.read_slate(state, "U1", k) for k in range(40)}
    for k in range(40):
        if before[k] is None:
            assert after[k] is None
            continue
        assert int(before[k]["count"]) == int(after[k]["count"])
        assert np.float32(before[k]["sum"]).tobytes() == \
            np.float32(after[k]["sum"]).tobytes()   # bitwise


def test_host_grow_pads_only_shard_leaves():
    """_host_grow pads exactly the [old_n, ...] leaves: a non-shard
    leaf keeps its shape, the new slot's table starts empty, its queue
    starts drained, and the tick carries over."""
    from jax.sharding import Mesh
    from repro.core.distributed import DistConfig, DistributedEngine
    from repro.core.workflow import Workflow
    from tests.conftest import CountingUpdater

    class U(CountingUpdater):
        subscribes = ("S1",)

    mesh = Mesh(np.asarray(jax.devices()[:1]), ("data",))
    wf = Workflow([U()], external_streams=("S1",))
    eng = DistributedEngine(wf, mesh, DistConfig(batch_size=16,
                                                 queue_capacity=64))
    state = eng.init_state()
    host = jax.device_get(state)
    host["aux"] = np.arange(15).reshape(3, 5)    # non-shard leaf
    eng.n_shards = 2                             # pad target (test rig)
    try:
        out = eng._host_grow(host, 1)
    finally:
        eng.n_shards = 1
    assert out["aux"].shape == (3, 5)            # untouched
    assert out["tick"].shape == (2,)
    assert int(out["tick"][1]) == int(np.asarray(host["tick"])[0])
    t = out["tables"]["U1"]
    assert t.keys.shape[0] == 2 and (t.keys[1] == -1).all()
    q = out["queues"]["U1"]
    assert q.size.shape == (2,) and int(q.size[1]) == 0


def test_durability_resize_shrink_closes_extra_wals(tmp_path):
    """Compaction's WAL shrink: resize down closes the dropped slots'
    logs and truncates the frontier offset list; resize back up appends
    fresh WALs at their (empty) head."""
    from repro.core.durability import DurabilityConfig, EngineDurability
    from repro.core.workflow import Workflow
    from repro.slates.flush import FlushConfig, FlushPolicy
    from tests.conftest import CountingUpdater

    class U(CountingUpdater):
        subscribes = ("S1",)

    wf = Workflow([U()], external_streams=("S1",))
    cfg = DurabilityConfig(dir=str(tmp_path),
                           flush=FlushConfig(policy=FlushPolicy.EVERY_K,
                                             every_k=4))
    dur = EngineDurability(cfg, wf, queue_capacity=64, batch_size=16,
                           n_shards=4)
    assert len(dur.wals) == 4
    dur.record_frontier(0)
    dur.resize(2)
    assert len(dur.wals) == 2
    assert len(dur.frontier_offsets()) == 2
    dur.resize(4)
    assert len(dur.wals) == 4 and len(dur.frontier_offsets()) == 4
    dur.close()


# ---------------------------------------------------------------------------
# multi-shard elasticity (subprocess; slow)
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
    from repro.core.distributed import (AutoscalePolicy, DistConfig,
                                        DistributedEngine)

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

    def gb(keys, xs, t, n_sh):
        k = keys.reshape(n_sh, -1)
        return EventBatch(sid=jnp.zeros(k.shape, jnp.int32),
                          ts=jnp.full(k.shape, t, jnp.int32),
                          key=jnp.asarray(k),
                          value={'x': jnp.asarray(xs.reshape(n_sh, -1))},
                          valid=jnp.ones(k.shape, bool))

    def slates(eng, state, n_keys):
        out = []
        for k in range(n_keys):
            s = eng.read_slate(state, 'U1', k)
            out.append((0, 0.0) if s is None else
                       (int(s['count']), float(s['sum'])))
        return out
"""


def run_sub(body: str, devices: int = 8, timeout: int = 560):
    code = textwrap.dedent(PRELUDE % {"devices": devices}) + \
        textwrap.dedent(body)
    r = subprocess.run([sys.executable, "-c", code],
                       capture_output=True, text=True,
                       env={**os.environ, "PYTHONPATH":
                            os.path.join(ROOT, "src")},
                       timeout=timeout)
    assert r.returncode == 0, f"STDOUT:\n{r.stdout}\nSTDERR:\n{r.stderr}"
    return r.stdout


def test_scale_2to4_parity_fast():
    """Small live scale-up mid-run == never-scaled run, slate for slate
    (the tier-1 smoke; the full 8->16 bitwise check is in the slow
    suite)."""
    out = run_sub("""
        def run(scale_to=None):
            mesh = Mesh(np.array(jax.devices()[:2]), ('data',))
            wf = Workflow([Counter()], external_streams=('S1',))
            eng = DistributedEngine(wf, mesh, DistConfig(
                batch_size=32, queue_capacity=256, fused='off'))
            state = eng.init_state()
            rng = np.random.default_rng(0)
            for t in range(6):
                keys = rng.integers(0, 32, 32).astype(np.int32)
                xs = rng.integers(0, 99, 32).astype(np.float32)
                if scale_to and t == 3:
                    state, rep = eng.scale(state, scale_to)
                    assert rep.recompiled and eng.n_shards == scale_to
                state, _ = eng.step(state, {'S1': gb(keys, xs, t,
                                                     eng.n_shards)})
            state, _ = eng.drain(state)
            return slates(eng, state, 32)
        a = run(); b = run(4)
        assert a == b, (a, b)
        print('FAST-PARITY-OK')
    """, devices=4)
    assert "FAST-PARITY-OK" in out


def test_device_migration_parity_fast():
    """The device tier (DESIGN.md 14.1): shape-preserving reconfigures
    move rows with on-device all_to_all and must match the host remap
    bitwise; reports carry the measured pause and payload, and
    heat_owners maps keys per updater salt."""
    out = run_sub("""
        class C2(Counter):
            name = 'U2'
        def run(mode, reconf):
            mesh = Mesh(np.array(jax.devices()[:4]), ('data',))
            wf = Workflow([Counter(), C2()], external_streams=('S1',))
            eng = DistributedEngine(wf, mesh, DistConfig(
                batch_size=32, queue_capacity=256, fused='off',
                device_migration=mode))
            state = eng.init_state()
            rng = np.random.default_rng(3)
            reps = []
            for t in range(6):
                keys = rng.integers(0, 48, 32).astype(np.int32)
                xs = rng.integers(0, 99, 32).astype(np.float32)
                if reconf and t == 2:
                    state, rep = eng.remove_shards(state, [3])
                    reps.append(rep)
                if reconf and t == 4:
                    state, rep = eng.scale(state, 4)    # rejoin
                    reps.append(rep)
                state, _ = eng.step(state, {'S1': gb(keys, xs, t, 4)})
            state, _ = eng.drain(state)
            return slates(eng, state, 48), reps, eng
        ref, _, _ = run('off', False)
        dev, dreps, eng = run('auto', True)
        host, hreps, _ = run('off', True)
        assert [r.path for r in dreps] == ['device', 'device'], dreps
        assert [r.path for r in hreps] == ['host', 'host']
        assert not any(r.recompiled for r in dreps)
        assert dev == ref and host == ref, (dev, host, ref)
        assert all(r.pause_s > 0 for r in dreps + hreps)
        assert sum(r.bytes_moved for r in dreps) > 0
        assert sum(dreps[0].moved_rows.values()) > 0
        # per-updater salted owner rows: [n_updaters, K], rows differ
        own = eng.heat_owners(np.arange(256, dtype=np.int32))
        assert own.shape == (2, 256)
        assert (own[0] != own[1]).any()
        print('DEVICE-PARITY-OK')
    """, devices=4)
    assert "DEVICE-PARITY-OK" in out


def test_grow_compact_grow_roundtrip_fast():
    """Physical grow -> auto-compaction -> grow again round-trips with
    exact counts and actually frees the parked slots' table HBM."""
    out = run_sub("""
        def tbytes(state):
            return sum(v.nbytes for v in jax.tree.leaves(state['tables']))
        mesh = Mesh(np.array(jax.devices()[:2]), ('data',))
        wf = Workflow([Counter()], external_streams=('S1',))
        eng = DistributedEngine(wf, mesh, DistConfig(
            batch_size=32, queue_capacity=256, fused='off',
            compact_threshold=0.5))
        state = eng.init_state()
        rng = np.random.default_rng(11)
        truth = np.zeros(48, np.int64)
        for t in range(9):
            keys = rng.integers(0, 48, 32).astype(np.int32)
            xs = np.ones(32, np.float32)
            for k in keys: truth[k] += 1
            if t == 2:
                state, rep = eng.scale(state, 4)        # physical grow
                assert rep.recompiled and eng.n_shards == 4
            if t == 5:
                big = tbytes(state)
                state, rep = eng.remove_shards(state, [2, 3])
                assert rep.recompiled and rep.path == 'host'
                assert eng.n_shards == 2                # auto-compacted
                assert tbytes(state) < big
            if t == 7:
                state, rep = eng.scale(state, 4)        # grow again
                assert rep.recompiled and eng.n_shards == 4
            state, _ = eng.step(state, {'S1': gb(keys, xs, t,
                                                 eng.n_shards)})
        state, _ = eng.drain(state)
        got = np.array([c for c, _ in slates(eng, state, 48)])
        assert (got == truth).all(), (got - truth)
        assert eng.stats(state)['exchange_dropped'] == 0
        print('ROUNDTRIP-OK')
    """, devices=4)
    assert "ROUNDTRIP-OK" in out


@pytest.mark.slow
@pytest.mark.parametrize("fused", ["jnp", "interpret"])
def test_device_path_scale_8to16_bitwise_parity(fused):
    """Acceptance bar for the device tier: on a pre-provisioned 16-slot
    mesh, activating 8 -> 16 moves rows via all_to_all (no recompile)
    with bitwise slate parity against both a never-scaled run and the
    host remap."""
    out = run_sub("""
        FUSED = %r
        def run(scale_to=None, mode='auto'):
            mesh = Mesh(np.array(jax.devices()[:16]), ('data',))
            wf = Workflow([Counter()], external_streams=('S1',))
            eng = DistributedEngine(wf, mesh, DistConfig(
                batch_size=64, queue_capacity=512, fused=FUSED,
                device_migration=mode, compact_threshold=0.0))
            state = eng.init_state()
            state, rep0 = eng.remove_shards(state, range(8, 16))
            assert not rep0.recompiled
            rng = np.random.default_rng(7)
            rep = None
            for t in range(12):
                keys = rng.integers(0, 96, 128).astype(np.int32)
                xs = rng.integers(0, 99, 128).astype(np.float32)
                if scale_to and t == 6:
                    state, rep = eng.scale(state, scale_to)
                    assert not rep.recompiled       # content-only swap
                state, _ = eng.step(state, {'S1': gb(keys, xs, t, 16)})
            state, _ = eng.drain(state)
            return slates(eng, state, 96), rep, eng, state
        a, _, _, _ = run()
        b, rep, eng, state = run(16)
        assert rep.path == 'device', rep
        assert rep.pause_s > 0 and rep.bytes_moved > 0
        assert sum(rep.moved_rows.values()) > 0
        for (ca, sa), (cb, sb) in zip(a, b):
            assert ca == cb
            assert np.float32(sa).tobytes() == np.float32(sb).tobytes()
        c, hrep, _, _ = run(16, mode='off')
        assert hrep.path == 'host' and b == c
        assert eng.stats(state)['exchange_dropped'] == 0
        rows16 = [int(jax.device_get(
            (state['tables']['U1'].keys[i] != -1).sum()))
            for i in range(16)]
        assert sum(1 for r in rows16[8:] if r > 0) >= 4, rows16
        print('DEVICE-8TO16-OK')
    """ % fused, devices=16)
    assert "DEVICE-8TO16-OK" in out


@pytest.mark.slow
def test_compaction_durable_recovery():
    """Compaction under durability: the WAL set shrinks with the mesh,
    counts stay exact through compact + continued feeding, and a crash
    after compaction recovers on the compacted layout."""
    out = run_sub("""
        import tempfile
        from repro.core.durability import DurabilityConfig
        from repro.slates.flush import FlushConfig, FlushPolicy
        def tbytes(state):
            return sum(v.nbytes for v in jax.tree.leaves(state['tables']))
        with tempfile.TemporaryDirectory() as d:
            def make(n):
                return DistributedEngine(
                    Workflow([Counter()], external_streams=('S1',)),
                    Mesh(np.array(jax.devices()[:n]), ('data',)),
                    DistConfig(batch_size=32, queue_capacity=256,
                               fused='off',
                               durability=DurabilityConfig(
                                   dir=d, flush=FlushConfig(
                                       policy=FlushPolicy.EVERY_K,
                                       every_k=2))))
            eng = make(8)
            state = eng.init_state()
            truth = np.zeros(64, np.int64)
            def src(t, _mx):
                r = np.random.default_rng(100 + t)
                ks = r.integers(0, 64, 64).astype(np.int32)
                for k in ks: truth[k] += 1
                return {'S1': gb(ks, np.ones(64, np.float32), t,
                                 eng.n_shards)}
            state, _ = eng.run(state, src, 6)
            b0 = tbytes(state)
            state, rep = eng.remove_shards(state, list(range(2, 8)))
            assert eng.n_shards == 2 and rep.recompiled
            assert rep.path == 'host' and rep.bytes_moved > 0
            assert len(eng.dur.wals) == 2           # WAL set compacted
            assert tbytes(state) < b0 / 3           # HBM actually freed
            state, _ = eng.drain(state)
            got = np.array([c for c, _ in slates(eng, state, 64)])
            assert (got == truth).all(), (got - truth)
            state, _ = eng.run(state, src, 2)       # keep feeding at 2
            state, _ = eng.drain(state)
            got = np.array([c for c, _ in slates(eng, state, 64)])
            assert (got == truth).all(), (got - truth)
            del state                               # crash
            eng2 = make(2)
            rec = eng2.recover()
            rec, _ = eng2.drain(rec)
            got2 = np.array([c for c, _ in slates(eng2, rec, 64)])
            assert (got2 == truth).all(), (got2 - truth)
            eng.close(); eng2.close()
        print('COMPACT-DURABLE-OK')
    """)
    assert "COMPACT-DURABLE-OK" in out


@pytest.mark.slow
def test_multiaxis_pod_data_growth():
    """Multi-axis growth: a ('pod','data') mesh scales 4 -> 8 along its
    trailing axis with exact counts; a target that is not a multiple of
    the leading axes' product is rejected."""
    out = run_sub("""
        mesh = Mesh(np.array(jax.devices()[:4]).reshape(2, 2),
                    ('pod', 'data'))
        eng = DistributedEngine(
            Workflow([Counter()], external_streams=('S1',)), mesh,
            DistConfig(batch_size=32, queue_capacity=256, fused='off',
                       axis_names=('pod', 'data')))
        state = eng.init_state()
        rng = np.random.default_rng(9)
        truth = np.zeros(48, np.int64)
        for t in range(8):
            keys = rng.integers(0, 48, 32).astype(np.int32)
            xs = np.ones(32, np.float32)
            for k in keys: truth[k] += 1
            if t == 4:
                state, rep = eng.scale(state, 8)
                assert rep.recompiled and eng.n_shards == 8
                assert tuple(eng.mesh.devices.shape) == (2, 4)
            state, _ = eng.step(state, {'S1': gb(keys, xs, t,
                                                 eng.n_shards)})
        state, _ = eng.drain(state)
        got = np.array([c for c, _ in slates(eng, state, 48)])
        assert (got == truth).all(), (got - truth)
        try:
            eng._grow_physical(9)
            raise SystemExit('expected ValueError')
        except ValueError as e:
            assert 'multiple' in str(e), e
        print('MULTIAXIS-OK')
    """)
    assert "MULTIAXIS-OK" in out


@pytest.mark.slow
@pytest.mark.parametrize("fused", ["jnp", "interpret"])
def test_live_scale_8to16_bitwise_parity(fused):
    """The acceptance bar: scale(8 -> 16) mid-run yields bitwise slate
    parity (int counts and f32 sums) with a never-scaled run — live
    migration is loss-free, unlike fail_shard."""
    out = run_sub("""
        FUSED = %r
        def run(scale_to=None):
            mesh = Mesh(np.array(jax.devices()[:8]), ('data',))
            wf = Workflow([Counter()], external_streams=('S1',))
            eng = DistributedEngine(wf, mesh, DistConfig(
                batch_size=64, queue_capacity=512, fused=FUSED))
            state = eng.init_state()
            rng = np.random.default_rng(7)
            for t in range(12):
                keys = rng.integers(0, 96, 128).astype(np.int32)
                xs = rng.integers(0, 99, 128).astype(np.float32)
                if scale_to and t == 6:
                    state, rep = eng.scale(state, scale_to)
                    assert rep.recompiled
                    assert sum(rep.moved_rows.values()) > 0
                state, _ = eng.step(state, {'S1': gb(keys, xs, t,
                                                     eng.n_shards)})
            state, _ = eng.drain(state)
            return slates(eng, state, 96), eng, state
        a, _, _ = run()
        b, eng, state = run(16)
        for (ca, sa), (cb, sb) in zip(a, b):
            assert ca == cb
            assert np.float32(sa).tobytes() == np.float32(sb).tobytes()
        assert eng.stats(state)['exchange_dropped'] == 0
        rows16 = [int(jax.device_get(
            (state['tables']['U1'].keys[i] != -1).sum()))
            for i in range(16)]
        assert sum(1 for r in rows16[8:] if r > 0) >= 4, rows16
        print('BITWISE-PARITY-OK')
    """ % fused, devices=16)
    assert "BITWISE-PARITY-OK" in out


@pytest.mark.slow
def test_remove_shards_loss_free_with_inflight_events():
    """Planned leave migrates slates AND events still queued on the
    leaving shards (drain_max=0 forces the in-flight path) — exact
    counts, zero drops; then the slots rejoin without recompilation."""
    out = run_sub("""
        mesh = Mesh(np.array(jax.devices()[:8]), ('data',))
        wf = Workflow([Counter()], external_streams=('S1',))
        eng = DistributedEngine(wf, mesh, DistConfig(
            batch_size=16, queue_capacity=512, exchange_slack=16.0))
        state = eng.init_state()
        rng = np.random.default_rng(1)
        feeds = [(rng.integers(0, 64, 128).astype(np.int32),
                  rng.integers(0, 99, 128).astype(np.float32))
                 for _ in range(10)]
        truth = np.zeros(64, np.int64)
        for ks, _ in feeds:
            for k in ks: truth[k] += 1
        for t in range(5):
            state, _ = eng.step(state, {'S1': gb(*feeds[t], t, 8)})
        backlog = {s: int(n) for s, n in enumerate(np.asarray(
            jax.device_get(state['queues']['U1'].size))) if int(n)}
        leave = sorted(backlog, key=backlog.get)[-2:]   # loaded shards
        state, rep = eng.remove_shards(state, leave, drain_max=0)
        assert sum(rep.moved_events.values()) > 0, (backlog, rep)
        for t in range(5, 10):
            state, _ = eng.step(state, {'S1': gb(*feeds[t], t, 8)})
        for _ in range(40):
            state = eng._step_empty(state)
        got = np.array([c for c, _ in slates(eng, state, 64)])
        assert (got == truth).all(), (got - truth)
        tb = state['tables']['U1']
        for s in leave:
            assert int(jax.device_get((tb.keys[s] != -1).sum())) == 0
        assert eng.stats(state)['exchange_dropped'] == 0
        # rejoin: content-only ring swap, compiled step object reused
        step_obj = eng._step
        state, rep = eng.scale(state, 8)
        assert not rep.recompiled and eng._step is step_obj
        print('REMOVE-REJOIN-OK')
    """)
    assert "REMOVE-REJOIN-OK" in out


@pytest.mark.slow
def test_rebalance_hot_ring_sheds_load():
    """The load-aware weighted ring: a shard running hot (queue peaks /
    drops) loses vnode arcs at the next rebalance, and counting stays
    exact through the reconfigure."""
    out = run_sub("""
        from repro.core.distributed import _salt
        mesh = Mesh(np.array(jax.devices()[:8]), ('data',))
        wf = Workflow([Counter()], external_streams=('S1',))
        eng = DistributedEngine(wf, mesh, DistConfig(
            batch_size=32, queue_capacity=2048, exchange_slack=16.0))
        state = eng.init_state()
        rng = np.random.default_rng(2)
        # hot traffic: one key -> one owner shard saturates
        hot_owner = int(eng.ring.owners(np.array([7], np.int32),
                                        _salt('U1'))[0])
        n_ticks = 6
        for t in range(n_ticks):
            keys = np.full(128, 7, np.int32)
            xs = np.ones(128, np.float32)
            state, _ = eng.step(state, {'S1': gb(keys, xs, t, 8)})
        counts0 = eng.ring.vnode_counts()
        state, rep = eng.rebalance(state)
        assert rep is not None
        counts1 = eng.ring.vnode_counts()
        assert counts1[hot_owner] < counts0[hot_owner], (hot_owner,
                                                         counts0, counts1)
        assert eng.ring.weights[hot_owner] < 1.0
        for _ in range(40):
            state = eng._step_empty(state)
        total = eng.read_slate(state, 'U1', 7)
        assert int(total['count']) == 128 * n_ticks, total
        print('REBALANCE-OK')
    """)
    assert "REBALANCE-OK" in out


@pytest.mark.slow
def test_autoscale_policy_through_run_and_durability():
    """The front-door path: cfg.autoscale drives scale boundaries inside
    DistributedEngine.run with durability attached; a crash after the
    scaled run recovers to the same slates (per-shard WAL/frontier set
    migrated with the shards)."""
    out = run_sub("""
        import tempfile
        from repro.core.durability import DurabilityConfig
        from repro.core.operators import Mapper
        from repro.slates.flush import FlushConfig, FlushPolicy

        class Fwd(Mapper):
            # an extra hop keeps events in flight at every reconfigure
            # boundary, forcing drain ticks there (the engine-tick vs
            # source-tick skew the WAL keying must survive)
            name = 'M1'; subscribes = ('S1',); in_value_spec = VSPEC
            out_streams = {'S2': VSPEC}
            def map_batch(self, b):
                return {'S2': EventBatch(sid=b.sid, ts=b.ts + 1,
                                         key=b.key, value=b.value,
                                         valid=b.valid)}

        class C2(Counter):
            subscribes = ('S2',)

        def make_wf():
            return Workflow([Fwd(), C2()], external_streams=('S1',))

        with tempfile.TemporaryDirectory() as d:
            reports = []
            cfg = DistConfig(batch_size=64, queue_capacity=512,
                             durability=DurabilityConfig(
                                 dir=d, flush=FlushConfig(
                                     policy=FlushPolicy.EVERY_K,
                                     every_k=4)),
                             autoscale=AutoscalePolicy(
                                 scale_at={4: 8}, rebalance_every=3,
                                 on_change=reports.append))
            eng = DistributedEngine(make_wf(), Mesh(
                np.array(jax.devices()[:4]), ('data',)), cfg)
            state = eng.init_state()
            fed = []
            def src(t, _mx):
                fed.append(t)
                r = np.random.default_rng(t)
                return {'S1': gb(r.integers(0, 32, 64).astype(np.int32),
                                 r.integers(0, 99, 64).astype(
                                     np.float32), t, eng.n_shards)}
            state, _ = eng.run(state, src, 8)
            state, _ = eng.drain(state)
            truth = np.zeros(32, np.int64)
            for t in fed:
                r = np.random.default_rng(t)
                for k in r.integers(0, 32, 64): truth[k] += 1
            assert any(r.recompiled for r in reports)
            assert eng.n_shards == 8
            # no duplicate WAL tick keys across any shard's log
            for w in eng.dur.wals:
                tks = [tk for tk, _ in w.replay(from_offset=0)]
                assert len(tks) == len(set(tks)), tks
            live = np.array([c for c, _ in slates(eng, state, 32)])
            assert (live == truth).all(), (live, truth)
            del state                      # crash
            def rebuild(n):
                c = DistConfig(batch_size=64, queue_capacity=512,
                               durability=DurabilityConfig(
                                   dir=d, flush=FlushConfig(
                                       policy=FlushPolicy.EVERY_K,
                                       every_k=4)))
                return DistributedEngine(
                    make_wf(),
                    Mesh(np.array(jax.devices()[:n]), ('data',)), c)
            eng2 = rebuild(8)
            rec = eng2.recover()
            rec, _ = eng2.drain(rec)
            got = np.array([c for c, _ in slates(eng2, rec, 32)])
            assert (got == truth).all(), (got, truth)
            # restart on the ORIGINAL 4-shard layout: the frontier's
            # 8-entry offset list outruns the engine — the extra
            # shards' WAL suffixes must fold into the replay, not be
            # silently dropped
            eng3 = rebuild(4)
            rec3 = eng3.recover()
            rec3, _ = eng3.drain(rec3)
            got3 = np.array([c for c, _ in slates(eng3, rec3, 32)])
            assert (got3 == truth).all(), (got3, truth)
            eng.close(); eng2.close(); eng3.close()
        print('AUTOSCALE-DURABLE-OK')
    """, devices=8)
    assert "AUTOSCALE-DURABLE-OK" in out


# ---------------------------------------------------------------------------
# front-door plumbing (tier-1)
# ---------------------------------------------------------------------------

def test_runtime_config_autoscale_front_door():
    from repro import AutoscalePolicy, RuntimeConfig
    pol = AutoscalePolicy(scale_at={24: 16}, rebalance_every=8)
    rt = RuntimeConfig(shards=2, autoscale=pol)
    assert rt.dist_config().autoscale is pol
    with pytest.raises(ValueError, match="distributed runtime"):
        RuntimeConfig(shards=1, autoscale=pol).engine_config()
    with pytest.raises(TypeError, match="AutoscalePolicy"):
        RuntimeConfig(shards=2, autoscale={"24": 16}).dist_config()


# ---------------------------------------------------------------------------
# read-tier edge cases against live elasticity (ISSUE 7)
# ---------------------------------------------------------------------------

def test_planned_leave_with_backlog_stays_on_device_path():
    """remove_shards(..., drain_max=0) with a queued backlog used to
    fall back to the host remap (exchange_rows only re-homed table
    rows).  exchange_queue now moves the backlog on device too: the
    device path must engage, report the same moved-event counts as the
    host migrator, and converge to bitwise-equal slates."""
    out = run_sub("""
        from repro.core.hashing import route
        from repro.core.distributed import _salt

        def total_dropped(eng, state):
            st = eng.stats(state)
            return (st['exchange_dropped'] +
                    sum(st['queue_dropped'].values()))

        def run(mode):
            mesh = Mesh(np.array(jax.devices()[:4]), ('data',))
            wf = Workflow([Counter()], external_streams=('S1',))
            eng = DistributedEngine(wf, mesh, DistConfig(
                batch_size=32, queue_capacity=2048, fused='off',
                device_migration=mode))
            state = eng.init_state()
            rng = np.random.default_rng(7)
            # keys the pre-leave ring homes on shard 3: hammer them so
            # the planned leave has a backlog exactly where it re-homes
            rh, rs = eng.ring.table()
            cand = jnp.arange(64, dtype=jnp.int32)
            owners = np.asarray(route(cand, _salt('U1'), rh, rs))
            hot = np.nonzero(owners == 3)[0][:4].astype(np.int32)
            assert len(hot) > 0
            reps = []
            for t in range(6):
                keys = np.where(rng.random(128) < 0.6,
                                rng.choice(hot, 128),
                                rng.integers(0, 24, 128)
                                ).astype(np.int32)
                xs = rng.integers(0, 99, 128).astype(np.float32)
                if t == 3:
                    sizes = jax.device_get({k: q.size for k, q in
                                            state['queues'].items()})
                    backlog = sum(int(np.asarray(v).sum())
                                  for v in sizes.values())
                    assert backlog > 0, 'no backlog built'
                    state, rep = eng.remove_shards(state, [3],
                                                   drain_max=0)
                    reps.append(rep)
                state, _ = eng.step(state, {'S1': gb(keys, xs, t, 4)})
            state, _ = eng.drain(state, max_ticks=256)
            return (slates(eng, state, 24), reps[0],
                    total_dropped(eng, state))

        dev, drep, ddrop = run('auto')
        host, hrep, hdrop = run('off')
        assert drep.path == 'device', drep
        assert hrep.path == 'host'
        assert drep.drain_ticks == 0 and hrep.drain_ticks == 0
        assert sum(drep.moved_events.values()) > 0, drep.moved_events
        assert drep.moved_events == hrep.moved_events, (
            drep.moved_events, hrep.moved_events)
        assert ddrop == hdrop, (ddrop, hdrop)  # feed overflow only
        assert dev == host, (dev, host)
        print('QEX-PARITY-OK')
    """, devices=4)
    assert "QEX-PARITY-OK" in out


def test_compaction_folds_lifetime_counters():
    """_compact_physical slices dead slots away; their lifetime
    counters (processed, drop tallies, count-min sketch mass, the
    tables' window stalls and claim rounds) must fold into survivors so
    TelemetryReport lifetime counts stay exact."""
    out = run_sub("""
        from repro.telemetry.metrics import TelemetryConfig

        def lifetime(eng, state):
            st = eng.stats(state)
            out = {'processed': sum(st['processed'].values()),
                   'queue_dropped': sum(st['queue_dropped'].values()),
                   'exchange_dropped': st['exchange_dropped'],
                   'throttle_hits': st['throttle_hits']}
            sk = jax.device_get(state['sketch'])
            out['sk_total'] = int(np.asarray(sk['total']).sum())
            out['sk_counts'] = int(np.asarray(sk['counts']).sum())
            out['sk_sample_n'] = int(np.asarray(sk['sample_n']).sum())
            for f in ('dropped', 'window_stalls', 'claim_rounds'):
                tally = jax.device_get({k: getattr(t, f) for k, t in
                                        state['tables'].items()})
                out['table_' + f] = sum(int(np.asarray(v).sum())
                                        for v in tally.values())
            return out

        mesh = Mesh(np.array(jax.devices()[:4]), ('data',))
        wf = Workflow([Counter()], external_streams=('S1',))
        eng = DistributedEngine(wf, mesh, DistConfig(
            batch_size=16, queue_capacity=32, fused='off',
            telemetry=TelemetryConfig(window=4, decay=0.5),
            compact_threshold=0.0))
        state = eng.init_state()
        rng = np.random.default_rng(1)
        for t in range(10):
            # heavy skew onto one key overflows a queue -> real drops
            keys = np.where(rng.random(64) < 0.5, 3,
                            rng.integers(0, 200, 64)).astype(np.int32)
            xs = rng.integers(0, 99, 64).astype(np.float32)
            state, _ = eng.step(state, {'S1': gb(keys, xs, t, 4)})
        state, _ = eng.drain(state, max_ticks=64)
        # the generic path runs no kernel: give each shard's table its
        # own stall tally, to be parked and resharded like the others
        from dataclasses import replace
        t = state['tables']['U1']
        state['tables']['U1'] = replace(t, window_stalls=jax.device_put(
            jnp.arange(1, 5, dtype=jnp.int32), t.window_stalls.sharding))
        before = lifetime(eng, state)
        assert (before['queue_dropped'] > 0 or
                before['exchange_dropped'] > 0), before
        assert before['table_window_stalls'] == 10

        state, rep = eng.remove_shards(state, [2, 3])
        state, rep = eng.compact(state)
        assert rep.recompiled and eng.n_shards == 2, rep
        after = lifetime(eng, state)
        # the rebuilt tables' own inserts run claim rounds: that tally
        # carries and grows
        assert after.pop('table_claim_rounds') >= before.pop(
            'table_claim_rounds') > 0
        for k in before:
            assert before[k] == after[k], (k, before[k], after[k])
        r = eng.telemetry.observe(eng, state)
        assert r.n_shards == 2
        print('COMPACT-FOLD-OK')
    """, devices=4)
    assert "COMPACT-FOLD-OK" in out


@pytest.mark.slow
def test_concurrent_reads_during_live_scale():
    """Readers on a StateHandle race a 4->8 scale mid-run.  step() and
    _reconfigure donate the buffers a reader may hold; the read_lock +
    in-lock handle republish must keep every read either pre- or post-
    migration -- no deleted-buffer errors, no torn slates -- and the
    scaled run still matches a never-scaled run slate for slate."""
    out = run_sub("""
        import threading
        from repro.core.distributed import AutoscalePolicy
        from repro.core.engine import StateHandle

        def src(t, ingest=None):
            rng = np.random.default_rng(40 + t)
            keys = rng.integers(0, 48, 128).astype(np.int32)
            xs = rng.integers(0, 99, 128).astype(np.float32)
            return {'S1': gb(keys, xs, t, eng.n_shards)}

        def build(scale):
            pol = AutoscalePolicy(scale_at={6: 8}) if scale else None
            mesh = Mesh(np.array(jax.devices()[:4]), ('data',))
            wf = Workflow([Counter()], external_streams=('S1',))
            return DistributedEngine(wf, mesh, DistConfig(
                batch_size=32, queue_capacity=512, fused='off',
                autoscale=pol))

        eng = build(scale=True)
        state = eng.init_state()
        h = StateHandle(eng, state)
        errors, n_reads = [], [0]
        stop = threading.Event()

        def reader():
            rng = np.random.default_rng(99)
            while not stop.is_set():
                try:
                    k = int(rng.integers(0, 48))
                    s = h.read_slate('U1', k)
                    if s is not None:   # torn slate check
                        assert int(s['count']) >= 0
                    many = h.read_slates(
                        'U1', rng.integers(0, 48, 16).tolist())
                    assert len(many) == 16
                    n_reads[0] += 1
                except Exception as e:  # noqa: BLE001
                    errors.append(repr(e))
                    return

        threads = [threading.Thread(target=reader) for _ in range(3)]
        for th in threads:
            th.start()
        state, _ = eng.run(state, src, 12, handle=h)
        state, _ = eng.drain(state)
        h.state = state
        stop.set()
        for th in threads:
            th.join(timeout=60)
        assert not errors, errors
        assert n_reads[0] > 0
        assert eng.n_shards == 8
        scaled = slates(eng, state, 48)

        eng = build(scale=False)   # src() reads eng.n_shards
        s2 = eng.init_state()
        s2, _ = eng.run(s2, src, 12)
        s2, _ = eng.drain(s2)
        assert scaled == slates(eng, s2, 48)
        print('READ-RACE-OK')
    """, devices=8)
    assert "READ-RACE-OK" in out
