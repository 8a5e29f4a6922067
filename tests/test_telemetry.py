"""Device-side telemetry + the closed control loop (DESIGN.md 13):
count-min sketch correctness and backend parity, the telemetry-on/off
bitwise parity contract of the chunk path, controller hysteresis, the
end-to-end closed-loop square wave, runtime hot-key splitting, and the
source-index / engine-tick decoupling in the distributed durable path.

Multi-shard coverage runs in subprocesses (the test_elasticity
pattern); the full 4 -> 8 -> 4 acceptance wave is in the slow suite
with a fast 2 -> 4 -> 2 twin in tier-1."""
import collections

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.kernels.countmin import countmin_update
from repro.telemetry import (LoadAutoscaler, TelemetryConfig,
                             TelemetryReport)
from repro.telemetry import controller as ctl_mod
from repro.telemetry import sketch as sk_mod
from tests.test_elasticity import run_sub


# ---------------------------------------------------------------------------
# count-min sketch: backends + bounds (tier-1, host-level)
# ---------------------------------------------------------------------------

def test_countmin_backends_agree_bitwise():
    """The interpret (kernel-body) backend must match the jnp oracle
    bit for bit — integer adds, no reassociation slack."""
    rng = np.random.default_rng(0)
    counts = jnp.asarray(rng.integers(0, 50, (4, 256)), jnp.int32)
    cols = jnp.asarray(rng.integers(0, 256, (4, 128)), jnp.int32)
    add = jnp.asarray(rng.integers(0, 2, 128), jnp.int32)
    a = countmin_update(counts, cols, add, impl="ref")
    b = countmin_update(counts, cols, add, impl="interpret")
    assert np.array_equal(np.asarray(a), np.asarray(b))
    # an unsupported width raises when the kernel is asked for; the
    # oracle still takes it
    with pytest.raises(ValueError, match="width % 128"):
        countmin_update(counts[:, :100], cols % 100, add, impl="pallas")
    d = countmin_update(counts[:, :100], cols % 100, add, impl="ref")
    assert int(np.asarray(d).sum()) == int(np.asarray(counts[:, :100]).sum()
                                          + 4 * np.asarray(add).sum())


def _true_counts(keys):
    return collections.Counter(int(k) for k in keys)


@settings(max_examples=20, deadline=None)
@given(st.lists(st.integers(-2**31 + 1, 2**31 - 1), min_size=1,
                max_size=200))
def test_sketch_estimate_never_underestimates(keys):
    """The one-sided count-min guarantee: estimate(k) >= true(k),
    always (collisions only ever inflate)."""
    salts = sk_mod.make_salts(4)
    s = sk_mod.make_sketch(4, 256, 64)
    s = sk_mod.sketch_update(s, jnp.asarray(keys, jnp.int32),
                             jnp.ones(len(keys), bool), salts,
                             impl="ref")
    true = _true_counts(keys)
    est = sk_mod.estimate(np.asarray(s["counts"]), list(true), salts)
    for (k, t), e in zip(true.items(), est):
        assert e >= t, (k, int(e), t)
    assert int(s["total"]) == len(keys)


def test_sketch_error_bound_example():
    """Stub-safe example twin: on a fixed workload the estimate error
    stays within the classic e*N/width bound and heavy_hitters ranks
    the planted hot keys first."""
    rng = np.random.default_rng(7)
    keys = np.concatenate([np.full(300, 77), np.full(150, -5),
                           rng.integers(0, 5000, 400)]).astype(np.int32)
    rng.shuffle(keys)
    salts = sk_mod.make_salts(4)
    s = sk_mod.make_sketch(4, 512, 256)
    for lo in range(0, len(keys), 128):     # batch-wise, like the tick
        chunk = np.zeros(128, np.int32)
        valid = np.zeros(128, bool)
        part = keys[lo:lo + 128]
        chunk[:len(part)], valid[:len(part)] = part, True
        s = sk_mod.sketch_update(s, jnp.asarray(chunk),
                                 jnp.asarray(valid), salts, impl="ref")
    true = _true_counts(keys)
    N = len(keys)
    bound = int(np.ceil(np.e * N / 512))
    est = sk_mod.estimate(np.asarray(s["counts"]), list(true), salts)
    for (k, t), e in zip(true.items(), est):
        assert t <= e <= t + bound, (k, int(e), t, bound)
    hh = sk_mod.heavy_hitters(np.asarray(s["counts"]),
                              np.asarray(s["sample"]),
                              int(s["sample_n"]), salts, k=2)
    assert [k for k, _ in hh] == [77, -5], hh
    # decay halves heat (floor), reset zeroes it
    dec = sk_mod.decay(s, 0.5)
    assert int(sk_mod.estimate(np.asarray(dec["counts"]), [77],
                               salts)[0]) <= (300 + bound) // 2 + 1
    assert not np.asarray(sk_mod.decay(s, 0.0)["counts"]).any()


# ---------------------------------------------------------------------------
# the parity contract: telemetry on vs off, chunk path, jnp + interpret
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("impl", ["ref", "interpret"])
def test_chunk_parity_telemetry_on_off(impl, counting_workflow):
    """With the sketch enabled, tables / queues / outputs of the jitted
    chunk path are bitwise identical to the untelemetered run — the
    sketch is pure extra state the tick never reads."""
    from repro.core.engine import Engine, EngineConfig, stack_sources
    from tests.conftest import make_batch

    rng = np.random.default_rng(3)
    srcs = [{"S1": make_batch(rng.integers(0, 40, 24),
                              rng.integers(0, 9, 24),
                              ts=np.full(24, t, np.int32))}
            for t in range(8)]

    def run(tc):
        eng = Engine(counting_workflow,
                     EngineConfig(batch_size=32, queue_capacity=128,
                                  telemetry=tc))
        state, outs, _ = eng.run_chunk(eng.init_state(),
                                       stack_sources(srcs), 8)
        return state, outs

    s0, o0 = run(None)
    s1, o1 = run(TelemetryConfig(width=256, impl=impl))
    for part in ("tables", "queues", "processed", "tick"):
        a, b = jax.device_get((s0[part], s1[part]))
        for la, lb in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
            assert np.array_equal(np.asarray(la), np.asarray(lb)), part
    for la, lb in zip(jax.tree.leaves(jax.device_get(o0)),
                      jax.tree.leaves(jax.device_get(o1))):
        assert np.array_equal(np.asarray(la), np.asarray(lb))


def test_chunk_sketch_backends_agree():
    """The sketch itself is backend-independent through the chunk."""
    from repro.core.engine import Engine, EngineConfig, stack_sources
    from repro.core.workflow import Workflow
    from tests.conftest import (CountingUpdater, PassThroughMapper,
                                make_batch)

    rng = np.random.default_rng(5)
    srcs = [{"S1": make_batch(rng.integers(0, 40, 24),
                              ts=np.full(24, t, np.int32))}
            for t in range(6)]
    sketches = []
    for impl in ("ref", "interpret"):
        wf = Workflow([PassThroughMapper(), CountingUpdater()],
                      external_streams=("S1",))
        eng = Engine(wf, EngineConfig(
            batch_size=32, queue_capacity=128,
            telemetry=TelemetryConfig(width=256, impl=impl)))
        state, _, _ = eng.run_chunk(eng.init_state(),
                                    stack_sources(srcs), 6)
        sketches.append(jax.device_get(state["sketch"]))
    a, b = sketches
    for la, lb in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        assert np.array_equal(np.asarray(la), np.asarray(lb))


# ---------------------------------------------------------------------------
# controller hysteresis (pure, tier-1)
# ---------------------------------------------------------------------------

def _rep(pressure, hh=()):
    p = np.asarray(pressure, np.float64)
    z = np.zeros_like(p)
    return TelemetryReport(
        tick=0, ticks=1, n_shards=len(p), active=list(range(len(p))),
        events=p * 32, events_per_tick=p * 32, queue_depth=z.copy(),
        queue_peak_delta=z.copy(), dropped_delta=z.copy(),
        occupancy=z.copy(), pressure=p, heavy_hitters=list(hh),
        migration_pause_s=0.0)


def test_controller_square_wave_does_not_flap():
    """A load square wave faster than the dwell produces zero actions:
    one-window spikes are noise by definition."""
    ctl = LoadAutoscaler(high=0.75, low=0.25, dwell=2, cooldown=2)
    acts = [ctl.decide(_rep([1.0, 1.0] if i % 2 == 0 else [0.05, 0.05]),
                       n_active=2, limit=8)
            for i in range(12)]
    assert all(a is None for a in acts), acts


def test_controller_scale_up_down_with_cooldown():
    ctl = LoadAutoscaler(high=0.75, low=0.25, dwell=2, cooldown=2,
                         min_shards=1)
    assert ctl.decide(_rep([1.0] * 2), n_active=2, limit=8) is None
    up = ctl.decide(_rep([1.0] * 2), n_active=2, limit=8)
    assert up is not None and up.kind == "scale" and up.target == 4
    # cooldown: two windows of silence even under sustained pressure
    assert ctl.decide(_rep([1.0] * 4), n_active=4, limit=8) is None
    assert ctl.decide(_rep([1.0] * 4), n_active=4, limit=8) is None
    up2 = ctl.decide(_rep([1.0] * 4), n_active=4, limit=8)
    assert up2 is not None and up2.target == 8
    # limit caps growth: no action when already at the ceiling
    ctl2 = LoadAutoscaler(high=0.75, dwell=1, cooldown=0)
    assert ctl2.decide(_rep([2.0] * 8), n_active=8, limit=8) is None
    # scale down needs the low watermark to *persist* too
    ctl3 = LoadAutoscaler(high=0.75, low=0.25, dwell=2, cooldown=0,
                          min_shards=2)
    assert ctl3.decide(_rep([0.05] * 4), n_active=4, limit=8) is None
    down = ctl3.decide(_rep([0.05] * 4), n_active=4, limit=8)
    assert down is not None and down.kind == "scale" and down.target == 2
    # min_shards floors the shrink
    ctl3.reset()
    for _ in range(4):
        a = ctl3.decide(_rep([0.01] * 2), n_active=2, limit=8)
        assert a is None


def test_controller_skew_prefers_split_and_heat_weights():
    """A single dominating key triggers split (scaling cannot shed
    it); heat_weights discounts the heavy hitter's irreducible mass."""
    ctl = LoadAutoscaler(high=0.5, dwell=1, cooldown=0, skew=0.5)
    rep = _rep([1.2, 0.1], hh=[(7, 100, 0.8)])
    a = ctl.decide(rep, n_active=2, limit=2)
    assert a is not None and a.kind == "split" and a.keys == (7,)
    # can_split=False (durable runs): the skew branch is skipped BEFORE
    # consuming streaks/cooldown, so scale still fires on pressure
    ctl2 = LoadAutoscaler(high=0.5, dwell=1, cooldown=0, skew=0.5)
    a2 = ctl2.decide(rep, n_active=2, limit=8, can_split=False)
    assert a2 is not None and a2.kind == "scale" and a2.target == 4
    # a key that is already split must not re-fire split forever —
    # sustained pressure escalates to scale instead
    ctl3 = LoadAutoscaler(high=0.5, dwell=1, cooldown=0, skew=0.5)
    a3 = ctl3.decide(rep, n_active=2, limit=8, already_split=(7,))
    assert a3 is not None and a3.kind == "scale", a3
    # heat weights: shard 0 hot purely from key 7 -> after discounting
    # it, both shards look alike and weights stay near-neutral
    rep2 = _rep([1.0, 1.0])
    rep2.events = np.array([132.0, 32.0])
    rep2.heavy_hitters = [(7, 100, 0.6)]
    w = ctl.heat_weights(rep2, owners=lambda ks: np.zeros(len(ks), int))
    assert abs(w[0] - w[1]) < 0.02, w
    # without the discount the hot shard would shed hard
    w2 = ctl.heat_weights(rep2, owners=None)
    assert w2[0] < w2[1], w2


def test_controller_rebalance_on_imbalance():
    ctl = LoadAutoscaler(high=5.0, low=0.0, dwell=1, cooldown=0,
                         rebalance_ratio=2.0)
    a = ctl.decide(_rep([1.0, 0.2, 0.2, 0.2]), n_active=4, limit=4)
    assert a is not None and a.kind == "rebalance", a


def test_controller_pause_sized_cooldown():
    """pause_factor stretches the post-action cooldown to cover the
    observed migration pause, measured in window wall-time units — a
    host-path migration that stalls the stream for 5 windows' worth of
    time earns a ~10-window sit-out at factor 2, while the device
    path's millisecond pauses keep the configured floor."""
    ctl = LoadAutoscaler(high=0.75, dwell=1, cooldown=1,
                         pause_factor=2.0)
    rep = _rep([1.0] * 2)
    rep.migration_pause_s = 5.0
    rep.window_s = 1.0
    a = ctl.decide(rep, n_active=2, limit=16)
    assert a is not None and a.kind == "scale"
    # ceil(2 * 5s / 1s) = 10 silent windows despite cooldown=1
    for _ in range(10):
        assert ctl.decide(rep, n_active=4, limit=16) is None
    a2 = ctl.decide(rep, n_active=4, limit=16)
    assert a2 is not None and a2.target == 8
    # a millisecond (device-path) pause keeps the configured floor
    ctl2 = LoadAutoscaler(high=0.75, dwell=1, cooldown=1,
                          pause_factor=2.0)
    rep2 = _rep([1.0] * 2)
    rep2.migration_pause_s = 0.001
    rep2.window_s = 1.0
    assert ctl2.decide(rep2, n_active=2, limit=16) is not None
    assert ctl2.decide(rep2, n_active=4, limit=16) is None
    assert ctl2.decide(rep2, n_active=4, limit=16) is not None


def test_registry_window_wall_clock_and_bytes_ema():
    """note_pause carries bytes alongside seconds, and observe_raw
    stamps the wall-clock span between readings (the denominator the
    controller sizes its pause cooldown with)."""
    import time as _time
    from repro.telemetry.metrics import MetricsRegistry
    reg = MetricsRegistry(TelemetryConfig(alpha=1.0), batch_size=32)
    kw = dict(queue_depth=[0.0], queue_peak=[0.0], dropped=[0.0],
              occupancy=[0.0], active=[0])
    rep0 = reg.observe_raw(tick=0, events=[0.0], **kw)
    assert rep0.window_s == 0.0              # no previous reading
    reg.note_pause(1.5, bytes_moved=4096)
    _time.sleep(0.02)
    rep1 = reg.observe_raw(tick=4, events=[64.0], **kw)
    assert rep1.window_s >= 0.02
    assert rep1.migration_pause_s == pytest.approx(1.5)
    assert rep1.migration_bytes_moved == pytest.approx(4096.0)
    assert rep1.to_dict()["migration_bytes_moved"] == \
        pytest.approx(4096.0)


def test_heat_weights_multi_updater_owner_rows():
    """heat_owners-shaped [n_updaters, K] owner maps: the sketch
    counted a hitter once per subscribing updater's dequeue, so its
    mass splits evenly across rows — two rows pinning key 7 to shard 0
    must discount exactly est, not 2*est."""
    ctl = LoadAutoscaler(skew=0.5)
    rep = _rep([1.0, 1.0])
    rep.events = np.array([132.0, 32.0])
    rep.heavy_hitters = [(7, 100, 0.6)]
    w = ctl.heat_weights(
        rep, owners=lambda ks: np.zeros((2, len(ks)), int))
    assert abs(w[0] - w[1]) < 0.02, w        # 132 - 2*(100/2) == 32
    # one row behaves exactly like the 1-D map
    w1 = ctl.heat_weights(
        rep, owners=lambda ks: np.zeros((1, len(ks)), int))
    w1d = ctl.heat_weights(
        rep, owners=lambda ks: np.zeros(len(ks), int))
    assert np.allclose(w1, w1d)


# ---------------------------------------------------------------------------
# front door (tier-1, single device)
# ---------------------------------------------------------------------------

def test_front_door_app_telemetry():
    from repro import (App, EventBatch, LoadAutoscaler, RuntimeConfig,
                       TelemetryConfig, ops)

    app = App("tele")
    s1 = app.source("S1", {"x": ((), jnp.int32)})
    s1.update(ops.counter("U1"))

    def src(t, _mx):
        keys = np.full(16, 3, np.int32)      # one hot key
        keys[:4] = np.arange(4)
        return {"S1": EventBatch.of(
            key=keys, value={"x": np.ones(16, np.int32)},
            ts=np.full(16, t, np.int32))}

    app.run(src, 8, runtime=RuntimeConfig(
        batch_size=16, chunk_size=2,
        telemetry=TelemetryConfig(width=256, window=2, impl="ref")))
    rep = app.telemetry()
    assert rep.events.sum() > 0
    assert rep.heavy_hitters and rep.heavy_hitters[0][0] == 3
    assert rep.pressure.shape == (1,)
    app.close()

    # config plumbing: LoadAutoscaler is distributed-only
    pol = LoadAutoscaler()
    assert RuntimeConfig(shards=2, autoscale=pol).dist_config() \
        .autoscale is pol
    with pytest.raises(ValueError, match="distributed"):
        RuntimeConfig(shards=1, autoscale=pol).engine_config()
    with pytest.raises(TypeError, match="TelemetryConfig"):
        RuntimeConfig(telemetry=object()).engine_config()


def test_registry_observe_raw_windows():
    """The engine-agnostic core: cumulative counters in, windowed
    deltas + EMA out; counter resets never read as negative load."""
    from repro.telemetry.metrics import MetricsRegistry
    reg = MetricsRegistry(TelemetryConfig(alpha=1.0), batch_size=32)
    kw = dict(queue_depth=[0.0], queue_peak=[0.0], dropped=[0.0],
              occupancy=[0.0], active=[0])
    reg.observe_raw(tick=0, events=[0.0], **kw)
    rep = reg.observe_raw(tick=4, events=[256.0], **kw)
    assert rep.ticks == 4 and rep.events[0] == 256.0
    assert rep.pressure[0] == pytest.approx(256 / 4 / 32)
    # a counter that went backwards (migration reset) clips to zero
    rep2 = reg.observe_raw(tick=8, events=[100.0], **kw)
    assert rep2.events[0] == 0.0 and rep2.pressure[0] == 0.0
    reg.note_pause(2.0)
    rep3 = reg.observe_raw(tick=12, events=[200.0], **kw)
    assert rep3.migration_pause_s > 0.0
    assert rep3.to_dict()["pressure"] == list(rep3.pressure)


# ---------------------------------------------------------------------------
# source-index / engine-tick decoupling in the distributed durable path
# ---------------------------------------------------------------------------

def test_run_span_decouples_source_index_from_engine_tick(tmp_path):
    """Flush-barrier drain ticks must not consume source indices: the
    two-hop workflow forces >= 1 drain tick per flush, yet source_fn
    sees exactly 0..n-1 and the frontier meta records the source
    cursor (the single-shard contract, ported)."""
    from jax.sharding import Mesh
    from repro.core.distributed import DistConfig, DistributedEngine
    from repro.core.durability import DurabilityConfig
    from repro.core.workflow import Workflow
    from repro.slates.flush import FlushConfig, FlushPolicy
    from tests.conftest import CountingUpdater, PassThroughMapper
    from tests.conftest import make_batch

    mesh = Mesh(np.asarray(jax.devices()[:1]), ("data",))
    wf = Workflow([PassThroughMapper(), CountingUpdater()],
                  external_streams=("S1",))
    cfg = DistConfig(batch_size=32, queue_capacity=128,
                     durability=DurabilityConfig(
                         dir=str(tmp_path),
                         flush=FlushConfig(policy=FlushPolicy.EVERY_K,
                                           every_k=3)))
    eng = DistributedEngine(wf, mesh, cfg)
    fed = []

    def src(t, _mx):
        fed.append(t)
        b = make_batch(np.arange(8) + t, ts=np.full(8, t, np.int32))
        return {"S1": jax.tree.map(lambda x: x[None], b)}

    state, _ = eng.run(eng.init_state(), src, 9)
    assert fed == list(range(9)), fed
    assert eng.tick_cursor == 9
    eng_tick = int(np.asarray(jax.device_get(state["tick"])).max())
    assert eng_tick > 9          # drain ticks happened, engine-side only
    assert eng.dur.frontier.meta["source_tick"] in (6, 9)
    # WAL records keyed by engine tick: unique and gap-tolerant
    tks = [tk for tk, _ in eng.dur.wals[0].replay(from_offset=0)]
    assert len(tks) == len(set(tks)) == 9
    assert max(tks) > 8          # post-drain ticks keyed past the gap
    eng.close()


# ---------------------------------------------------------------------------
# multi-shard closed loop + actuators (subprocess)
# ---------------------------------------------------------------------------

def test_rebalance_window_rebase_back_to_back():
    """Controller-style back-to-back rebalance(): the first migrates,
    the second sees the rebased (empty) window and no-op skips."""
    out = run_sub("""
        mesh = Mesh(np.array(jax.devices()[:4]), ('data',))
        wf = Workflow([Counter()], external_streams=('S1',))
        eng = DistributedEngine(wf, mesh, DistConfig(
            batch_size=32, queue_capacity=1024, exchange_slack=16.0))
        state = eng.init_state()
        hot = np.full(128, 7, np.int32)
        for t in range(6):
            state, _ = eng.step(state, {'S1': gb(
                hot, np.ones(128, np.float32), t, 4)})
        state, rep1 = eng.rebalance(state)
        assert rep1 is not None
        counts = eng.ring.vnode_counts().copy()
        state, rep2 = eng.rebalance(state)
        assert rep2 is None, rep2
        assert np.array_equal(counts, eng.ring.vnode_counts())
        print('REBASE-OK')
    """, devices=4)
    assert "REBASE-OK" in out


def test_split_keys_runtime_exact_counts():
    """split_keys spreads a heavy hitter over primary + secondary,
    read_slate merges the partials exactly, and clear_split converges
    them back onto the owner — all without recompiling."""
    out = run_sub("""
        from repro.core.distributed import _salt
        from repro.core.hashing import route, route_secondary
        from repro.telemetry import TelemetryConfig
        mesh = Mesh(np.array(jax.devices()[:4]), ('data',))
        wf = Workflow([Counter()], external_streams=('S1',))
        eng = DistributedEngine(wf, mesh, DistConfig(
            batch_size=64, queue_capacity=2048, exchange_slack=16.0,
            hot_key_capacity=8, telemetry=TelemetryConfig(width=256)))
        state = eng.init_state()
        hot = np.full(64, 7, np.int32)
        xs = np.ones(64, np.float32)
        for t in range(3):
            state, _ = eng.step(state, {'S1': gb(hot, xs, t, 4)})
        step_obj = eng._step
        state, _ = eng.split_keys(state, [7])
        assert eng.split_key_set() == [7]
        for t in range(3, 9):
            state, _ = eng.step(state, {'S1': gb(hot, xs, t, 4)})
        assert eng._step is step_obj          # no recompilation
        for _ in range(20):
            state = eng._step_empty(state)
        rh, rs = eng.ring.table()
        k7 = jnp.asarray([7], jnp.int32)
        p = int(route(k7, _salt('U1'), rh, rs)[0])
        s = int(route_secondary(k7, _salt('U1'), rh, rs)[0])
        tb = state['tables']['U1']
        occ = [int(jax.device_get((tb.keys[i] != -1).sum()))
               for i in range(4)]
        assert p != s and occ[p] >= 1 and occ[s] >= 1, (p, s, occ)
        total = eng.read_slate(state, 'U1', 7)
        assert int(total['count']) == 64 * 9, total
        state, rep = eng.clear_split(state)
        assert not eng.split_key_set()
        total2 = eng.read_slate(state, 'U1', 7)
        assert int(total2['count']) == 64 * 9, total2
        occ2 = [int(jax.device_get(
            (state['tables']['U1'].keys[i] != -1).sum()))
            for i in range(4)]
        assert occ2[s] == 0, occ2             # partials converged
        print('SPLIT-OK')
    """, devices=4)
    assert "SPLIT-OK" in out


_CLOSED_LOOP = """
    from repro.telemetry import LoadAutoscaler, TelemetryConfig
    G = %(G)d                     # global events per tick
    LOW, HIGH = %(low)d, %(high)d  # active-shard band
    def feed(t):
        rng = np.random.default_rng(t)
        keys = rng.integers(0, 48, G).astype(np.int32)
        xs = rng.integers(0, 9, G).astype(np.float32)
        hi = (t // 15) %% 2 == 0   # square wave, period 30
        n = G if hi else G // 10
        return keys, xs, np.arange(G) < n
    def gbv(keys, xs, valid, t, n_sh):
        shp = lambda a: a.reshape(n_sh, -1)
        return EventBatch(sid=jnp.zeros(shp(keys).shape, jnp.int32),
                          ts=jnp.full(shp(keys).shape, t, jnp.int32),
                          key=jnp.asarray(shp(keys)),
                          value={'x': jnp.asarray(shp(xs))},
                          valid=jnp.asarray(shp(valid)))
    def run(ctl, shards, n_ticks=60):
        mesh = Mesh(np.array(jax.devices()[:shards]), ('data',))
        wf = Workflow([Counter()], external_streams=('S1',))
        eng = DistributedEngine(wf, mesh, DistConfig(
            batch_size=G // LOW, queue_capacity=4 * G,
            fused=%(fused)r, exchange_slack=8.0,
            telemetry=TelemetryConfig(width=256, alpha=1.0),
            autoscale=ctl))
        state = eng.init_state()
        trace = []
        def src(t, _mx):
            trace.append(len(eng.active_shards))
            return {'S1': gbv(*feed(t), t, eng.n_shards)}
        state, _ = eng.run(state, src, n_ticks)
        state, _ = eng.drain(state)
        return eng, state, trace
    ctl = LoadAutoscaler(high=0.75, low=0.25, window=3, dwell=2,
                         cooldown=1, min_shards=LOW, max_shards=HIGH)
    eng, state, trace = run(ctl, LOW)
    assert trace[0] == LOW and max(trace) == HIGH, trace
    assert trace[-1] == LOW, trace      # shrank back after the wave
    flips = sum(1 for a, b in zip(trace, trace[1:]) if a != b)
    assert flips <= 5, (flips, trace)   # hysteresis: no flapping
    # bitwise parity vs an untelemetered fixed-HIGH run
    engf, statef, _ = run(None, HIGH)
    for k in range(48):
        a = eng.read_slate(state, 'U1', k)
        b = engf.read_slate(statef, 'U1', k)
        assert (a is None) == (b is None), k
        if a is not None:
            assert int(a['count']) == int(b['count']), (k, a, b)
            assert np.float32(a['sum']).tobytes() == \\
                np.float32(b['sum']).tobytes(), k
    print('CLOSED-LOOP-OK', trace)
"""


def test_closed_loop_square_wave_2to4_fast():
    """Tier-1 twin of the acceptance wave: a square-wave load drives
    the LoadAutoscaler 2 -> 4 shards at the high watermark and back to
    2 after cooldown, with slate parity against a fixed-4 run."""
    out = run_sub(_CLOSED_LOOP % {"G": 64, "low": 2, "high": 4,
                                  "fused": "off"}, devices=4)
    assert "CLOSED-LOOP-OK" in out


@pytest.mark.slow
@pytest.mark.parametrize("fused", ["jnp", "interpret"])
def test_closed_loop_square_wave_4to8(fused):
    """The acceptance bar: square-wave load, 4 -> 8 shards at the high
    watermark, back to 4 after cooldown, bitwise slate parity with an
    untelemetered fixed-8 run — on both fused backends."""
    out = run_sub(_CLOSED_LOOP % {"G": 128, "low": 4, "high": 8,
                                  "fused": fused}, devices=8)
    assert "CLOSED-LOOP-OK" in out
