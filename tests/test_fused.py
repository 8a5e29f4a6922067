"""Parity tests for the fused slate-update path (ISSUE 1 tentpole):
Pallas kernel (interpret) vs jnp oracle vs the generic apply path, on
Zipf-skewed and all-duplicate-key batches, plus the ``supported()``
guard and an engine-level fused run."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import apply as apply_mod
from repro.core import packing
from repro.core.engine import Engine, EngineConfig
from repro.core.event import EventBatch
from repro.core.workflow import Workflow
from repro.slates import table as tbl
from tests.conftest import CountingUpdater, PassThroughMapper, make_batch


class FusedCountingUpdater(CountingUpdater):
    """Counter with the packed-path capability declared."""
    sum_mergeable = True


def zipf_keys(rng, n, n_keys=40, alpha=1.2):
    ranks = np.arange(1, n_keys + 1, dtype=np.float64)
    p = ranks ** (-alpha)
    p /= p.sum()
    return rng.choice(n_keys, size=n, p=p).astype(np.int32)


def _table_state(impl, batch, capacity=256, n_batches=1, tick0=0):
    up = FusedCountingUpdater()
    table = tbl.make_table(capacity, up.slate_spec())
    for i in range(n_batches):
        table, ems, n = apply_mod.apply_associative(up, table, batch,
                                                    tick=tick0 + i,
                                                    impl=impl)
    return table, ems, n


@pytest.mark.parametrize("impl", ["jnp", "ref", "interpret"])
@pytest.mark.parametrize("case", ["zipf", "all_dup", "masked"])
def test_fused_matches_generic(impl, case):
    rng = np.random.default_rng(hash((impl, case)) % 2**31)
    if case == "zipf":
        keys = zipf_keys(rng, 96)
        valid = None
    elif case == "all_dup":
        keys = np.full(96, 7, np.int32)       # one giant run
        valid = None
    else:
        keys = zipf_keys(rng, 96)
        valid = (rng.random(96) > 0.3).tolist()
    xs = rng.integers(-40, 40, size=96).astype(np.int32)
    batch = make_batch(keys, xs, valid=valid)

    ref_t, ref_ems, ref_n = _table_state("off", batch, n_batches=3)
    got_t, got_ems, got_n = _table_state(impl, batch, n_batches=3)

    assert int(ref_n) == int(got_n)
    assert got_ems == {}
    assert np.array_equal(np.asarray(ref_t.keys), np.asarray(got_t.keys))
    assert np.array_equal(np.asarray(ref_t.vals["count"]),
                          np.asarray(got_t.vals["count"]))
    # f32 sums may differ in combine order, not value (ints here: exact)
    assert np.allclose(np.asarray(ref_t.vals["sum"]),
                       np.asarray(got_t.vals["sum"]), atol=1e-4)
    assert np.array_equal(np.asarray(ref_t.dirty), np.asarray(got_t.dirty))
    assert np.array_equal(np.asarray(ref_t.ts), np.asarray(got_t.ts))


def test_kernel_interpret_matches_ref_oracle():
    """kernel (interpret) vs kernels/slate_update/ref on a skewed batch,
    straight through the ops dispatcher."""
    from repro.kernels.slate_update import ops
    rng = np.random.default_rng(3)
    B, D, C = 128, 8, 256
    keys = np.sort(zipf_keys(rng, B)).astype(np.int32)
    deltas = rng.normal(size=(B, D)).astype(np.float32)
    run_last = np.concatenate([keys[1:] != keys[:-1], [True]])
    slots = np.where(run_last, (keys * 11 + 5) % C, -1).astype(np.int32)
    table = rng.normal(size=(C, D)).astype(np.float32)
    a, _ = ops.slate_update(jnp.asarray(keys), jnp.asarray(deltas),
                            jnp.asarray(slots), jnp.asarray(table),
                            impl="interpret")
    b, _ = ops.slate_update(jnp.asarray(keys), jnp.asarray(deltas),
                            jnp.asarray(slots), jnp.asarray(table),
                            impl="ref")
    assert np.abs(np.asarray(a) - np.asarray(b)).max() < 1e-4


@pytest.mark.parametrize("op", ["sum", "max"])
def test_kernel_multi_tile_runs_exact(op):
    """A batch of several kernel tiles (padded last tile) whose longest
    run crosses two tile edges, into a table whose capacity is not a
    whole number of 128-lane windows: integer-valued deltas make every
    combine order exact, so the kernel must equal the oracle bitwise."""
    from repro.kernels.slate_update import kernel, ops
    rng = np.random.default_rng(8)
    B, D, C = 2 * kernel.TILE_B + 452, 8, 1000
    keys = np.sort(np.concatenate([
        np.full(1500, 7), rng.integers(0, 300, B - 1500)])).astype(np.int32)
    deltas = rng.integers(0, 5, size=(B, D)).astype(np.float32)
    run_last = np.concatenate([keys[1:] != keys[:-1], [True]])
    slots = np.where(run_last, (keys * 7 + 3) % C, -1).astype(np.int32)
    table = rng.integers(0, 9, size=(C, D)).astype(np.float32)
    args = (jnp.asarray(keys), jnp.asarray(deltas), jnp.asarray(slots),
            jnp.asarray(table))
    a, _ = ops.slate_update(*args, impl="interpret", op=op)
    b, _ = ops.slate_update(*args, impl="ref", op=op)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def _model_stalls(slots):
    """The hazard rule, counted the plain way: per kernel tile, a live
    row (slot >= 0) stalls when its 128-lane window is that of one of
    the RING - 1 live rows before it in the tile."""
    from repro.kernels.slate_update import kernel
    n = 0
    for lo in range(0, len(slots), kernel.TILE_B):
        wins = [s // kernel.LANES for s in slots[lo:lo + kernel.TILE_B]
                if s >= 0]
        n += sum(w in wins[max(0, m - kernel.RING + 1):m]
                 for m, w in enumerate(wins))
    return n


def _live_batch(rng, live_slots, run_len=3):
    """Sorted keys whose run-last rows carry ``live_slots`` in row order
    (runs of 1..run_len rows), integer deltas (every combine order is
    exact) and ``slots`` -1 on the other rows."""
    runs = rng.integers(1, run_len + 1, len(live_slots))
    keys = np.repeat(np.arange(len(live_slots), dtype=np.int32), runs)
    slots = np.full(keys.size, -1, np.int32)
    slots[np.cumsum(runs) - 1] = live_slots
    return keys, slots


def _window_case(case, rng):
    """``(keys, slots, capacity, stalls)`` of one constructed batch; the
    stall count is counted by hand (None: left to ``_model_stalls``)."""
    from repro.kernels.slate_update import kernel
    K, L = kernel.RING, kernel.LANES
    if case == "one_window":          # every live row in window 0
        keys, slots = _live_batch(rng, rng.permutation(L)[:100])
        return keys, slots, 1000, 99
    if case == "lane_edges":          # lanes 127 | 0 of neighbouring windows
        live = [L - 1] + [s for w in range(1, 21) for s in (w * L,
                                                            w * L + L - 1)]
        keys, slots = _live_batch(rng, np.asarray(live))
        return keys, slots, 21 * L + 5, 20
    if case.startswith("ring_"):      # same-window rows d live rows apart
        d = {"ring_1": 1, "ring_K-1": K - 1, "ring_K": K,
             "ring_K+1": K + 1}[case]
        wins = np.arange(4 * K + 2)
        lanes = rng.integers(0, L - 1, wins.size)
        for j in (0, K + 1, 2 * K + 2):       # three pairs, one at row 0
            wins[j + d], lanes[j + d] = wins[j], lanes[j] + 1
        keys, slots = _live_batch(rng, wins * L + lanes)
        return keys, slots, wins.size * L, 3 if d < K else 0
    if case == "tile_edge":
        # runs of 2 up to row 999 (live rows 1, 3, .. 999), one run over
        # rows 1000..1100 across the tile edge at 1024, then runs of 1;
        # rows 999 (tile 0), 1100 and 1101 (tile 1) share a window, so
        # only row 1101 stalls: the hazard never crosses tiles
        B = 1400
        keys = np.concatenate([np.arange(1000) // 2,
                               np.full(101, 500),
                               501 + np.arange(B - 1101)]).astype(np.int32)
        last = np.concatenate([keys[1:] != keys[:-1], [True]])
        slots = np.full(B, -1, np.int32)
        live = np.nonzero(last)[0]
        slots[live] = (np.arange(live.size) + 1) * L + rng.integers(
            0, L, live.size)
        slots[999], slots[1100], slots[1101] = 5, 77, 126
        return keys, slots, (live.size + 1) * L, 1
    if case == "no_live":
        keys = np.sort(rng.integers(0, 50, 300)).astype(np.int32)
        return keys, np.full(300, -1, np.int32), 256, 0
    if case == "ragged_B":            # 1500 rows: two tiles, one padded
        keys = np.sort(zipf_keys(rng, 1500, n_keys=600)).astype(np.int32)
        last = np.concatenate([keys[1:] != keys[:-1], [True]])
        perm = rng.permutation(2048)
        slots = np.where(last, perm[keys], -1).astype(np.int32)
        return keys, slots, 2048, None
    raise ValueError(case)


WINDOW_CASES = ["one_window", "lane_edges", "ring_1", "ring_K-1",
                "ring_K", "ring_K+1", "tile_edge", "no_live", "ragged_B"]


@pytest.mark.parametrize("op", ["sum", "max"])
@pytest.mark.parametrize("case", WINDOW_CASES)
def test_kernel_window_cases_exact(case, op):
    """Batches aimed at the pipelined read-modify-writes: the kernel
    (interpret) equals the oracle bit for bit, and counts exactly the
    rows the hazard rule holds back — by hand and by the plain model."""
    from repro.kernels.slate_update import ops
    rng = np.random.default_rng(WINDOW_CASES.index(case))
    keys, slots, C, want = _window_case(case, rng)
    deltas = rng.integers(0, 5, size=(keys.size, 8)).astype(np.float32)
    table = rng.integers(0, 9, size=(C, 8)).astype(np.float32)
    args = (jnp.asarray(keys), jnp.asarray(deltas), jnp.asarray(slots),
            jnp.asarray(table))
    a, stalls = ops.slate_update(*args, impl="interpret", op=op)
    b, none = ops.slate_update(*args, impl="ref", op=op)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert int(none) == 0
    assert int(stalls) == _model_stalls(slots.tolist())
    if want is not None:
        assert int(stalls) == want


@pytest.mark.parametrize("op", ["sum", "max"])
def test_kernel_wide_keys_exact(op):
    """int64 keys whose low 32 bits collide reach the kernel as
    ``_segment_ids``: the same runs, bit for bit the oracle's table."""
    from repro.kernels.slate_update import ops
    rng = np.random.default_rng(11)
    with jax.enable_x64(True):
        base = np.sort(rng.integers(0, 40, 200))
        keys = np.where(base % 3 == 0, base, base + (1 << 32)).astype(
            np.int64)
        keys = np.sort(keys)
        last = np.concatenate([keys[1:] != keys[:-1], [True]])
        slots = np.where(last, (base * 13 + 1) % 512, -1).astype(np.int32)
        deltas = rng.integers(0, 5, size=(200, 8)).astype(np.float32)
        table = rng.integers(0, 9, size=(512, 8)).astype(np.float32)
        args = (jnp.asarray(keys), jnp.asarray(deltas), jnp.asarray(slots),
                jnp.asarray(table))
        assert args[0].dtype == jnp.int64
        a, _ = ops.slate_update(*args, impl="interpret", op=op)
        b, _ = ops.slate_update(*args, impl="ref", op=op)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("impl", ["interpret", "jnp", "ref"])
def test_table_window_stalls_count(impl):
    """``SlateTable.window_stalls`` adds the kernel's count: ten keys in
    a 128-slot table all share its one window, so each fused update of
    them holds back every live row after the first (9); the backends
    that move no windows leave it at 0."""
    up = FusedCountingUpdater()
    table = tbl.make_table(128, up.slate_spec())
    batch = make_batch(np.repeat(np.arange(10) * 11, 3))
    for tick in range(2):
        table, _, _ = apply_mod.apply_associative(up, table, batch,
                                                  tick=tick, impl=impl)
    assert int(table.dropped) == 0
    assert int(table.window_stalls) == (18 if impl == "interpret" else 0)


def test_unsupported_width_falls_back_to_ref():
    """D not lane-aligned -> supported() is False, and a dispatcher asked
    for the kernel raises instead of quietly serving the oracle; the
    oracle itself still takes the shape."""
    from repro.kernels.slate_update import kernel, ops
    rng = np.random.default_rng(4)
    B, D, C = 32, 5, 64                       # 5 % 8 != 0
    keys = np.sort(rng.integers(0, 10, B)).astype(np.int32)
    deltas = rng.normal(size=(B, D)).astype(np.float32)
    run_last = np.concatenate([keys[1:] != keys[:-1], [True]])
    slots = np.where(run_last, keys % C, -1).astype(np.int32)
    table = np.zeros((C, D), np.float32)
    assert not kernel.supported(jnp.asarray(deltas))
    args = (jnp.asarray(keys), jnp.asarray(deltas), jnp.asarray(slots),
            jnp.asarray(table))
    for impl in ("pallas", "interpret"):
        with pytest.raises(ValueError, match="D % 8"):
            ops.slate_update(*args, impl=impl)
    ref, stalls = ops.slate_update(*args, impl="ref")
    assert ref.shape == (C, D) and int(stalls) == 0


def test_pack_unpack_roundtrip():
    spec = packing.pack_spec({"count": ((), jnp.int32),
                              "vec": ((3,), jnp.float32)})
    assert spec.width == 4 and spec.padded_width == 8
    rng = np.random.default_rng(5)
    tree = {"count": jnp.asarray(rng.integers(0, 1000, 17), jnp.int32),
            "vec": jnp.asarray(rng.normal(size=(17, 3)), jnp.float32)}
    buf = packing.pack(tree, spec)
    assert buf.shape == (17, 8) and buf.dtype == jnp.float32
    back = packing.unpack(buf, spec)
    assert np.array_equal(np.asarray(back["count"]),
                          np.asarray(tree["count"]))
    assert np.array_equal(np.asarray(back["vec"]), np.asarray(tree["vec"]))
    # unpadded pack serves the jnp backend
    assert packing.pack(tree, spec, pad=False).shape == (17, 4)


def test_fused_engine_counting_exact():
    """Engine-level: the fused path produces the same slates as the
    generic path over a multi-tick pipelined run."""
    rng = np.random.default_rng(6)
    ticks = [(zipf_keys(rng, 24),
              rng.integers(0, 9, 24).astype(np.int32)) for _ in range(6)]

    def final_state(fused):
        wf = Workflow([PassThroughMapper(), FusedCountingUpdater()],
                      external_streams=("S1",))
        eng = Engine(wf, EngineConfig(batch_size=32, queue_capacity=128,
                                      fused=fused))
        state = eng.init_state()
        for t, (keys, xs) in enumerate(ticks):
            state, _ = eng.step(state, {"S1": make_batch(
                keys, xs, ts=[t] * 24)})
        for t in range(3):   # drain
            state, _ = eng.step(state, {"S1": make_batch(
                [0] * 24, valid=[False] * 24, ts=[90 + t] * 24)})
        return eng, state

    eng_a, st_a = final_state("off")
    eng_b, st_b = final_state("jnp")
    truth = {}
    for keys, xs in ticks:
        for k, x in zip(keys, xs):
            c, s = truth.get(int(k), (0, 0))
            truth[int(k)] = (c + 1, s + int(x))
    for k, (c, s) in truth.items():
        for eng, st in ((eng_a, st_a), (eng_b, st_b)):
            slate = eng.read_slate(st, "U1", k)
            assert slate is not None and int(slate["count"]) == c
            assert abs(float(slate["sum"]) - s) < 1e-3


@pytest.mark.parametrize("impl", ["jnp", "ref", "interpret"])
def test_fused_zeroes_reused_slots_after_ttl_expiry(impl):
    """expire_ttl frees a slot but keeps the dead occupant's values;
    the additive path must not fold them into the new key's slate."""
    up = FusedCountingUpdater()
    batch = make_batch([7])

    def count_after_reuse(path):
        table = tbl.make_table(64, up.slate_spec())
        table, _, _ = apply_mod.apply_associative(up, table, batch,
                                                  tick=0, impl=path)
        table = tbl.expire_ttl(table, now=10, ttl=2)
        table, _, _ = apply_mod.apply_associative(up, table, batch,
                                                  tick=11, impl=path)
        slot, found = tbl.lookup(table, jnp.asarray([7], jnp.int32))
        assert bool(found[0])
        return int(table.vals["count"][int(slot[0])])

    assert count_after_reuse("off") == 1
    assert count_after_reuse(impl) == 1


def test_fused_requires_matching_lift_structure():
    class BadLift(FusedCountingUpdater):
        def lift(self, batch):
            return {"only_count": jnp.ones_like(batch.key)}

    up = BadLift()
    table = tbl.make_table(64, up.slate_spec())
    with pytest.raises(TypeError):
        apply_mod.apply_associative(up, table, make_batch([1, 2, 3]),
                                    tick=0, impl="jnp")


def test_generic_path_untouched_for_non_mergeable():
    """A plain AssociativeUpdater never routes through the packed path,
    whatever the impl knob says."""
    up = CountingUpdater()
    assert not apply_mod.fused_eligible(up)
    table = tbl.make_table(64, up.slate_spec())
    t2, ems, n = apply_mod.apply_associative(up, table,
                                             make_batch([5, 5, 6]),
                                             tick=0, impl="ref")
    slot, found = tbl.lookup(t2, jnp.asarray([5, 6], jnp.int32))
    assert bool(found[0]) and bool(found[1])
    assert int(t2.vals["count"][int(slot[0])]) == 2
