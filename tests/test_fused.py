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
    a = ops.slate_update(jnp.asarray(keys), jnp.asarray(deltas),
                         jnp.asarray(slots), jnp.asarray(table),
                         impl="interpret")
    b = ops.slate_update(jnp.asarray(keys), jnp.asarray(deltas),
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
    a = ops.slate_update(*args, impl="interpret", op=op)
    b = ops.slate_update(*args, impl="ref", op=op)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


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
    ref = ops.slate_update(*args, impl="ref")
    assert ref.shape == (C, D)


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
