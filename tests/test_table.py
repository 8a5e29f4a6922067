import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.slates import table as tbl

SPEC = {"v": ((), jnp.float32)}


def test_insert_lookup_roundtrip():
    t = tbl.make_table(64, SPEC)
    keys = jnp.asarray([7, 13, 99], jnp.int32)
    t, slot, found, placed = tbl.insert_or_find(t, keys,
                                                jnp.ones(3, bool))
    assert bool(placed.all()) and not bool(found.any())
    t = tbl.write_slates(t, slot, placed,
                         {"v": jnp.asarray([1., 2., 3.])}, 0)
    slot2, found2 = tbl.lookup(t, keys)
    assert bool(found2.all())
    assert np.allclose(np.asarray(t.vals["v"])[np.asarray(slot2)],
                       [1., 2., 3.])


def test_missing_key_gets_insertion_point():
    t = tbl.make_table(32, SPEC)
    slot, found = tbl.lookup(t, jnp.asarray([5], jnp.int32))
    assert not bool(found[0]) and int(slot[0]) >= 0


def test_ttl_expiry():
    t = tbl.make_table(32, SPEC)
    keys = jnp.asarray([1, 2], jnp.int32)
    t, slot, _, placed = tbl.insert_or_find(t, keys, jnp.ones(2, bool))
    t = tbl.write_slates(t, slot, placed, {"v": jnp.asarray([1., 2.])},
                         tick=0)
    # touch key 1 at tick 50
    t, slot1, _, p1 = tbl.insert_or_find(t, jnp.asarray([1], jnp.int32),
                                         jnp.ones(1, bool))
    t = tbl.write_slates(t, slot1, p1, {"v": jnp.asarray([9.])}, tick=50)
    t = tbl.expire_ttl(t, now=60, ttl=30)
    _, found = tbl.lookup(t, keys)
    assert bool(found[0]) and not bool(found[1])   # 2 expired, 1 alive


def test_read_slates_initializes_missing():
    t = tbl.make_table(32, SPEC)
    keys = jnp.asarray([4], jnp.int32)
    t, slot, found, placed = tbl.insert_or_find(t, keys, jnp.ones(1, bool))
    init = lambda n: {"v": jnp.full((n,), 7.0)}
    vals = tbl.read_slates(t, slot, found, init)
    assert float(vals["v"][0]) == 7.0   # fresh slate initialized


@settings(max_examples=25, deadline=None)
@given(st.sets(st.integers(0, 10_000), min_size=1, max_size=200))
def test_no_key_lost_under_load(keys):
    """Property: unique keys inserted below ~50% load factor all land."""
    cap = max(512, 4 * len(keys))
    t = tbl.make_table(cap, SPEC)
    karr = jnp.asarray(sorted(keys), jnp.int32)
    t, slot, found, placed = tbl.insert_or_find(
        t, karr, jnp.ones(len(keys), bool))
    assert bool(placed.all())
    assert int(t.dropped) == 0
    slot2, found2 = tbl.lookup(t, karr)
    assert bool(found2.all())
    # slots are unique
    assert len(np.unique(np.asarray(slot2))) == len(keys)


def test_dropped_counted_when_full():
    t = tbl.make_table(8, SPEC)  # tiny
    keys = jnp.arange(64, dtype=jnp.int32)
    t, slot, found, placed = tbl.insert_or_find(t, keys,
                                                jnp.ones(64, bool))
    assert int(t.dropped) > 0
    assert int(placed.sum()) <= 8


# ---------------------------------------------------------------------------
# insert_or_find against the unconditional-rounds batch insert
# ---------------------------------------------------------------------------

X64 = bool(jax.config.jax_enable_x64)


def _insert_or_find_all_rounds(table, query, valid):
    """The oracle: INSERT_ROUNDS claim rounds run unconditionally, each a
    lookup, a claim scatter and an owner read-back.  Returns (keys,
    slot, found, placed, dropped)."""
    keys_arr = table.keys
    slot = jnp.full(query.shape, -1, jnp.int32)
    placed = jnp.zeros(query.shape, bool)
    found = jnp.zeros(query.shape, bool)
    pending = valid
    for _ in range(tbl.INSERT_ROUNDS):
        cand_slot, cand_found = tbl._lookup_keys(keys_arr, query,
                                                 table.capacity)
        want = pending & (cand_slot >= 0)
        safe_slot = jnp.where(want & ~cand_found, cand_slot, table.capacity)
        keys_try = keys_arr.at[safe_slot].set(query, mode="drop")
        owner_ok = keys_try[jnp.clip(cand_slot, 0,
                                     table.capacity - 1)] == query
        success = want & (cand_found | owner_ok)
        slot = jnp.where(success, cand_slot, slot)
        found = found | (want & cand_found)
        placed = placed | success
        pending = pending & ~success
        keys_arr = keys_try
    dropped = table.dropped + jnp.sum(pending, dtype=jnp.int32)
    return keys_arr, slot, found, placed, dropped


_oracle = jax.jit(_insert_or_find_all_rounds)
_insert = jax.jit(tbl.insert_or_find)
BATCH = 64


def _fresh_keys(rng, n, taken, dtype):
    """``n`` distinct keys outside ``taken``; int64 keys lie beyond the
    int32 band."""
    lift = (1 << 33) if dtype == jnp.int64 else 0
    out = []
    while len(out) < n:
        k = int(rng.integers(0, 1 << 30)) + lift
        if k not in taken:
            taken.add(k)
            out.append(k)
    return out


def _filled(cap, n, rng, dtype, ttl_freed=False):
    """A table holding ``n`` keys inserted in batches; with
    ``ttl_freed`` the first half is written at tick 0, the rest at tick
    100, and ``expire_ttl`` frees the first half again.  Returns
    (table, live keys, taken keys)."""
    t = tbl.make_table(cap, SPEC, key_dtype=dtype)
    taken, live = set(), []
    for i in range(0, n, BATCH):
        ks = _fresh_keys(rng, min(BATCH, n - i), taken, dtype)
        q = jnp.asarray(ks + [0] * (BATCH - len(ks)), dtype)
        valid = jnp.arange(BATCH) < len(ks)
        t, slot, _, placed = _insert(t, q, valid)
        tick = 0 if ttl_freed and i < n // 2 else 100
        t = tbl.write_slates(t, slot, placed,
                             {"v": jnp.zeros(BATCH, jnp.float32)}, tick)
        live += [k for k, p in zip(ks, np.asarray(placed)) if p]
    if ttl_freed:
        t = tbl.expire_ttl(t, now=110, ttl=50)
        live = live[len(live) // 2:]
    return t, live, taken


def _colliding(cap, n, taken, dtype, rng, probes):
    """``n`` new keys whose first ``probes`` probe slots agree; at two
    they agree in every probe (double hashing: same start, same step)."""
    lift = (1 << 33) if dtype == jnp.int64 else 0
    cand = rng.choice(1 << 22, size=1 << 16, replace=False) + lift
    seq = np.asarray(tbl._probe_seq(jnp.asarray(cand, dtype), cap))
    code = sum(seq[p].astype(np.int64) * cap ** p for p in range(probes))
    vals, counts = np.unique(code, return_counts=True)
    pick = cand[code == vals[counts.argmax()]]
    ks = [int(k) for k in pick if int(k) not in taken][:n]
    assert len(ks) == n
    taken.update(ks)
    return ks


def _hidden_key(dtype, rng):
    """A 64-slot table whose key ``k`` sits at its fifth probe slot
    behind four TTL-freed holes, and a batch of ``k`` and four keys with
    its probe sequence.  ``k`` comes first, so the later writer of each
    claim beats it: it loses all INSERT_ROUNDS claim rounds and is
    dropped, though one more lookup would find it."""
    cap, taken = 64, set()
    g = _colliding(cap, 9, taken, dtype, rng, probes=2)
    t = tbl.make_table(cap, SPEC, key_dtype=dtype)
    first = jnp.arange(BATCH) == 0
    for i, k in enumerate(g[:5]):
        t, slot, _, placed = _insert(t, jnp.full(BATCH, k, dtype), first)
        t = tbl.write_slates(t, slot, placed,
                             {"v": jnp.zeros(BATCH, jnp.float32)},
                             0 if i < 4 else 100)
    t = tbl.expire_ttl(t, now=110, ttl=50)
    q = jnp.asarray(g[4:] + [g[4]] * (BATCH - 5), dtype)
    return t, q, jnp.arange(BATCH) < 5


# (table capacity, keys loaded): empty, 12.5%, near the 8-probe failure
# point, half loaded then half freed by TTL, a small table for keys of
# one probe sequence, and a tiny table whose probe budget runs out
LOADS = {"empty": (1024, 0), "eighth": (1024, 128),
         "near_full": (1024, 704), "ttl_freed": (1024, 512),
         "twins": (64, 0), "tiny": (8, 0)}
CASES = [(load, batch) for load in ("empty", "eighth", "near_full",
                                    "ttl_freed")
         for batch in ("found", "new", "mixed", "race")
         if not (load == "empty" and batch in ("found", "mixed"))]
CASES += [("twins", "race"), ("twins", "hidden"), ("tiny", "new")]


@pytest.mark.parametrize("dtype", [
    jnp.int32,
    pytest.param(jnp.int64, marks=pytest.mark.skipif(
        not X64, reason="int64 keys need JAX_ENABLE_X64=1 (x64 CI lane)"))],
    ids=["int32", "int64"])
@pytest.mark.parametrize("load,batch", CASES)
def test_insert_or_find_matches_all_rounds(load, batch, dtype):
    """Claim rounds that run only while a key lacks a slot give, bit for
    bit, the slot, found and placed masks, keys array and drop count of
    INSERT_ROUNDS unconditional rounds; a batch of present keys runs no
    claim round."""
    cap, n = LOADS[load]
    for seed in range(3):
        rng = np.random.default_rng(1000 * seed + len(load) + len(batch))
        t, live, taken = _filled(cap, n, rng, dtype,
                                 ttl_freed=load == "ttl_freed")
        n_live = min(len(live), BATCH)
        valid = jnp.asarray(rng.random(BATCH) < 0.9)
        if batch == "found":
            ks = list(rng.choice(live, size=n_live, replace=False))
        elif batch == "mixed":
            ks = (list(rng.choice(live, size=n_live // 2, replace=False))
                  + _fresh_keys(rng, BATCH - n_live // 2, taken, dtype))
        elif batch == "race" and load == "twins":
            ks = _colliding(cap, 8, taken, dtype, rng, probes=2)
            ks += _fresh_keys(rng, BATCH - len(ks), taken, dtype)
            valid = jnp.arange(BATCH) < 16        # 8 twins, 8 others
        elif batch == "race":
            ks = _colliding(cap, 16, taken, dtype, rng, probes=1)
            ks += _fresh_keys(rng, BATCH - len(ks), taken, dtype)
        else:
            ks = _fresh_keys(rng, BATCH, taken, dtype)
        if load == "ttl_freed" and batch == "new":
            ks[:8] = rng.choice(sorted(taken - set(live)), 8,
                                replace=False).tolist()  # expired keys
        assert len(ks) == BATCH
        q = jnp.asarray(ks if load == "twins"
                        else rng.permutation(np.asarray(ks)), dtype)
        if batch == "hidden":
            t, q, valid = _hidden_key(dtype, rng)
        keys, slot, found, placed, dropped = _oracle(t, q, valid)
        got, slot2, found2, placed2 = _insert(t, q, valid)
        np.testing.assert_array_equal(np.asarray(got.keys),
                                      np.asarray(keys))
        np.testing.assert_array_equal(np.asarray(slot2), np.asarray(slot))
        np.testing.assert_array_equal(np.asarray(found2),
                                      np.asarray(found))
        np.testing.assert_array_equal(np.asarray(placed2),
                                      np.asarray(placed))
        assert int(got.dropped) == int(dropped)
        rounds = int(got.claim_rounds) - int(t.claim_rounds)
        assert 0 <= rounds <= tbl.INSERT_ROUNDS
        if batch == "found" and load != "ttl_freed":
            # (behind a TTL hole a present key reads as missing)
            assert rounds == 0 and bool(found2[valid].all())
        if load == "twins":                # the rounds run out
            assert rounds == tbl.INSERT_ROUNDS and int(dropped) > 0
        if load == "tiny":     # spent probe budgets end the rounds early
            assert rounds < tbl.INSERT_ROUNDS and int(dropped) > 0
        if batch == "hidden":
            assert not bool(placed[0])
