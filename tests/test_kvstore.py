import dataclasses
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.event import EventBatch
from repro.slates import flush as flush_mod
from repro.slates import table as tbl
from repro.slates.flush import (Flusher, FlushConfig, FlushPolicy,
                                dirty_snapshot, restore_into)
from repro.slates.kvstore import TIER_BLOCKS, KVStore, newest
from repro.slates.wal import WriteAheadLog

SPEC = {"count": ((), jnp.int32)}


@pytest.fixture
def store(tmp_path):
    return KVStore(str(tmp_path / "kv"), replicas=3, write_quorum=2,
                   read_quorum=2)


def test_put_get_roundtrip(store):
    store.put("U1", 42, {"count": np.int32(7)}, ts=1)
    assert int(store.get("U1", 42)["count"]) == 7
    assert store.get("U1", 43) is None


def test_newest_ts_wins(store):
    store.put("U1", 1, {"count": np.int32(1)}, ts=5)
    store.put("U1", 1, {"count": np.int32(2)}, ts=9)
    assert int(store.get("U1", 1)["count"]) == 2


def test_quorum_survives_replica_failure(store):
    store.put("U1", 5, {"count": np.int32(3)}, ts=0)
    store.set_replica_down(1)
    assert int(store.get("U1", 5)["count"]) == 3
    store.put("U1", 6, {"count": np.int32(4)}, ts=1)   # still quorum-2
    assert int(store.get("U1", 6)["count"]) == 4


def test_write_quorum_failure_raises(store):
    store.set_replica_down(0)
    store.set_replica_down(1)
    with pytest.raises(IOError):
        store.put("U1", 7, {"count": np.int32(1)}, ts=0)
        store.flush()


def test_ttl_and_gc(store):
    store.put("U1", 9, {"count": np.int32(1)}, ts=0, ttl=5)
    assert store.get("U1", 9, now=3) is not None
    assert store.get("U1", 9, now=10) is None
    removed = store.gc("U1", now=10)
    assert removed >= 1


def test_scan_bulk_read(store):
    for k in range(20):
        store.put("U1", k, {"count": np.int32(k)}, ts=0)
    data = store.scan("U1")
    assert len(data) == 20
    assert int(data[13]["count"]) == 13


def test_flusher_and_crash_restore(store):
    t = tbl.make_table(64, SPEC)
    keys = jnp.asarray([3, 5], jnp.int32)
    t, slot, _, placed = tbl.insert_or_find(t, keys, jnp.ones(2, bool))
    t = tbl.write_slates(t, slot, placed,
                         {"count": jnp.asarray([30, 50], jnp.int32)}, 2)
    fl = Flusher(store, FlushConfig(policy=FlushPolicy.IMMEDIATE))
    t = fl.flush_table("U1", t)
    fl.drain()
    assert not fl.errors
    assert not bool(np.asarray(jax.device_get(t.dirty)).any())
    # crash -> empty table -> restore from store
    fresh = tbl.make_table(64, SPEC)
    data = store.scan("U1")
    ks = np.array(sorted(data), np.int32)
    vals = {"count": np.array([int(data[k]["count"]) for k in ks],
                              np.int32)}
    restored = restore_into(fresh, ks, vals, np.full(len(ks), 2))
    slot2, found = tbl.lookup(restored, keys)
    assert bool(found.all())
    got = np.asarray(jax.device_get(restored.vals["count"]))[
        np.asarray(slot2)]
    assert got.tolist() == [30, 50]
    fl.close()


def test_flush_policies():
    fl_cfg = FlushConfig(policy=FlushPolicy.EVERY_K, every_k=4)
    t = tbl.make_table(16, SPEC)

    class Dummy:
        cfg = fl_cfg
    f = Flusher.__new__(Flusher)
    f.cfg = fl_cfg
    assert f.should_flush(0, t) and f.should_flush(4, t)
    assert not f.should_flush(3, t)


def test_wal_append_replay(tmp_path):
    wal = WriteAheadLog(str(tmp_path / "wal.log"))
    b1 = EventBatch.of(key=np.asarray([1, 2], np.int32),
                       value={"x": np.asarray([5, 6], np.int32)})
    wal.append(0, {"S1": b1})
    wal.append(1, {"S1": b1})
    wal.append(2, {"S1": b1})
    wal.close()
    wal2 = WriteAheadLog(str(tmp_path / "wal.log"))
    records = list(wal2.replay(from_tick=1))
    assert [t for t, _ in records] == [1, 2]
    _, src = records[0]
    assert np.asarray(src["S1"].key).tolist() == [1, 2]
    assert np.asarray(src["S1"].value["x"]).tolist() == [5, 6]
    wal2.close()


def test_compression_on_disk(store, tmp_path):
    big = {"blob": np.zeros(4096, np.float32)}   # compressible
    store.put("U1", 1, big, ts=0)
    store.flush()
    total = 0
    for root, _, files in os.walk(str(tmp_path / "kv")):
        for fn in files:
            total += os.path.getsize(os.path.join(root, fn))
    assert total < 4096 * 4 * 3   # zstd beats raw x3 replicas easily


# ---------------------------------------------------------------------------
# the columnar store against a plain dict reference
# ---------------------------------------------------------------------------

class DictStore:
    """What the store must answer, kept as one dict per replica: key ->
    (ts, write order, ttl, slate), newest ``(ts, order)`` wins."""

    def __init__(self, replicas, write_quorum, read_quorum):
        self.reps = [dict() for _ in range(replicas)]
        self.wq, self.rq = write_quorum, read_quorum
        self.down = [False] * replicas
        self.pending = []
        self.order = 0

    def put(self, key, slate, ts, ttl):
        self.order += 1
        self.pending.append((key, (ts, self.order, ttl, slate)))

    def flush(self):
        alive = [r for r, d in zip(self.reps, self.down) if not d]
        for key, rec in self.pending:
            for r in alive:
                if key not in r or rec[:2] >= r[key][:2]:
                    r[key] = rec
        self.pending = []
        return len(alive) >= self.wq

    @staticmethod
    def _live(rec, now):
        ts, _, ttl, _ = rec
        return not (ttl and now is not None and now - ts > ttl)

    def get(self, key, now):
        alive = [r for r, d in zip(self.reps, self.down) if not d]
        if len(alive) < self.rq:
            return "IOError"
        recs = [r[key] for r in alive[:self.rq] if key in r]
        if not recs:
            return None
        best = max(recs, key=lambda rec: rec[:2])
        return best[3] if self._live(best, now) else None

    def scan(self, now):
        out = {}
        for r, d in zip(self.reps, self.down):
            if d:
                continue
            for k, rec in r.items():
                if k not in out or rec[:2] > out[k][:2]:
                    out[k] = rec
        return {k: (rec[0], rec[3]) for k, rec in out.items()
                if self._live(rec, now)}

    def gc(self, now):
        removed = 0
        for r, d in zip(self.reps, self.down):
            if d:
                continue
            dead = [k for k, rec in r.items() if not self._live(rec, now)]
            for k in dead:
                del r[k]
            removed += len(dead)
        return removed


def _slate(rng):
    return {"count": np.int32(rng.integers(0, 1000)),
            "v": rng.random(3).astype(np.float32)}


def _same(a, b):
    if a is None or b is None:
        return a is None and b is None
    return all(np.asarray(a[k]).tobytes() == np.asarray(b[k]).tobytes()
               for k in ("count", "v"))


def _check(store, ref, rng, now):
    for key in rng.integers(0, 48, 12).tolist():
        want = ref.get(key, now)
        if want == "IOError":
            with pytest.raises(IOError):
                store.get("U1", key, now=now)
        else:
            assert _same(store.get("U1", key, now=now), want), key
    got = store.scan_records("U1", now=now)
    want = ref.scan(now)
    assert set(got) == set(want)
    for k, (ts, slate) in want.items():
        assert got[k][0] == ts and _same(got[k][1], slate), k


@pytest.mark.parametrize("quorum", [(1, 1), (2, 2), (3, 3)],
                         ids=["one", "quorum", "all"])
@pytest.mark.parametrize("seed", [0, 1])
def test_store_matches_a_dict(tmp_path, seed, quorum):
    rng = np.random.default_rng(seed)
    w, r = quorum
    store = KVStore(str(tmp_path / "kv"), replicas=3, write_quorum=w,
                    read_quorum=r, flush_buffer=1 << 20)
    ref = DictStore(3, w, r)
    now = 0
    for step in range(40):
        now += int(rng.integers(0, 3))
        for _ in range(int(rng.integers(1, 30))):
            # repeated keys, equal and older ts, some with a TTL
            key = int(rng.integers(0, 48))
            ts = now - int(rng.integers(0, 3))
            ttl = int(rng.choice([0, 0, 2, 5]))
            slate = _slate(rng)
            if rng.random() < 0.5:
                store.put("U1", key, slate, ts=ts, ttl=ttl)
            else:
                store.put_many("U1", [key], {k: np.asarray(v)[None]
                                             for k, v in slate.items()},
                               ts=[ts], ttl=ttl)
            ref.put(key, slate, ts, ttl)
        if ref.flush():
            store.flush()
        else:
            with pytest.raises(IOError):
                store.flush()
        if rng.random() < 0.3:
            i = int(rng.integers(0, 3))
            down = not store._replica_down[i]
            store.set_replica_down(i, down)
            ref.down[i] = down
        if rng.random() < 0.3:
            store.compact()
        if rng.random() < 0.15:
            assert store.gc("U1", now=now) == ref.gc(now)
        _check(store, ref, rng, now if rng.random() < 0.7 else None)
    # a crash mid-write leaves a block that was never renamed in: a
    # reopened store neither reads nor keeps it
    d = tmp_path / "kv" / "replica_0" / "U1"
    d.mkdir(parents=True, exist_ok=True)
    dead = subprocess.Popen([sys.executable, "-c", "pass"])
    dead.wait()
    torn = d / f"{10**15:016d}-5.blk.{dead.pid}.tmp"
    blocks = sorted(d.glob("*.blk"))
    torn.write_bytes(blocks[-1].read_bytes()[:7] if blocks else b"\x28")
    again = KVStore(str(tmp_path / "kv"), replicas=3, write_quorum=w,
                    read_quorum=r)
    assert not torn.exists()
    for i, down in enumerate(store._replica_down):
        again.set_replica_down(i, down)
    _check(again, ref, rng, now)


def test_a_flush_writes_bytes_for_its_rows_only(tmp_path):
    rows = 512

    def put(store, f):
        store.put_many("U1", np.arange(f * rows, (f + 1) * rows),
                       {"count": np.ones(rows, np.int32),
                        "v": np.zeros((rows, 8), np.float32)}, ts=f)
        return store.flush()

    store = KVStore(str(tmp_path / "kv"), replicas=1, write_quorum=1,
                    read_quorum=1)
    sizes, seqs, blocks = [], [], []
    for f in range(64):
        seqs.append(store._seq)
        sizes.append(put(store, f))
        store.compact()
        blocks.append(len(store._blocks(0, "U1")))
    # each flush writes the bytes the same write costs in an empty store,
    # from 512 rows stored before the second to 32,256 before the last
    for f in (1, 31, 63):
        alone = KVStore(str(tmp_path / f"alone{f}"), replicas=1,
                        write_quorum=1, read_quorum=1)
        alone._seq = seqs[f]
        assert sizes[f] == put(alone, f), f
    assert store.bytes_written == sum(sizes)
    # merges write the rest, and keep the blocks a read opens few: at
    # most three in each of the size classes of 512 to 32,768 rows, and
    # one once the 64 flushes have merged all the way up
    assert store.bytes_compacted > store.bytes_written
    assert max(blocks) <= (TIER_BLOCKS - 1) * 4 and blocks[-1] == 1
    keys, ts, slates = store.scan_columns("U1")
    assert keys.tolist() == list(range(64 * rows))
    assert ts.tolist() == np.repeat(np.arange(64), rows).tolist()
    assert slates["v"].shape == (64 * rows, 8)


def test_newest_rows_of_columns():
    key = np.asarray([5, 3, 5, 5, 3, 9], np.int64)
    ts = np.asarray([2, 1, 4, 4, 0, 7], np.int64)
    seq = np.asarray([1, 1, 1, 2, 3, 0], np.int64)
    # 3: largest ts wins over a later seq; 5: equal ts, the later seq
    assert newest(key, ts, seq).tolist() == [1, 3, 5]
    # one write: equal ts and seq, the last row wins
    assert newest(np.asarray([4, 4]), np.asarray([1, 1]),
                  np.asarray([0, 0])).tolist() == [1]


def test_recovery_from_columns_equals_the_per_key_path(tmp_path):
    from repro.core.durability import DurabilityConfig
    from repro.core.engine import Engine, EngineConfig
    from repro.core.workflow import Workflow
    from tests.conftest import PassThroughMapper
    from tests.test_recovery import SumCounter, counting_source

    def engine():
        wf = Workflow([PassThroughMapper(), SumCounter()],
                      external_streams=("S1",))
        return Engine(wf, EngineConfig(
            batch_size=32, queue_capacity=128, chunk_size=4, fused="jnp",
            durability=DurabilityConfig(dir=str(tmp_path / "d"), flush=FlushConfig(
                policy=FlushPolicy.EVERY_K, every_k=8))))

    eng = engine()
    state, _ = eng.run(eng.init_state(), counting_source, 21)
    state = eng.checkpoint(state)       # the log's suffix is empty
    eng.close()
    eng = engine()
    got = eng.recover()
    assert eng.last_recovery["replayed_ticks"] == 0
    # the per-key path: a dict per key, sorted, stacked, inserted op by op
    recs = eng.dur.store.scan_records("U1")
    ks = np.asarray(sorted(recs), np.int32)
    want, slot, _, placed = tbl.insert_or_find(
        eng.init_state()["tables"]["U1"], jnp.asarray(ks),
        jnp.ones(ks.size, bool))
    want = tbl.write_slates(
        want, slot, placed,
        jax.tree.map(lambda *r: jnp.asarray(np.stack(r)),
                     *[recs[int(k)][1] for k in ks]),
        jnp.asarray([recs[int(k)][0] for k in ks], jnp.int32))
    want = dataclasses.replace(want, dirty=jnp.zeros_like(want.dirty))
    assert ks.size > 0 and ks.size & (ks.size - 1)   # padded to 2^k
    for a, b in zip(jax.tree.leaves(got["tables"]["U1"]),
                    jax.tree.leaves(want)):
        assert np.asarray(a).tobytes() == np.asarray(b).tobytes()
    eng.close()


def test_reads_beside_writes_and_merges_see_every_acknowledged_row(tmp_path):
    """Readers scanning while a writer flushes and merges never miss a
    row whose flush returned, and never trip on a merged-away block."""
    import threading
    store = KVStore(str(tmp_path / "kv"), replicas=1, write_quorum=1,
                    read_quorum=1)
    rows, flushes = 256, 40
    acked = [0]
    errors = []
    stop = threading.Event()

    def write():
        try:
            for f in range(flushes):
                store.put_many("U1", np.arange(f * rows, (f + 1) * rows),
                               {"count": np.full(rows, f, np.int32)}, ts=f)
                store.flush()
                acked[0] = f + 1
                store.compact()
        except Exception as e:      # surfaced by the assertion below
            errors.append(e)
        finally:
            stop.set()

    def read():
        try:
            while not stop.is_set():
                n = acked[0] * rows
                keys, _, slates = store.scan_columns("U1")
                if slates is None:      # nothing flushed yet
                    assert n == 0
                    continue
                assert keys[:n].tolist() == list(range(n))
                assert (slates["count"][:n] == keys[:n] // rows).all()
        except Exception as e:
            errors.append(e)

    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        threads = [threading.Thread(target=write)] + \
            [threading.Thread(target=read) for _ in range(2 * os.cpu_count())]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=120)
    finally:
        sys.setswitchinterval(old)
    assert not any(t.is_alive() for t in threads)
    assert not errors, errors[0]
    assert acked[0] == flushes and store.bytes_compacted > 0


def test_merges_leave_unsealed_blocks_and_discard_rolls_them_back(tmp_path):
    """A frontier seals the writes up to its ``seq``: merges take only
    sealed blocks (the merged one keeps the newest input's ``seq``), and
    ``discard_after`` removes the newer ones on every replica, leaving
    the rows as the sealed writes left them."""
    store = KVStore(str(tmp_path / "kv"), replicas=2, write_quorum=2,
                    read_quorum=1)
    keys = np.arange(64)
    for f in range(8):
        store.put_many("U1", keys, {"count": np.full(64, f, np.int32)}, ts=f)
        store.flush()
    assert store.last_seq == 8
    store.seal(4)
    store.compact()
    for i in range(2):
        assert [(s, n) for s, n, _ in store._blocks(i, "U1")] == \
            [(4, 64), (5, 64), (6, 64), (7, 64), (8, 64)]
    assert (store.scan_columns("U1")[2]["count"] == 7).all()
    assert store.discard_after(4) == 8
    assert store.last_seq == 4
    _, ts, slates = store.scan_columns("U1")
    assert (slates["count"] == 3).all() and (ts == 3).all()
    store.put_many("U1", keys[:1], {"count": np.full(1, 9, np.int32)}, ts=9)
    store.flush()
    assert store.last_seq == 5 and store.get("U1", 0)["count"] == 9


@pytest.mark.parametrize("n_keys", [0, 3000])
def test_dirty_snapshot_moves_exactly_the_dirty_rows(n_keys):
    """A snapshot hands the host exactly the dirty occupied rows, in slot
    order, and nothing for a clean table; the returned table is clean."""
    spec = {"count": ((), jnp.int32), "v": ((3,), jnp.float32)}
    t = tbl.make_table(8192, spec)
    keys = jnp.arange(n_keys, dtype=jnp.int32) * 7 + 1
    t, slot, _, placed = tbl.insert_or_find(t, keys, jnp.ones(n_keys, bool))
    t = tbl.write_slates(t, slot, placed, {
        "count": jnp.arange(n_keys, dtype=jnp.int32),
        "v": jnp.ones((n_keys, 3), jnp.float32) * keys[:, None]}, 5)
    host = jax.device_get(t)
    mask = np.asarray(host.dirty) & (np.asarray(host.keys) != -1)
    k, ts, vals, cleared = dirty_snapshot(t)
    assert mask.sum() == n_keys
    assert k.tolist() == np.asarray(host.keys)[mask].tolist()
    assert ts.tolist() == np.asarray(host.ts)[mask].tolist()
    for leaf in ("count", "v"):
        want = np.asarray(host.vals[leaf])[mask]
        assert vals[leaf].dtype == want.dtype and \
            vals[leaf].shape == want.shape
        assert (vals[leaf] == want).all()
    assert not np.asarray(jax.device_get(cleared.dirty)).any()


@pytest.mark.parametrize("capacity,n_keys,rows", [
    (1000, 150, 32),      # three padded gathers; 1000 is no multiple of 64
    (4096, 1024, 1024),   # exactly one full gather
    (520, 60, 1 << 16),   # the gather is cut to the table's size
])
def test_dirty_snapshot_gathers_in_chunks(monkeypatch, capacity, n_keys,
                                          rows):
    """The dirty rows come from fixed-size gathers whatever their number
    or the table's size, and equal a whole-table fetch's, in slot order;
    the token stays readable after the table's buffers are deleted, as
    the next chunk's donation deletes them."""
    monkeypatch.setattr(flush_mod, "SNAPSHOT_ROWS", rows)
    spec = {"count": ((), jnp.int32), "v": ((2,), jnp.float32)}
    t = tbl.make_table(capacity, spec)
    keys = jnp.arange(n_keys, dtype=jnp.int32) * 3 + 2
    t, slot, _, placed = tbl.insert_or_find(t, keys, jnp.ones(n_keys, bool))
    t = tbl.write_slates(t, slot, placed, {
        "count": jnp.arange(n_keys, dtype=jnp.int32),
        "v": jnp.ones((n_keys, 2), jnp.float32) * keys[:, None]}, 4)
    # a second write marks only every other key dirty again
    t = dataclasses.replace(t, dirty=jnp.zeros_like(t.dirty))
    half = keys[::2]
    t, slot, _, placed = tbl.insert_or_find(t, half,
                                            jnp.ones(half.size, bool))
    t = tbl.write_slates(t, slot, placed, {
        "count": -jnp.ones(half.size, jnp.int32),
        "v": jnp.zeros((half.size, 2), jnp.float32)}, 6)
    host = jax.device_get(t)
    mask = np.asarray(host.dirty) & (np.asarray(host.keys) != -1)
    assert mask.sum() == half.size
    token, cleared = flush_mod.begin_dirty_snapshot(t)
    for leaf in jax.tree.leaves(t):
        leaf.delete()
    k, ts, vals = flush_mod.finish_dirty_snapshot(token)
    assert k.tolist() == np.asarray(host.keys)[mask].tolist()
    assert sorted(k.tolist()) == sorted(np.asarray(half).tolist())
    assert (ts == 6).all()
    assert (vals["count"] == -1).all() and vals["v"].shape == (half.size, 2)
    assert not np.asarray(jax.device_get(cleared.dirty)).any()
