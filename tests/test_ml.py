"""Streaming-ML subsystem (repro/ml, DESIGN.md section 16).

Pins the three contracts the subsystem rests on:

- **Bucket-padding exactness**: ``ModelMapper.map_batch`` pads the
  event batch to the compiled microbatch size — outputs must be
  bitwise-identical to unbucketed inference for odd batch sizes, and
  empty ticks must flow through as all-invalid no-ops.
- **Fused-vs-unfused parity**: ``semantic_topk`` is an elementwise-max
  monoid, so the fused ``kernels/slate_update`` path ("jnp" and
  "interpret" backends) must agree *bitwise* with the generic
  scan/merge path (``fused="off"``).
- **Durable recovery**: a model-backed app (LM serving as a MapUpdate
  stream) crash-recovers from WAL replay to bitwise-identical slates.

Heavy model configs stay behind the ``slow`` marker; the tier-1 tests
use a 2-layer toy transformer.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import App, EventBatch, RuntimeConfig
from repro.api import ops
from repro.configs import get_config

TINY = get_config("qwen2-0.5b").replace(
    n_layers=2, d_model=64, n_heads=2, n_kv_heads=1, d_ff=128,
    vocab_size=512, head_dim=32)


# ---------------------------------------------------------------------------
# ModelMapper: bucket padding is exact; empty ticks are no-ops
# ---------------------------------------------------------------------------

def test_model_mapper_bucket_padding_exact():
    """Padding to the microbatch bucket and slicing back must not
    perturb any real row: per-event outputs depend only on their own
    token row (attention never mixes across batch rows)."""
    mm = ops.model_mapper(TINY, field="tokens", out="o", bucket=8)
    rng = np.random.default_rng(0)
    whole = jax.jit(mm._infer)
    for B in (1, 5, 8, 13):
        toks = rng.integers(1, TINY.vocab_size, (B, 8)).astype(np.int32)
        batch = EventBatch.of(key=np.arange(1, B + 1, dtype=np.int32),
                              value={"tokens": toks})
        out = mm.map_batch(batch)["o"]
        # oracle: one unpadded, unbucketed forward over the true batch
        want = np.asarray(whole(jnp.asarray(toks)))
        np.testing.assert_array_equal(np.asarray(out.value["emb"]), want)
        np.testing.assert_array_equal(np.asarray(out.key),
                                      np.asarray(batch.key))
        np.testing.assert_array_equal(np.asarray(out.ts),
                                      np.asarray(batch.ts) + 1)


def test_model_mapper_empty_tick_passthrough():
    """An all-invalid batch (empty tick) must flow through with every
    row still invalid — no NaNs, no crashes, no spurious emissions."""
    mm = ops.model_mapper(TINY, field="tokens", out="o", bucket=4)
    B = 6
    batch = EventBatch.of(
        key=np.zeros(B, np.int32),
        value={"tokens": np.zeros((B, 8), np.int32)},
        valid=np.zeros(B, bool))
    out = mm.map_batch(batch)["o"]
    assert not bool(np.asarray(out.valid).any())
    assert np.isfinite(np.asarray(out.value["emb"])).all()


def test_model_mapper_keep_and_classify():
    mm = ops.model_mapper(TINY, field="tokens", out="o", mode="classify",
                          n_classes=3, bucket=4, keep=("item",))
    rng = np.random.default_rng(1)
    B = 5
    batch = EventBatch.of(
        key=np.arange(B, dtype=np.int32),
        value={"tokens": rng.integers(1, TINY.vocab_size,
                                      (B, 8)).astype(np.int32),
               "item": np.arange(10, 10 + B, dtype=np.int32)})
    out = mm.map_batch(batch)["o"]
    assert set(out.value) == {"cls", "score", "item"}
    cls = np.asarray(out.value["cls"])
    assert cls.shape == (B,) and (0 <= cls).all() and (cls < 3).all()
    np.testing.assert_array_equal(np.asarray(out.value["item"]),
                                  np.asarray(batch.value["item"]))


# ---------------------------------------------------------------------------
# semantic_topk: fused (jnp / interpret) vs generic — bitwise
# ---------------------------------------------------------------------------

def _run_topk(fused: str):
    app = App(f"topk_{fused}")
    app.source("ev", {"emb": ((4,), jnp.float32),
                      "item": ((), jnp.int32)})
    app.stream("ev").update(ops.semantic_topk(
        k=4, n_slots=16, table_capacity=64))
    rng = np.random.default_rng(7)

    def src(tick, max_events):
        B = 16
        return {"ev": EventBatch.of(
            key=rng.integers(0, 5, B).astype(np.int32),
            value={"emb": rng.normal(size=(B, 4)).astype(np.float32),
                   "item": rng.integers(1, 1000, B).astype(np.int32)},
            ts=np.full(B, tick, np.int32))}

    app.run(src, n_ticks=6,
            runtime=RuntimeConfig(batch_size=16, fused=fused), drain=True)
    cells = {}
    for key in range(5):
        slate = app.read_slate("semantic_topk", key)
        cells[key] = None if slate is None \
            else np.asarray(slate["cells"]).copy()
    app.close()
    return cells


def test_semantic_topk_fused_unfused_bitwise_parity():
    from repro.core.apply import fused_eligible, merge_monoid
    up = ops.semantic_topk()
    assert merge_monoid(up) == "max" and fused_eligible(up)
    base = _run_topk("off")                 # generic scan/merge path
    assert any(v is not None and (v > 0).any() for v in base.values())
    for impl in ("jnp", "interpret"):
        got = _run_topk(impl)
        for key, want in base.items():
            if want is None:
                assert got[key] is None
            else:
                np.testing.assert_array_equal(got[key], want,
                                              err_msg=f"key {key} {impl}")


def test_slate_update_max_kernel_matches_ref():
    """The op="max" branch of the fused kernel (interpret) against the
    jnp segment reference, on sorted keyed deltas."""
    from repro.kernels.slate_update import ops as su_ops
    rng = np.random.default_rng(3)
    B, C, N = 64, 8, 32
    keys = np.sort(rng.integers(0, 10, B)).astype(np.int32)
    valid = rng.random(B) > 0.2
    deltas = np.abs(rng.normal(size=(B, C))).astype(np.float32)
    deltas[~valid] = 0.0          # caller contract: invalid rows zeroed
    last = np.ones(B, bool)
    last[:-1] = keys[:-1] != keys[1:]
    slots = np.where(last, keys % N, -1).astype(np.int32)
    rows = np.abs(rng.normal(size=(N, C))).astype(np.float32)
    out_ref, _ = su_ops.slate_update(
        jnp.asarray(keys), jnp.asarray(deltas), jnp.asarray(slots),
        jnp.asarray(rows), impl="ref", op="max")
    out_int, _ = su_ops.slate_update(
        jnp.asarray(keys), jnp.asarray(deltas), jnp.asarray(slots),
        jnp.asarray(rows), impl="interpret", op="max")
    np.testing.assert_array_equal(np.asarray(out_ref),
                                  np.asarray(out_int))
    # hand oracle: per-slot elementwise max over valid deltas (0 = the
    # max identity on the non-negative domain)
    want = rows.copy()
    for i in range(B):
        if valid[i]:
            want[keys[i] % N] = np.maximum(want[keys[i] % N], deltas[i])
    np.testing.assert_array_equal(np.asarray(out_ref), want)


# ---------------------------------------------------------------------------
# personalization: engine sequential path == direct step replay
# ---------------------------------------------------------------------------

def test_personalization_matches_step_replay():
    D, K = 3, 2
    up = ops.personalization(d=D, k=K, alpha=0.5, table_capacity=32)
    rng = np.random.default_rng(5)
    n_ev = 5
    embs = rng.normal(size=(n_ev, D)).astype(np.float32)
    items = np.array([3, 7, 3, 9, 11], np.int32)

    app = App("pers")
    app.source("ev", {"emb": ((D,), jnp.float32),
                      "item": ((), jnp.int32)})
    app.stream("ev").update(up)

    def src(tick, max_events):
        return {"ev": EventBatch.of(
            key=np.ones(n_ev, np.int32),
            value={"emb": embs, "item": items},
            ts=np.arange(n_ev, dtype=np.int32))}

    app.run(src, n_ticks=1, runtime=RuntimeConfig(batch_size=8),
            drain=True)
    got = app.read_slate("personalization", 1)
    assert got is not None

    # oracle: apply `step` one event at a time, in ts order
    slate = {"user": jnp.zeros(D), "items": jnp.zeros(K, jnp.int32),
             "cand": jnp.zeros((K, D)), "scores": jnp.zeros(K),
             "n": jnp.zeros((), jnp.int32)}
    for i in range(n_ev):
        slate, _ = up.step(slate, {"value": {"emb": jnp.asarray(embs[i]),
                                             "item": jnp.asarray(items[i])},
                                   "ts": jnp.int32(i)})
    for leaf in slate:
        np.testing.assert_array_equal(np.asarray(got[leaf]),
                                      np.asarray(slate[leaf]),
                                      err_msg=leaf)
    ranked = up.ranked(got)
    assert 0 < len(ranked) <= K
    assert all(i > 0 for i, _ in ranked)
    app.close()


# ---------------------------------------------------------------------------
# durable recovery of a model-backed app — bitwise slates
# ---------------------------------------------------------------------------

def _mk_reqs(n, rng):
    from repro.launch.serve import Request
    return [Request(rid=i + 1,
                    prompt=rng.integers(1, TINY.vocab_size,
                                        int(rng.integers(3, 8))
                                        ).astype(np.int32),
                    max_new=4)
            for i in range(n)]


def test_serve_app_crash_recovery_bitwise(tmp_path):
    from repro.ml.serve_app import build_serve_app, request_source
    n_req = 6

    def runtime(d):
        # a flush boundary lands mid-run: recovery restores the earlier
        # requests' token slates from the store (wide-leaf round-trip)
        # and replays the rest of the WAL through the model mapper
        return RuntimeConfig(batch_size=4, chunk_size=2,
                             durable_dir=str(d), flush_every=2)

    def make():
        return build_serve_app(TINY, prompt_len=8, max_new=4,
                               cache_len=32, bucket=2)

    def source():
        return request_source(_mk_reqs(n_req, np.random.default_rng(9)),
                              prompt_len=8, capacity=4, per_tick=2)

    # uninterrupted durable run: all requests fed in 3 ticks
    app_a = make()
    app_a.run(source(), n_ticks=3, runtime=runtime(tmp_path / "a"),
              drain=True)
    base = {}
    for rid in range(1, n_req + 1):
        slate = app_a.read_slate("requests", rid)
        assert slate is not None, f"request {rid} missing"
        base[rid] = np.asarray(slate["tokens"]).copy()
    app_a.close()

    # same run, crashed before any drain: in-memory state dropped
    app_b = make()
    app_b.run(source(), n_ticks=3, runtime=runtime(tmp_path / "b"))
    assert app_b.engine.dur.frontier.tick > 0   # a flush boundary hit
    app_b.close()                            # the crash

    # recover on a fresh app (new process in real life) and drain
    app_c = make()
    app_c.run(lambda t, m: {}, n_ticks=0,
              runtime=runtime(tmp_path / "b"), recover=True, drain=True)
    for rid, want in base.items():
        slate = app_c.read_slate("requests", rid)
        assert slate is not None, f"request {rid} lost in recovery"
        np.testing.assert_array_equal(np.asarray(slate["tokens"]), want)
    app_c.close()


# ---------------------------------------------------------------------------
# heavy config behind `slow`
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_model_mapper_heavy_config_bucket_parity():
    cfg = get_config("qwen2-0.5b").replace(
        n_layers=4, d_model=128, n_heads=4, n_kv_heads=2, d_ff=512,
        vocab_size=4096, head_dim=32)
    mm = ops.model_mapper(cfg, field="tokens", out="o", bucket=16)
    rng = np.random.default_rng(11)
    toks = rng.integers(1, cfg.vocab_size, (37, 16)).astype(np.int32)
    batch = EventBatch.of(key=np.arange(37, dtype=np.int32),
                          value={"tokens": toks})
    out = mm.map_batch(batch)["o"]
    want = np.asarray(jax.jit(mm._infer)(jnp.asarray(toks)))
    np.testing.assert_array_equal(np.asarray(out.value["emb"]), want)
