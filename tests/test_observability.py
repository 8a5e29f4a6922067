"""End-to-end latency observability (DESIGN.md section 18).

Covers the PR-10 contract:

- device latency histograms: clz bucketize exactness at power-of-two
  edges, kernel-vs-oracle bitwise equality (including the saturating
  top bucket), and bitwise slate parity with histograms on vs off —
  telemetry state is pure-extra, the tick never reads it;
- host readout: quantile interpolation units, windowed report
  quantiles from a lagged feed;
- span tracing: Chrome-trace JSON schema, ring bounding, migration
  pause reconciliation lives in the distributed suite;
- exposition: /metrics scrape parses as Prometheus text 0.0.4 with
  counter + native histogram families;
- control: the LoadAutoscaler p99 watermark, and recovery timing
  (``recovery_replay_s``) on the report.
"""
import json
import re
import urllib.request

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.engine import Engine, EngineConfig, stack_sources
from repro.core.workflow import Workflow
from repro.telemetry import latency as lat
from repro.telemetry.metrics import TelemetryConfig, TelemetryReport
from tests.conftest import (CountingUpdater, PassThroughMapper,
                            make_batch)


def _wf():
    return Workflow([PassThroughMapper(), CountingUpdater()],
                    external_streams=("S1",))


# ---------------------------------------------------------------------------
# bucketize: exact power-of-two edges
# ---------------------------------------------------------------------------

def test_bucketize_exact_edges():
    """clz binning: bucket b is exactly [2^(b-1), 2^b) — no float-log2
    misplacement at the edges."""
    vals, want = [0, 1], [0, 1]
    for k in range(1, 30):
        vals += [(1 << k) - 1, 1 << k, (1 << k) + 1]
        want += [k, k + 1, k + 1]
    got = np.asarray(lat.bucketize(jnp.asarray(vals, jnp.int32), 32))
    assert got.tolist() == [min(w, 31) for w in want]


def test_bucketize_clamps_negative_and_saturates():
    got = np.asarray(lat.bucketize(
        jnp.asarray([-5, -1, 2**31 - 1, 1 << 20], jnp.int32), 8))
    assert got.tolist() == [0, 0, 7, 7]   # future-stamped -> bucket 0


# ---------------------------------------------------------------------------
# kernel vs oracle: bitwise
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("impl", ["ref", "interpret"])
def test_histogram_kernel_vs_oracle_bitwise(impl):
    from repro.kernels.histogram import histogram_update
    from repro.kernels.histogram.ref import histogram_update as oracle
    rng = np.random.default_rng(7)
    rows, B, width = 3, 64, 128        # width%128==0 keeps pallas viable
    counts = jnp.asarray(rng.integers(0, 50, (rows, width)), jnp.int32)
    cols = jnp.asarray(rng.integers(0, width, (rows, B)), jnp.int32)
    add = jnp.asarray(rng.integers(0, 2, B), jnp.int32)
    got = histogram_update(counts, cols, add, impl=impl)
    want = oracle(counts, cols, add)
    assert np.array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("impl", ["ref", "interpret"])
def test_hist_update_edges_and_saturation(impl):
    """Latencies at bucket edges land in exactly the right device
    bucket; out-of-range mass saturates into the top bucket; invalid
    rows add nothing; ``sum`` is the exact masked total."""
    nb = 8
    h = lat.make_hist(["U1"], nb)["U1"]
    ts = jnp.zeros((6,), jnp.int32)
    tick = jnp.asarray(0, jnp.int32)
    lats = jnp.asarray([0, 1, 2, 3, 4, 1 << 20], jnp.int32)
    valid = jnp.asarray([True, True, True, True, True, True])
    got = lat.hist_update(h, tick + lats, ts * 0, valid,
                          n_buckets=nb, impl=impl)
    # per-row tick works too, but here each event gets its own latency
    # by feeding tick as a vector (tick - ts broadcast)
    counts = np.asarray(got["counts"]).ravel()[:nb]
    #            b0  b1  b2[2,4)  b3[4,8)           top (saturated)
    assert counts.tolist() == [1, 1, 2, 1, 0, 0, 0, 1]
    assert int(got["sum"]) == 0 + 1 + 2 + 3 + 4 + (1 << 20)
    # invalid rows: nothing moves
    got2 = lat.hist_update(got, tick + lats, ts * 0,
                           jnp.zeros_like(valid), n_buckets=nb,
                           impl=impl)
    assert np.array_equal(np.asarray(got2["counts"]),
                          np.asarray(got["counts"]))
    assert int(got2["sum"]) == int(got["sum"])


# ---------------------------------------------------------------------------
# quantile interpolation (host units)
# ---------------------------------------------------------------------------

def test_quantile_interpolation_units():
    nb = 8
    counts = np.zeros(nb)
    counts[2] = 100                    # all mass in [2, 4)
    q = lat.quantile(counts, 0.5, n_buckets=nb)
    assert isinstance(q, float) and not isinstance(q, np.floating)
    assert q == pytest.approx(3.0)     # lo + (hi-lo) * 0.5
    assert lat.quantile(counts, 0.0, n_buckets=nb) == pytest.approx(2.0)
    # mass split across buckets: rank walks the cumulative counts
    counts = np.zeros(nb)
    counts[1] = 50                     # {1}: [1, 2)
    counts[3] = 50                     # [4, 8)
    assert lat.quantile(counts, 0.25, n_buckets=nb) <= 2.0
    assert 4.0 <= lat.quantile(counts, 0.99, n_buckets=nb) < 8.0
    # saturating top bucket reports its lower edge (+Inf convention)
    counts = np.zeros(nb)
    counts[nb - 1] = 10
    assert lat.quantile(counts, 0.99, n_buckets=nb) \
        == float(lat.bucket_lo(nb - 1))
    assert lat.quantile(np.zeros(nb), 0.9, n_buckets=nb) == 0.0


# ---------------------------------------------------------------------------
# the parity contract: histograms are pure-extra state
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("impl", ["ref", "interpret"])
def test_chunk_parity_histograms_on_off(impl):
    """Tables / queues / outputs of the jitted chunk are bitwise
    identical with latency histograms on vs off — the tick updates
    telemetry state but never reads it."""
    rng = np.random.default_rng(3)
    srcs = [{"S1": make_batch(rng.integers(0, 40, 24),
                              rng.integers(0, 9, 24),
                              ts=np.full(24, t, np.int32))}
            for t in range(8)]

    def run(nb):
        eng = Engine(_wf(), EngineConfig(
            batch_size=32, queue_capacity=128,
            telemetry=TelemetryConfig(impl=impl, latency_buckets=nb)))
        state, outs, _ = eng.run_chunk(eng.init_state(),
                                       stack_sources(srcs), 8)
        return state, outs

    s0, o0 = run(0)
    s1, o1 = run(32)
    assert "lat_hist" not in s0 and "lat_hist" in s1
    for part in ("tables", "queues", "processed", "tick"):
        a, b = jax.device_get((s0[part], s1[part]))
        for la, lb in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
            assert np.array_equal(np.asarray(la), np.asarray(lb)), part
    for la, lb in zip(jax.tree.leaves(jax.device_get(o0)),
                      jax.tree.leaves(jax.device_get(o1))):
        assert np.array_equal(np.asarray(la), np.asarray(lb))


def test_hist_backends_agree_through_chunk():
    """The histogram state itself is backend-independent (bitwise)."""
    rng = np.random.default_rng(5)
    srcs = [{"S1": make_batch(rng.integers(0, 40, 24),
                              ts=np.full(24, t, np.int32))}
            for t in range(8)]

    def run(impl):
        eng = Engine(_wf(), EngineConfig(
            batch_size=32, queue_capacity=128,
            telemetry=TelemetryConfig(impl=impl)))
        state, _, _ = eng.run_chunk(eng.init_state(),
                                    stack_sources(srcs), 8)
        return jax.device_get(state["lat_hist"])

    a, b = run("ref"), run("interpret")
    for la, lb in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        assert np.array_equal(np.asarray(la), np.asarray(lb))


# ---------------------------------------------------------------------------
# windowed report quantiles, end to end
# ---------------------------------------------------------------------------

def test_report_quantiles_from_lagged_feed():
    """Sources stamped 3 ticks in the past -> the updater sees ~4-tick
    old events (one mapper hop re-stamps +1); the windowed report's
    pooled quantiles and per-arc p99 land in that band."""
    eng = Engine(_wf(), EngineConfig(
        batch_size=32, queue_capacity=128, chunk_size=4,
        telemetry=TelemetryConfig(window=4, impl="ref")))
    reports = []

    class H:
        state = None
        def on_telemetry(self, r): reports.append(r)
        def on_frontier_advance(self): pass

    def src(t, _mx):
        return {"S1": make_batch(np.arange(16) + t,
                                 ts=np.full(16, max(t - 3, 0), np.int32))}

    state, _ = eng.run(eng.init_state(), src, 16, handle=H())
    assert reports, "windowed observe never fired"
    rep = reports[-1]
    assert 0 < rep.event_latency_p50 <= rep.event_latency_p90 \
        <= rep.event_latency_p99
    assert rep.event_latency_p99 <= 8.0      # small fixed lag, no backlog
    assert rep.queue_delay_p99.get("U1", 0) > 0
    # report round-trips to JSON (no numpy scalars leak)
    json.dumps(rep.to_dict())


def test_recovery_replay_seconds_reported():
    """``recover()`` (restore + WAL replay) is timed into the next
    report's ``recovery_replay_s`` — the satellite bugfix: recovery
    previously ran unobserved."""
    from repro.core.durability import DurabilityConfig
    from repro.slates.flush import FlushConfig, FlushPolicy
    import tempfile
    with tempfile.TemporaryDirectory() as d:
        def build():
            return Engine(_wf(), EngineConfig(
                batch_size=32, queue_capacity=128, chunk_size=4,
                telemetry=TelemetryConfig(window=4, impl="ref"),
                durability=DurabilityConfig(
                    dir=d, flush=FlushConfig(policy=FlushPolicy.EVERY_K,
                                             every_k=4))))

        eng = build()
        src = lambda t, _mx: {"S1": make_batch(
            np.arange(8) + t, ts=np.full(8, t, np.int32))}
        eng.run(eng.init_state(), src, 10)

        eng2 = build()
        state2 = eng2.recover()
        assert eng2.telemetry._recovery_s > 0
        rep = eng2.telemetry.observe(eng2, state2)
        assert rep.recovery_replay_s > 0
        if eng2.tracer is not None:       # trace off by default: None
            pass
        eng2.close()
        eng.close()


# ---------------------------------------------------------------------------
# span tracing
# ---------------------------------------------------------------------------

def test_trace_json_schema(tmp_path):
    """Exported trace is valid Chrome trace JSON: complete events with
    name/ph/ts/dur/pid/tid, JSON-safe args, ring-bounded."""
    from repro.telemetry.trace import Tracer
    tr = Tracer(capacity=8)
    for i in range(12):                  # overflow the ring
        with tr.span("tick", tick=np.int32(i),
                     arr=np.arange(2)) as sp:
            sp["outcome"] = np.float64(1.5)
    path = tr.export(str(tmp_path / "t.json"))
    doc = json.load(open(path))
    evs = doc["traceEvents"]
    assert doc["displayTimeUnit"] == "ms"
    assert len(evs) == 8                 # ring kept the newest 8
    for e in evs:
        assert {"name", "cat", "ph", "ts", "dur", "pid", "tid",
                "args"} <= set(e)
        assert e["ph"] == "X" and e["dur"] >= 0
        assert e["args"]["outcome"] == 1.5       # json-safe numpy
    assert [e["args"]["tick"] for e in evs] == list(range(4, 12))


def test_engine_run_emits_phase_spans():
    """A traced durable run records the split phases the drive loop
    already has — chunk dispatch, WAL fence, flush, observe."""
    from repro.core.durability import DurabilityConfig
    from repro.slates.flush import FlushConfig, FlushPolicy
    import tempfile
    with tempfile.TemporaryDirectory() as d:
        eng = Engine(_wf(), EngineConfig(
            batch_size=32, queue_capacity=128, chunk_size=4,
            telemetry=TelemetryConfig(window=4, trace=True),
            durability=DurabilityConfig(
                dir=d, flush=FlushConfig(policy=FlushPolicy.EVERY_K,
                                         every_k=4))))
        src = lambda t, _mx: {"S1": make_batch(
            np.arange(8) + t, ts=np.full(8, t, np.int32))}
        eng.run(eng.init_state(), src, 8)
        names = {e["name"] for e in eng.tracer.events()}
        assert {"source_build", "stack_sources", "chunk_dispatch",
                "chunk_sync", "wal_fence", "flush_begin",
                "flush_commit"} <= names, names
        eng.close()


def test_durable_write_spans_and_counters(tmp_path):
    """The flusher thread names each store write and the log's writer
    each append; the engine's counters add up the rows flushed and the
    bytes the store and the log were given."""
    import os
    from repro.core.durability import DurabilityConfig
    from repro.slates.flush import FlushConfig, FlushPolicy
    d = str(tmp_path / "d")
    eng = Engine(_wf(), EngineConfig(
        batch_size=32, queue_capacity=128, chunk_size=4,
        telemetry=TelemetryConfig(window=4, trace=True),
        durability=DurabilityConfig(
            dir=d, flush=FlushConfig(policy=FlushPolicy.EVERY_K,
                                     every_k=4))))
    src = lambda t, _mx: {"S1": make_batch(
        np.arange(8) + t, ts=np.full(8, t, np.int32))}
    state, _ = eng.run(eng.init_state(), src, 12)
    writes = eng.tracer.spans("store_write")
    appends = eng.tracer.spans("wal_append")
    assert len(writes) >= 2 and len(appends) == 12
    assert sorted(a["args"]["tick"] for a in appends) == \
        sorted({a["args"]["tick"] for a in appends})
    main = {e["tid"] for e in eng.tracer.spans("chunk_dispatch")}
    assert not main & {e["tid"] for e in writes + appends}
    st = eng.stats(state)
    assert st["flush_rows"] == {"U1": sum(w["args"]["rows"]
                                          for w in writes)}
    assert st["flush_rows"]["U1"] > 0
    assert st["store_bytes_written"] == sum(w["args"]["bytes"]
                                            for w in writes)
    on_disk = sum(os.path.getsize(os.path.join(r, f))
                  for r, _, fs in os.walk(os.path.join(d, "store"))
                  for f in fs)
    assert st["store_bytes_compacted"] == 0       # no size class is full
    assert st["store_bytes_written"] == on_disk
    assert st["wal_bytes_written"] == \
        os.path.getsize(os.path.join(d, "wal.log")) - 12   # its header
    from repro.telemetry.prom import render_prometheus
    text = render_prometheus(stats=st)
    assert (f"muppet_wal_bytes_written_total {st['wal_bytes_written']}"
            in text)
    eng.close()


TICK_SCOPES = ("tick.queues", "tick.telemetry", "tick.map", "apply.sort",
               "apply.probe", "apply.pack", "apply.write")


def _counting_app(capacity=256, batch=32, fused="ref", mesh=None):
    """Paper Examples 1/4 at a tiny size: a mapper, a counter and an
    [8]-lane sum, both on the packed path; sharded over ``mesh`` when
    one is given."""
    from repro import App, EventBatch, RuntimeConfig, ops
    app = App("scoped")
    checkins = app.source("S1", {"x": ((), jnp.float32)})

    @app.mapper(checkins, out="S2")
    def parse(b):
        return EventBatch(sid=b.sid, ts=b.ts + 1, key=b.key,
                          value=b.value, valid=b.valid)

    parsed = app.stream("S2")
    parsed.update(ops.counter("U1", table_capacity=capacity))

    @app.updater(parsed, slate={"v": ((8,), jnp.float32)}, name="UV",
                 table_capacity=capacity)
    def lanes(b):
        return {"v": jnp.ones((b.key.shape[0], 8), jnp.float32)}

    app.start(RuntimeConfig(batch_size=batch, chunk_size=2, fused=fused,
                            telemetry=TelemetryConfig(impl="jnp"),
                            mesh=mesh))

    def source(t, _mx=None):
        return {"S1": EventBatch(
            sid=jnp.zeros(batch, jnp.int32),
            ts=jnp.full(batch, t, jnp.int32),
            key=jnp.arange(batch, dtype=jnp.int32) * 7 + t,
            value={"x": jnp.ones(batch, jnp.float32)},
            valid=jnp.ones(batch, bool))}
    return app, source


def _lowered_tick(program, cap):
    """The counting app's tick program, lowered: the single-shard chunk,
    or the sharded step or chunk on a one-device mesh."""
    if program == "chunk":
        app, source = _counting_app(capacity=cap)
        eng = app.engine
        return eng._chunk.lower(
            eng.init_state(), stack_sources([source(0), source(1)]),
            jnp.int32(32), n_ticks=2, adapt=False, throttle_floor=8)
    from jax.sharding import Mesh
    mesh = Mesh(np.asarray(jax.devices()[:1]), ("data",))
    app, source = _counting_app(capacity=cap, mesh=mesh)
    eng = app.engine
    if program == "sharded-step":
        src = jax.tree.map(lambda x: x[None], source(0))
        return eng._step.lower(eng.init_state(), src, *eng._tick_inputs())
    src = jax.tree.map(lambda x: x[:, None],
                       stack_sources([source(0), source(1)]))
    return eng._chunk.lower(eng.init_state(), src, *eng._tick_inputs())


@pytest.mark.parametrize("program", ["chunk", "sharded-step",
                                     "sharded-chunk"])
def test_tick_phases_are_named_in_the_compiled_chunk(program):
    """Every phase of the tick carries its named scope into the compiled
    program's op_name metadata, on one shard and on many (both engines
    run one tick function), and every claim scatter of
    ``insert_or_find`` (one per updater, in the body of its claim-round
    loop, into the s32[C] key array) lies under ``apply.probe``."""
    cap = 256
    hlo = _lowered_tick(program, cap).compile().as_text()
    names = re.findall(r'op_name="([^"]*)"', hlo)
    for scope in TICK_SCOPES:
        assert any(f"/{scope}/" in n for n in names), scope
    claims = [ln for ln in hlo.splitlines()
              if re.search(rf"= s32\[{cap}\]\S* scatter\(", ln)
              and "/apply.probe/" in ln]
    assert len(claims) == 2, claims


def test_claim_rounds_run_only_for_missing_keys():
    """``table_claim_rounds`` stays put over ticks whose keys are all in
    the tables and rises over ticks that insert."""
    app, source = _counting_app()
    eng = app.engine
    same = lambda t, _mx=None: source(0)
    state, _ = eng.run(eng.init_state(), same, 4)
    loaded = eng.stats(state)["table_claim_rounds"]
    assert set(loaded) == {"U1", "UV"} and min(loaded.values()) > 0
    state, _ = eng.run(state, same, 4)
    assert eng.stats(state)["table_claim_rounds"] == loaded
    state, _ = eng.run(state, lambda t, _mx=None: source(1000), 4)
    grown = eng.stats(state)["table_claim_rounds"]
    assert all(grown[k] > loaded[k] for k in loaded)
    assert sum(eng.stats(state)["table_dropped"].values()) == 0


def test_app_stats_report_window_stalls():
    """``App.stats()`` shows each table's ``table_window_stalls`` beside
    its ``table_claim_rounds``.  The tables have one 128-lane window, so
    each kernel update of a tick's 8 keys holds back 7 of its 8 rows."""
    app, source = _counting_app(capacity=128, batch=8, fused="interpret")
    app.run(lambda t, _mx=None: source(0), 4)
    st = app.stats()
    assert set(st["table_window_stalls"]) == set(st["table_claim_rounds"])
    assert set(st["table_window_stalls"]) == {"U1", "UV"}
    assert sum(st["table_dropped"].values()) == 0
    for name, stalls in st["table_window_stalls"].items():
        assert stalls == 7 * st["processed"][name] // 8 > 0
    app.close()


def test_engine_run_spans_reach_the_profiler(tmp_path):
    """With no span ring (``trace`` off), the drive loop's spans still
    land in a profiler session, nested in the caller's annotation."""
    from jax.profiler import ProfileData
    app, source = _counting_app()
    eng = app.engine
    assert eng.tracer is None
    state = eng.init_state()
    state, _ = eng.run(state, source, 2)          # compile outside
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation("caller.run"):
        state, _ = eng.run(state, source, 4)
        jax.block_until_ready(state)
    jax.profiler.stop_trace()
    path = next(tmp_path.rglob("*.xplane.pb"))
    spans = [(ev.name, ev.start_ns, ev.start_ns + ev.duration_ns)
             for plane in ProfileData.from_file(str(path)).planes
             if plane.name.startswith("/host:")
             for line in plane.lines for ev in line.events]
    outer = [(a, b) for n, a, b in spans if n == "caller.run"]
    assert len(outer) == 1
    lo, hi = outer[0]
    for name in ("source_build", "stack_sources", "chunk_dispatch",
                 "chunk_sync"):
        inside = [(a, b) for n, a, b in spans if n == name]
        assert len(inside) == 2, (name, inside)   # two 2-tick chunks
        assert all(lo <= a <= b <= hi for a, b in inside), name


def test_control_log_jsonl(tmp_path):
    from repro.telemetry.trace import ControlLog
    p = tmp_path / "ctl.jsonl"
    log = ControlLog(str(p))
    log.log({"tick": 8, "action": None,
             "pressure": np.asarray([0.5, 0.25])})
    log.log({"tick": 16, "action": {"kind": "scale", "target": 4}})
    log.close()
    recs = [json.loads(l) for l in open(p)]
    assert [r["tick"] for r in recs] == [8, 16]
    assert recs[0]["pressure"] == [0.5, 0.25]
    assert recs[1]["action"]["kind"] == "scale"


# ---------------------------------------------------------------------------
# /metrics exposition
# ---------------------------------------------------------------------------

_SAMPLE = re.compile(
    r'^[a-zA-Z_:][a-zA-Z0-9_:]*(\{[^}]*\})? (\+Inf|-?[0-9.e+-]+)$')


def test_metrics_scrape_parses(tmp_path):
    """GET /metrics on the slate server returns Prometheus text 0.0.4:
    every sample line parses, counter and native histogram families are
    present, bucket series are cumulative and end at +Inf."""
    from repro.core.engine import StateHandle
    eng = Engine(_wf(), EngineConfig(
        batch_size=32, queue_capacity=128, chunk_size=4,
        telemetry=TelemetryConfig(window=4, impl="ref")))
    src = lambda t, _mx: {"S1": make_batch(
        np.arange(16) + t, ts=np.full(16, max(t - 2, 0), np.int32))}
    state, _ = eng.run(eng.init_state(), src, 8)
    h = StateHandle(eng, state)
    srv = h.serve()
    try:
        with urllib.request.urlopen(
                f"http://127.0.0.1:{srv.port}/metrics") as r:
            assert r.status == 200
            assert r.headers["Content-Type"].startswith("text/plain")
            text = r.read().decode()
    finally:
        srv.close()

    kinds = {}
    for line in text.strip().splitlines():
        if line.startswith("# TYPE"):
            _, _, name, kind = line.split()
            kinds[name] = kind
        elif not line.startswith("#"):
            assert _SAMPLE.match(line), f"unparseable sample: {line!r}"
    assert kinds.get("muppet_processed_total") == "counter"
    assert kinds.get("muppet_event_latency_ticks") == "gauge"
    assert kinds.get("muppet_event_latency_ticks_hist") == "histogram"

    # native histogram series: cumulative counts, +Inf last, _count
    # equals the +Inf bucket
    buckets = re.findall(
        r'muppet_event_latency_ticks_hist_bucket\{arc="U1",le="([^"]+)"\}'
        r' ([0-9.e+]+)', text)
    assert buckets and buckets[-1][0] == "+Inf"
    cums = [float(v) for _, v in buckets]
    assert cums == sorted(cums) and cums[-1] > 0
    count = re.search(
        r'muppet_event_latency_ticks_hist_count\{arc="U1"\} ([0-9.e+]+)',
        text)
    assert count and float(count.group(1)) == cums[-1]
    # integer-latency le edges: 2^b - 1 inclusive
    les = [b for b, _ in buckets[:-1]]
    assert les[:4] == ["0", "1", "3", "7"]


def test_render_prometheus_shapes():
    """Renderer unit: stats counters, report gauges with labels, and
    histogram families from synthetic inputs."""
    from repro.telemetry.prom import render_prometheus
    nb = 8
    counts = np.zeros((1, lat.pad_width(nb)), np.int32)
    counts[0, :4] = [2, 3, 0, 5]
    text = render_prometheus(
        stats={"tick": 7, "processed": {"M1": 10, "U1": 9},
               "queue_dropped": {"S2": 1}, "throttle_hits": 2},
        report=TelemetryReport(
            tick=7, ticks=4, n_shards=1, active=[0], window_s=0.1,
            events=np.asarray([32]), events_per_tick=np.asarray([8.0]),
            queue_depth=np.asarray([3]), queue_peak_delta=np.asarray([0]),
            dropped_delta=np.asarray([0]), occupancy=np.asarray([12]),
            pressure=np.asarray([0.5]), heavy_hitters=[],
            migration_pause_s=0.0,
            event_latency_p50=2.0, event_latency_p90=3.5,
            event_latency_p99=3.9, queue_delay_p99={"U1": 3.9}),
        hist={"U1": {"counts": counts, "sum": 17}}, n_buckets=nb)
    assert 'muppet_processed_total{op="M1"} 10' in text
    assert 'muppet_queue_dropped_total{queue="S2"} 1' in text
    assert 'muppet_throttle_hits_total 2' in text
    assert 'muppet_window_pressure{shard="0"} 0.5' in text
    assert 'muppet_event_latency_ticks{quantile="0.99"} 3.9' in text
    assert 'muppet_queue_delay_p99_ticks{arc="U1"} 3.9' in text
    assert 'muppet_event_latency_ticks_hist_sum{arc="U1"} 17' in text
    assert 'muppet_event_latency_ticks_hist_count{arc="U1"} 10' in text
    assert re.search(r'_bucket\{arc="U1",le="\+Inf"\} 10', text)


# ---------------------------------------------------------------------------
# control: the p99 watermark
# ---------------------------------------------------------------------------

def _report(pressure, p99):
    n = len(pressure)
    z = np.zeros(n)
    return TelemetryReport(
        tick=8, ticks=8, n_shards=n, active=list(range(n)),
        window_s=0.1, events=z, events_per_tick=np.asarray(pressure),
        queue_depth=z, queue_peak_delta=z, dropped_delta=z,
        occupancy=z, pressure=np.asarray(pressure, np.float64),
        heavy_hitters=[], migration_pause_s=0.0,
        event_latency_p99=p99)


def test_autoscaler_p99_watermark_scales_up():
    """With ``p99_high`` set, scale-up fires on tail latency even while
    mean pressure sits under the high watermark; a quiet p99 holds."""
    from repro.telemetry.controller import LoadAutoscaler
    pol = LoadAutoscaler(high=0.75, low=0.1, dwell=2, cooldown=1,
                         p99_high=5.0)
    r_hot = _report([0.3, 0.3], p99=12.0)      # mean well under high
    assert pol.decide(r_hot, n_active=2, limit=8) is None   # dwell 1/2
    act = pol.decide(r_hot, n_active=2, limit=8)
    assert act is not None and act.kind == "scale" and act.target == 4
    assert "p99" in act.reason

    pol.reset()
    r_cool = _report([0.3, 0.3], p99=2.0)
    for _ in range(4):
        assert pol.decide(r_cool, n_active=2, limit=8) is None


def test_autoscaler_p99_zero_keeps_pressure_trigger():
    from repro.telemetry.controller import LoadAutoscaler
    pol = LoadAutoscaler(high=0.75, low=0.1, dwell=1, cooldown=1)
    act = pol.decide(_report([0.9, 0.9], p99=0.0), n_active=2, limit=8)
    assert act is not None and act.kind == "scale"
