#!/usr/bin/env python3
"""Chip smoke test: Muppet's counting deployment on a TPU.

Drives the paper's counting application (Examples 1 and 4) once through
the entry points a user calls — ``App`` + ``RuntimeConfig`` ->
``Engine`` — at the state size one worker of the paper's cluster holds
(2^24 slates per updater), and checks every slate it touched against a
numpy reference of the events it fed.  Events come from ``--seed``:
Zipf(1.1) keys over 2^22 ids, 8192 events per tick.

Phases (one process; JAX holds the chip for all of them):

  counting    1 chip: mapper -> ops.counter + an [8]-wide vector
              counter, chunk 8, telemetry on.  One batched read of every
              touched key per updater must equal a numpy bincount bit for
              bit; the compiled tick must contain the slate_update,
              countmin and histogram kernels and the read program the
              slate_lookup kernel.
  durable     1 chip: the same app with a WAL and slate store under
              chiprun_out/chip_smoke/, then recover() into a fresh engine
              and read the same counts back.
  four-chips  only with --four-chips: the same app sharded over four
              chips (DistributedEngine, shard_map all_to_all exchange).

    python chip_smoke.py [--seed N] [--four-chips]

Without a TPU it exits non-zero before any phase.  The last line of
stdout is one JSON object, printed only when every phase passed:
{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}.
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import re
import shutil
import sys
import time
from dataclasses import dataclass

import numpy as np

ROOT = os.path.dirname(os.path.abspath(__file__))
OUT_DIR = os.path.join(ROOT, "chiprun_out", "chip_smoke")
KERNELS_TICK = ("slate_update", "countmin_update", "histogram_update")
LANE_SCALE = np.arange(1, 9, dtype=np.float32)   # vector slate lanes


@dataclass(frozen=True)
class Sizes:
    """One deployment's scale; ``main`` runs the real one."""
    capacity: int = 1 << 24     # slates per updater table (per shard)
    n_keys: int = 1 << 22       # distinct ids the Zipf source draws
    alpha: float = 1.1
    batch: int = 8192           # events per tick (per shard)
    chunk: int = 8              # ticks per device-resident scan
    ticks: int = 32
    kernels: str = "auto"       # fused / telemetry backend


def log(phase: str, **fields):
    print(json.dumps({"phase": phase, **fields}), flush=True)


def check(cond: bool, what: str):
    if not cond:
        raise AssertionError(what)


def zipf_events(rng, sizes: Sizes, shape):
    """Keys [*shape] int32, Zipf(alpha) over ``n_keys`` ids (hot ranks
    land on scattered ids), and small integer values as f32, so every
    per-key sum stays exact in f32."""
    p = np.arange(1, sizes.n_keys + 1, dtype=np.float64) ** -sizes.alpha
    cdf = np.cumsum(p)
    cdf /= cdf[-1]
    n = int(np.prod(shape))
    ranks = np.minimum(np.searchsorted(cdf, rng.random(n), side="right"),
                       sizes.n_keys - 1)
    ids = rng.permutation(sizes.n_keys).astype(np.int32)
    keys = ids[ranks].reshape(shape)
    xs = rng.integers(0, 4, size=shape).astype(np.float32)
    return keys, xs


def build_app(capacity: int):
    """Paper Examples 1/4: checkins are parsed by a mapper and counted
    per retailer; a second updater keeps an [8]-lane vector sum per key,
    the single-leaf slate the point-lookup kernel reads."""
    import jax.numpy as jnp
    from repro import App, EventBatch, ops

    app = App("chip_smoke")
    checkins = app.source("S1", {"x": ((), jnp.float32)})

    @app.mapper(checkins, out="S2")
    def parse(batch):
        return EventBatch(sid=batch.sid, ts=batch.ts + 1, key=batch.key,
                          value=batch.value, valid=batch.valid)

    parsed = app.stream("S2")
    parsed.update(ops.counter("U1", table_capacity=capacity))

    @app.updater(parsed, slate={"v": ((8,), jnp.float32)}, name="UV",
                 table_capacity=capacity)
    def lanes(batch):
        return {"v": batch.value["x"][:, None] * jnp.asarray(LANE_SCALE)}

    return app


def runtime(sizes: Sizes, **kw):
    from repro import RuntimeConfig, TelemetryConfig
    return RuntimeConfig(batch_size=sizes.batch, chunk_size=sizes.chunk,
                         fused=sizes.kernels,
                         telemetry=TelemetryConfig(impl=sizes.kernels),
                         **kw)


def source(keys, xs):
    """``source_fn`` over pre-generated [T, (shards,) B] events."""
    import jax.numpy as jnp
    from repro import EventBatch

    def fn(t, _max_events):
        k = keys[t]
        return {"S1": EventBatch(
            sid=jnp.zeros(k.shape, jnp.int32),
            ts=jnp.full(k.shape, t, jnp.int32), key=jnp.asarray(k),
            value={"x": jnp.asarray(xs[t])},
            valid=jnp.ones(k.shape, bool))}
    return fn


def check_reads(handle, keys, xs, sizes: Sizes, phase: str):
    """One batched read per updater over every touched key must equal a
    numpy bincount of the events fed, exactly; untouched keys read as
    missing."""
    flat_k, flat_x = keys.ravel(), xs.ravel()
    touched = np.unique(flat_k)
    ref_count = np.bincount(flat_k, minlength=sizes.n_keys)[touched]
    ref_x = np.bincount(flat_k, weights=flat_x,
                        minlength=sizes.n_keys)[touched]
    absent = np.setdiff1d(np.arange(64, dtype=np.int32), touched)
    query = np.concatenate([touched, absent])
    t0 = time.perf_counter()
    got_c = handle.read_slates("U1", query)
    got_v = handle.read_slates("UV", query)
    read_s = time.perf_counter() - t0
    n = touched.size
    check(all(r is not None for r in got_c[:n] + got_v[:n]),
          f"{phase}: a touched key reads as missing")
    check(all(r is None for r in got_c[n:] + got_v[n:]),
          f"{phase}: an untouched key reads as present")
    count = np.asarray([int(r["count"]) for r in got_c[:n]])
    vec = np.stack([np.asarray(r["v"]) for r in got_v[:n]])
    want_v = (ref_x[:, None] * LANE_SCALE[None, :]).astype(np.float32)
    check(np.array_equal(count, ref_count),
          f"{phase}: counter differs from numpy on "
          f"{int((count != ref_count).sum())} keys")
    check(np.array_equal(vec, want_v),
          f"{phase}: vector slate differs from numpy on "
          f"{int((vec != want_v).any(axis=1).sum())} keys")
    log(phase, check="reads == numpy bincount", keys_read=int(query.size),
        touched=int(n), max_count=int(ref_count.max()),
        read_s=round(read_s, 3))
    return count, vec


def custom_calls(hlo_text: str):
    """Pallas kernels in a compiled program, by kernel name."""
    names = {}
    for line in hlo_text.splitlines():
        if 'custom_call_target="tpu_custom_call"' in line:
            m = re.search(r"%([A-Za-z_]+?)(?:\.\d+)? = ", line)
            nm = m.group(1) if m else "?"
            names[nm] = names.get(nm, 0) + 1
    return names


def memory(devices):
    out = []
    for d in devices:
        st = d.memory_stats() or {}
        out.append({"device": d.id, "bytes_in_use": st.get("bytes_in_use"),
                    "peak_bytes_in_use": st.get("peak_bytes_in_use")})
    return out


def phase_counting(sizes: Sizes, rng):
    import jax
    import jax.numpy as jnp
    from repro.core.engine import _batched_lookup, stack_sources

    keys, xs = zipf_events(rng, sizes, (sizes.ticks, sizes.batch))
    src = source(keys, xs)
    app = build_app(sizes.capacity)
    h = app.start(runtime(sizes))
    eng = app.engine

    # the chunk program the run dispatches, compiled ahead of time so
    # the compile is timed apart from the ticks and its HLO inspected
    stacked = stack_sources([src(t, None) for t in range(sizes.chunk)])
    t0 = time.perf_counter()
    compiled = eng._chunk.lower(
        h.state, stacked, jnp.int32(sizes.batch), n_ticks=sizes.chunk,
        adapt=False, throttle_floor=8).compile()
    compile_s = time.perf_counter() - t0
    calls = custom_calls(compiled.as_text())
    log("counting", compiled="chunk", compile_s=round(compile_s, 3),
        tpu_custom_calls=calls)
    on_tpu = jax.default_backend() == "tpu"
    if on_tpu:
        missing = [k for k in KERNELS_TICK if k not in calls]
        check(not missing, f"compiled chunk lacks kernels {missing}")

    t0 = time.perf_counter()
    app.run(src, sizes.chunk)
    jax.block_until_ready(h.state)
    first_s = time.perf_counter() - t0
    rest = sizes.ticks - sizes.chunk
    t0 = time.perf_counter()
    app.run(src, rest, source_offset=sizes.chunk)
    jax.block_until_ready(h.state)
    steady_s = time.perf_counter() - t0
    app.run(src, 0, drain=True)
    jax.block_until_ready(h.state)
    st = app.stats()
    log("counting", ticks=sizes.ticks, batch=sizes.batch,
        capacity=sizes.capacity, first_chunk_s=round(first_s, 3),
        steady_ticks_per_s=rest / steady_s,
        steady_events_per_s=rest * sizes.batch / steady_s,
        processed=st["processed"], table_occupancy=st["table_occupancy"],
        memory=memory(jax.devices()[:1]))
    check(all(v == 0 for v in st["queue_dropped"].values()),
          f"queue drops {st['queue_dropped']}")
    check(all(v == 0 for v in st["table_dropped"].values()),
          f"table drops {st['table_dropped']}")
    check(st["processed"]["U1"] == keys.size, "U1 missed events")

    check_reads(h, keys, xs, sizes, "counting")
    table = h.state["tables"]["UV"]
    read_hlo = _batched_lookup.lower(
        table.keys, table.vals, jnp.asarray(np.unique(keys)),
        impl="auto").compile().as_text()
    read_calls = custom_calls(read_hlo)
    log("counting", compiled="read UV", tpu_custom_calls=read_calls)
    if on_tpu:
        check("slate_lookup" in read_calls,
              "read program lacks the slate_lookup kernel")
    app.close()


def phase_durable(sizes: Sizes, rng, out_dir: str):
    """The same app with a WAL + slate store under ``out_dir``: flush
    every 16 ticks, then recover() into a fresh engine (restore the
    store, replay the WAL suffix) and read back what the first engine
    acknowledged."""
    import jax

    ddir = os.path.join(out_dir, "durable")
    shutil.rmtree(ddir, ignore_errors=True)
    ticks = sizes.ticks + sizes.chunk      # leaves a WAL suffix to replay
    keys, xs = zipf_events(rng, sizes, (ticks, sizes.batch))
    src = source(keys, xs)
    rt = runtime(sizes, durable_dir=ddir, flush_every=16)

    app = build_app(sizes.capacity)
    t0 = time.perf_counter()
    app.run(src, ticks, runtime=rt, drain=True)
    jax.block_until_ready(app.handle.state)
    run_s = time.perf_counter() - t0
    before = check_reads(app.handle, keys, xs, sizes, "durable")
    app.close()
    del app
    gc.collect()

    again = build_app(sizes.capacity)
    t0 = time.perf_counter()
    h = again.start(rt, recover=True)
    again.run(src, 0, drain=True)
    jax.block_until_ready(h.state)
    recover_s = time.perf_counter() - t0
    after = check_reads(h, keys, xs, sizes, "durable-recovered")
    check(all(np.array_equal(a, b) for a, b in zip(before, after)),
          "recovered slates differ from the acknowledged ones")
    log("durable", ticks=ticks, flush_every=16, run_s=round(run_s, 3),
        recover_s=round(recover_s, 3),
        frontier_tick=again.engine.dur.frontier.tick)
    again.close()


def phase_four_chips(sizes: Sizes, rng, n_chips: int = 4):
    """The counting app sharded over ``n_chips`` (DistributedEngine):
    events cross chips through the shard_map all_to_all exchange.  Each
    shard takes half a batch per tick, so the hottest shard keeps up at
    Zipf(1.1) skew and no queue overflows."""
    import jax

    per_shard = sizes.batch // 2
    keys, xs = zipf_events(rng, sizes, (sizes.ticks, n_chips, per_shard))
    src = source(keys, xs)
    # a shard may send its whole batch to one peer
    rt = runtime(sizes, shards=n_chips, exchange_slack=float(n_chips))
    app = build_app(sizes.capacity)
    t0 = time.perf_counter()
    app.run(src, sizes.ticks, runtime=rt, drain=True)
    jax.block_until_ready(app.handle.state)
    run_s = time.perf_counter() - t0
    st = app.stats()
    devs = {s.device for s in
            app.handle.state["tables"]["U1"].keys.addressable_shards}
    mem = memory(jax.devices()[:n_chips])
    log("four-chips", ticks=sizes.ticks, shards=n_chips,
        events_per_shard_tick=per_shard, run_s=round(run_s, 3),
        exchange_dropped=st["exchange_dropped"],
        queue_dropped=st["queue_dropped"], processed=st["processed"],
        state_devices=sorted(d.id for d in devs), memory=mem)
    check(st["exchange_dropped"] == 0, "exchange dropped events")
    check(all(v == 0 for v in st["queue_dropped"].values()),
          f"queue drops {st['queue_dropped']}")
    check(len(devs) == n_chips,
          f"state on {len(devs)} devices, not {n_chips}")
    if jax.default_backend() == "tpu":    # CPU devices report no stats
        check(all((m["bytes_in_use"] or 0) > 0 for m in mem),
              "a chip holds no state")
    check_reads(app.handle, keys, xs, sizes, "four-chips")
    app.close()


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the four-chip sharded phase")
    args = ap.parse_args(argv)

    sys.path.insert(0, os.path.join(ROOT, "src"))
    from repro.launch.compile_cache import use_compile_cache
    cache = use_compile_cache()
    import jax

    devices = jax.devices()
    d0 = devices[0]
    if d0.platform != "tpu":
        print(f"chip_smoke: needs a TPU; JAX found {d0.platform}",
              file=sys.stderr)
        return 2
    need = 4 if args.four_chips else 1
    if len(devices) < need:
        print(f"chip_smoke: needs {need} chips, found {len(devices)}",
              file=sys.stderr)
        return 2
    os.makedirs(OUT_DIR, exist_ok=True)
    log("start", seed=args.seed, jax=jax.__version__, compile_cache=cache,
        device_kind=d0.device_kind, n_devices=len(devices))
    rng = np.random.default_rng(args.seed)
    sizes = Sizes()
    if args.four_chips:
        phase_four_chips(sizes, rng)
    else:
        phase_counting(sizes, rng)
        phase_durable(sizes, rng, OUT_DIR)
    print(json.dumps({"ok": True, "device": {
        "platform": d0.platform, "kind": d0.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
