#!/usr/bin/env python3
"""Record the small profiler trace that ``test_bench_durable.py`` reads:
the counting app at a small size (2^16 slots, 1024 events a tick) with a
durability directory and a flush every 16 ticks, on one TPU, one traced
run of 32 ticks between two ``bench.traced`` marks.  Writes
``<out>/durable.xplane.pb.gz`` and prints what was recorded.

    python3 bench/tests/record_durable_trace.py [out, default .bench_out/testdata]
"""
import glob
import gzip
import json
import os
import shutil
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]


def main():
    import jax
    import numpy as np
    if jax.devices()[0].platform != "tpu":
        print("record_durable_trace: needs a TPU", file=sys.stderr)
        return 2
    from bench import traffic
    from bench.apps import counting
    cfg = json.load(open(os.path.join(ROOT, "bench/configs/counting.json")))
    cfg.update(table_capacity=1 << 16, name="durable")
    cfg["runtime"]["batch_size"] = 1024
    mix = traffic.load_mix("flood", os.path.join(ROOT, "bench"))
    mix["keys"]["ids"] = 1 << 14
    mix["keys"]["load"] = False
    gen = traffic.Generator(mix, traffic.streams(7), batch=1024)
    out = os.path.join(ROOT, sys.argv[1] if len(sys.argv) > 1
                       else os.path.join(".bench_out", "testdata"))
    ddir = os.path.join(out, "durable")
    shutil.rmtree(ddir, ignore_errors=True)
    rt = counting.runtime(cfg)
    rt.durable_dir, rt.flush_every = ddir, 16
    app = counting.build(cfg)
    app.start(rt)

    def source(t, mx):
        k, b, n = gen.take(mx)
        return counting.batch(k, b, n, t)

    app.run(source, 32)
    jax.block_until_ready(app.handle.state)
    tdir = os.path.join(out, "trace")
    shutil.rmtree(tdir, ignore_errors=True)
    from bench.harness import profile_options
    jax.profiler.start_trace(tdir, profiler_options=profile_options())
    with jax.profiler.TraceAnnotation("bench.traced"):
        with jax.profiler.TraceAnnotation("bench.app_run"):
            app.run(source, 32, source_offset=32)
        jax.block_until_ready(app.handle.state)
    jax.profiler.stop_trace()
    stats = app.stats()
    app.close()
    shutil.rmtree(ddir, ignore_errors=True)
    path = glob.glob(os.path.join(tdir, "**", "*.xplane.pb"),
                     recursive=True)[0]
    with open(path, "rb") as f, gzip.open(
            os.path.join(out, "durable.xplane.pb.gz"), "wb") as g:
        g.write(f.read())
    print(json.dumps({"bytes": os.path.getsize(path),
                      "kind": jax.devices()[0].device_kind, "ticks": 32,
                      "flush_rows": stats["flush_rows"],
                      "np": np.__version__}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
