#!/usr/bin/env python3
"""The control of ``correct``: the reference put in the program's place,
computed one precision below what the configuration states (bfloat16
sums for the int32 counts and f32 lanes), and judged by the harness's
own comparison and limits.  It must come out not correct.  The
benchmark's own runs never run it.

    python3 bench/control.py --workload <cell> --seeds 1,2,3 --rate <events/s>

Per seed it makes the events one run of the cell delivers at its own
size (the load phase, the warm-up and ``--seconds`` at ``--rate``
events/s), reads the same sample of keys a run reads back, and prints
the numbers compared with their limits.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def control_slates(keys: np.ndarray, bits: np.ndarray, lanes: int,
                   query: np.ndarray):
    """Per ``query`` key: event count and lane sums, accumulated in
    bfloat16 on the default device.  ``(count [Q], lanes [Q, L])``."""
    import jax
    import jax.numpy as jnp
    from bench.reference import lane_bits
    order = np.argsort(query)
    qs = query[order]
    pos = np.searchsorted(qs, keys)
    hit = (pos < qs.size) & (qs[np.minimum(pos, qs.size - 1)] == keys)
    seg = np.where(hit, order[np.minimum(pos, qs.size - 1)], query.size)
    data = np.concatenate([np.ones((keys.size, 1), np.float32),
                           lane_bits(bits, lanes).astype(np.float32)], 1)
    out = jax.jit(lambda d, s: jax.ops.segment_sum(
        d.astype(jnp.bfloat16), s, num_segments=query.size + 1),
    )(jnp.asarray(data), jnp.asarray(seg, jnp.int32))
    out = np.asarray(jax.device_get(out)).astype(np.float64)[:-1]
    return out[:, 0], out[:, 1:]


def control(workload: str, seed: int, rate: float, seconds=None,
            overrides=None) -> dict:
    """The control's reading on one seed: the numbers compared, with
    their limits, and ``correct`` as the harness judges them."""
    from bench import harness, traffic
    from bench.reference import Reference
    spec = harness.load_spec(ROOT)
    cell, cfg, mix = harness.resolve(spec, workload, ROOT)
    if overrides:
        overrides(cfg, mix)
    seconds = seconds or spec["run_seconds"]
    lanes = int(cfg["lanes"])
    per = int(cfg["events_per_tick"])
    warm = int(cfg["warmup_segments"]) * int(cfg["segment_ticks"])
    rngs = traffic.streams(seed)
    gen = traffic.Generator(mix, rngs, batch=per)
    while gen.loading:
        gen.take()
    for _ in range(warm + int(rate * seconds / per) + 1):
        gen.take()
    d = gen.delivered()
    ref = Reference(d.keys, d.bits, lanes)
    q = harness._sample(ref, gen.zipf.ids.size, int(cfg["check_keys"]),
                        rngs["sample"])
    count, vec = control_slates(d.keys, d.bits, lanes, q)
    checks = harness.compare((count > 0, np.zeros(q.size, bool), count,
                              vec), ref, q)
    return {"workload": workload, "seed": seed, "events": int(d.keys.size),
            "correct": harness.judge(checks), "checks": checks}


def main():
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=None,
                    help="window length; BENCHMARK.json's run_seconds")
    ap.add_argument("--rate", type=float, required=True,
                    help="events/s the cell's runs apply")
    args = ap.parse_args()
    import jax
    kind = jax.devices()[0].device_kind
    for s in args.seeds.split(","):
        out = control(args.workload, int(s), args.rate, args.seconds)
        print(json.dumps({**out, "device": kind}), flush=True)


if __name__ == "__main__":
    main()
