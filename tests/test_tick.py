"""One tick for one shard and many (``core/tick.py``).

``DistributedEngine`` runs the same tick function as ``Engine``, with
the ring route and the exchange as its ``send``.  On a one-device mesh
every batch routes to the one shard, so both engines must leave the
same state bit for bit, tick after tick, whatever the tick exercises.
"""
import jax
import numpy as np
import pytest
from jax.sharding import Mesh

from repro.core.distributed import DistConfig, DistributedEngine
from repro.core.engine import Engine, EngineConfig
from repro.core.queues import OverflowPolicy
from repro.core.workflow import Workflow
from repro.telemetry.metrics import TelemetryConfig
from tests.conftest import (CountingUpdater, LastValueUpdater,
                            PassThroughMapper, make_batch)


class FusedCounter(CountingUpdater):
    sum_mergeable = True


class SourceCounter(CountingUpdater):
    """Reads the source stream itself, so its queue fills from it."""
    subscribes = ("S1",)


class SpillCounter(CountingUpdater):
    name = "U3"
    subscribes = ("OV",)


class ExpiringCounter(CountingUpdater):
    ttl = 2


class DeferringLast(LastValueUpdater):
    max_run = 2


# (operators, config overrides, events a tick, distinct keys a tick,
#  the stat that shows the case ran its mechanism)
CASES = {
    "fused-off": ([PassThroughMapper(), FusedCounter()],
                  dict(fused="off"), 24, 9, None),
    "fused-interpret": ([PassThroughMapper(), FusedCounter()],
                        dict(fused="interpret"), 24, 9, None),
    "sequential-defers": ([PassThroughMapper(), CountingUpdater(),
                           DeferringLast()], {}, 24, 5, "deferred"),
    "overflow-stream": ([SourceCounter(), SpillCounter()],
                        dict(overflow={"U1": OverflowPolicy.OVERFLOW_STREAM},
                             overflow_stream={"U1": "OV"}),
                        12, 9, "spilled"),
    "throttle": ([SourceCounter()],
                 dict(overflow={"U1": OverflowPolicy.THROTTLE}),
                 12, 9, "throttle_hits"),
    "ttl": ([PassThroughMapper(), ExpiringCounter()], {}, 8, 40, "expired"),
    "telemetry": ([PassThroughMapper(), CountingUpdater(),
                   DeferringLast()],
                  dict(telemetry=TelemetryConfig(width=64, impl="jnp")),
                  24, 7, None),
}


def _sources(t, n, n_keys):
    rng = np.random.default_rng(t)
    keys = (rng.integers(0, n_keys, n) + 4 * t) % 40
    return {"S1": make_batch(keys, rng.integers(-9, 9, n),
                             ts=np.full(n, t, np.int32))}


def _bits(tree):
    return [(jax.tree_util.keystr(p), np.asarray(x).dtype,
             np.asarray(x).tobytes())
            for p, x in jax.tree_util.tree_leaves_with_path(tree)]


@pytest.mark.parametrize("case", list(CASES))
def test_sharded_tick_on_one_shard_matches_engine_bitwise(case):
    """Tables, queues, ``processed``, ``deferred``, ``throttle_hits``,
    the sketch and the histograms agree bit for bit after every tick,
    and so do the ticks' outputs."""
    ops, over, n_events, n_keys, shows = CASES[case]
    streams = ("S1", "OV") if "overflow_stream" in over else ("S1",)
    wf = Workflow(ops, external_streams=streams)
    kw = dict(batch_size=8, queue_capacity=16, **over)
    single = Engine(wf, EngineConfig(**kw))
    mesh = Mesh(np.asarray(jax.devices()[:1]), ("data",))
    sharded = DistributedEngine(wf, mesh,
                                DistConfig(exchange_slack=4.0, **kw))
    s1, s2 = single.init_state(), sharded.init_state()
    seen = set()
    for t in range(8):
        src = _sources(t, n_events, n_keys)
        seen.update(np.asarray(src["S1"].key).tolist())
        s1, out1 = single.step(s1, src)
        s2, out2 = sharded.step(s2, {s: jax.tree.map(lambda x: x[None], b)
                                     for s, b in src.items()})
        local = jax.tree.map(lambda x: x[0], s2)
        assert int(local.pop("exchange_dropped")) == 0
        assert _bits(local) == _bits(s1), t
        assert _bits(jax.tree.map(lambda x: x[0], out2)) == _bits(out1), t
    st = single.stats(s1)
    if shows == "spilled":
        assert st["processed"]["U3"] > 0
    elif shows == "expired":
        assert st["table_occupancy"]["U1"] < len(seen)
    elif shows is not None:
        assert st[shows] > 0
    if "telemetry" in over:
        assert set(s1) >= {"sketch", "lat_hist"}
