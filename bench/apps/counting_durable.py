"""The durable counting deployment: ``counting``'s app and sizes, run with
a durability directory, so every tick's sources go to the write-ahead log
and every updater's dirty slates to the slate store at each flush
(paper sections 4.2-4.3).

Three things differ from ``counting``:

- ``runtime(cfg)`` points ``durable_dir`` at a fresh directory under the
  checkout's ``.bench_out/``, emptied at the start and removed at exit.
- ``read`` is the crash check.  The window ends on a flush as often as
  not (a flush every 16 engine ticks, windows of 32-tick segments), so
  the check first runs a tail on the window's app: events on the sampled
  keys it holds, drawn from the sample, until one whole chunk of them
  lies past the last flush.  Then the app crashes: its log writer and
  store threads stop with no flush.  A fresh app recovers from the same
  directory (the store at the frontier, then the log's suffix, the
  unflushed chunk in it, replayed), drains, and the sample is read
  through the recovered ``StateHandle``, less what the tail added.  The
  recovery's seconds, restored rows and replayed ticks go to stderr.
- Set-up has a deadline (``setup_deadline_s``, counted from
  ``runtime``): past it the process prints why and exits with status 1.
  Set-up ends with the harness's first ``processed`` call, which it
  makes as the window opens.
"""
from __future__ import annotations

import atexit
import itertools
import os
import shutil
import sys
import threading
import time

import jax
import numpy as np

from bench.apps import counting
from bench.apps.counting import (UPDATERS, batch, build, live_slates,  # noqa: F401
                                 telemetry_gaps)

OUT = os.path.join(os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))), ".bench_out")
_dirs = itertools.count()


class _Deadline:
    """Ends the process with status 1, and removes ``dir``, ``seconds``
    from now unless cancelled.  A thread of its own waits, so a main
    thread blocked anywhere (a flush waiting for the store) is stopped
    too."""

    def __init__(self, seconds: float, dir: str):
        self.seconds = seconds
        self.dir = dir
        self.done = threading.Event()
        threading.Thread(target=self._watch, daemon=True).start()

    def _watch(self):
        if self.done.wait(self.seconds):
            return
        print(f"bench: set-up passed {self.seconds} s", file=sys.stderr,
              flush=True)
        shutil.rmtree(self.dir, ignore_errors=True)
        os._exit(1)

    def cancel(self):
        self.done.set()


class _Run:
    """The configuration and the durability directory of this process's
    run, its set-up deadline, and what its crash check recovered."""

    def __init__(self, cfg: dict):
        self.cfg = cfg
        self.recovery = None
        self.dir = os.path.join(OUT, f"durable-{os.getpid()}-{next(_dirs)}")
        shutil.rmtree(self.dir, ignore_errors=True)
        os.makedirs(self.dir)
        atexit.register(shutil.rmtree, self.dir, True)
        self.deadline = _Deadline(float(cfg["setup_deadline_s"]), self.dir)

    def runtime(self):
        rt = counting.runtime(self.cfg)
        rt.durable_dir = self.dir
        return rt


_run: _Run = None


def runtime(cfg: dict):
    global _run
    if _run is not None:
        _run.deadline.cancel()
    _run = _Run(cfg)
    rt = _run.runtime()
    dur = rt.engine_config().durability
    got = {k: getattr(dur, k) for k in cfg["store"]}
    if got != cfg["store"]:
        raise ValueError(f"the runtime's store settings {got} are not the "
                         f"configuration's {cfg['store']}")
    return rt


def processed(state) -> dict:
    _run.deadline.cancel()
    return counting.processed(state)


def _room(engine, state) -> int:
    """Engine ticks the app can run before its next flush falls due (the
    rule of ``EngineDurability.due`` for a flush every k ticks)."""
    k = engine.dur.cfg.flush.every_k
    tick = int(jax.device_get(state["tick"]))
    return k * (engine.dur.frontier.tick // k + 1) - 1 - tick


class _Tail:
    """Events the crash check adds after the window: full batches over
    the sampled keys the app holds, each with a uniform value byte, drawn
    from the sample (so from the seed), and what they add to each sampled
    key's count and lanes."""

    def __init__(self, keys, held, batch: int, lanes: int):
        self.keys = np.asarray(keys)
        self.held = np.nonzero(held)[0]
        self.rng = np.random.default_rng(self.keys.astype(np.int64))
        self.batch, self.lanes = batch, lanes
        self.count = np.zeros(self.keys.size, np.int64)
        self.sums = np.zeros((self.keys.size, lanes), np.float64)
        self.ticks = 0

    def source(self, t, max_events):
        n = self.batch if max_events is None else min(self.batch,
                                                       max_events)
        pos = self.held[self.rng.integers(0, self.held.size, self.batch)]
        bits = self.rng.integers(0, 1 << self.lanes, self.batch,
                                 dtype=np.uint8)
        self.count += np.bincount(pos[:n], minlength=self.keys.size)
        for j in range(self.lanes):
            self.sums[:, j] += np.bincount(
                pos[:n], weights=(bits[:n] >> j) & 1,
                minlength=self.keys.size)
        return batch(self.keys[pos], bits, n, t)

    def run(self, handle, n_ticks: int):
        """``n_ticks`` ticks of the tail through ``Engine.run``, in whole
        chunks (the offset stays a multiple of the chunk)."""
        handle.state, _ = handle.engine.run(
            handle.state, self.source, n_ticks, source_offset=self.ticks,
            handle=handle)
        self.ticks += n_ticks


def read(handle, keys, lanes: int):
    """The crash check (module docstring): ``counting.read``'s result
    for ``keys`` from the app recovered after the tail, less the tail's
    own counts and lane sums."""
    engine = handle.engine
    chunk = engine.cfg.chunk_size
    tail = _Tail(keys, counting.read(handle, keys, lanes)[0],
                 int(_run.cfg["events_per_tick"]), lanes)
    for _ in range(4):
        if _room(engine, handle.state) >= chunk:
            break
        tail.run(handle, chunk)          # runs into a flush
    else:
        raise RuntimeError("the crash check found no chunk's room "
                           "before a flush")
    tail.run(handle, chunk)
    unflushed = int(jax.device_get(handle.state["tick"])) \
        - engine.dur.frontier.tick
    if unflushed < chunk:
        raise RuntimeError(f"the crash check's last chunk was flushed "
                           f"({unflushed} ticks past the frontier)")
    engine.dur.halt()                    # the crash

    t0 = time.perf_counter()
    app = build(_run.cfg)
    app.start(_run.runtime(), recover=True)
    app.run(None, 0, drain=True)
    jax.block_until_ready(app.handle.state)
    rec = _run.recovery = dict(app.engine.last_recovery,
                               seconds=time.perf_counter() - t0,
                               tail_ticks=tail.ticks, unflushed=unflushed)
    print(f"counting-durable: recovery {rec['seconds']:.3f} s "
          f"(restore {rec['restore_s']:.3f} s of {rec['restored']} rows, "
          f"replay {rec['replay_s']:.3f} s of {rec['replayed_ticks']} ticks "
          f"from frontier tick {rec['frontier']}; a tail of {tail.ticks} "
          f"ticks left {unflushed} past it)", file=sys.stderr, flush=True)
    try:
        present, torn, count, vec = counting.read(app.handle, keys, lanes)
    finally:
        app.close()
    return present, torn, count - tail.count, vec - tail.sums
