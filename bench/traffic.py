"""The one traffic generator.  A mix is a JSON file of parameters under
``bench/traffic/``; this module reads it and makes the events of one run
from ``--seed``, in vectorised numpy blocks, ahead of the source calls.

Parameters of a mix (see ``bench/traffic/*.json``):

- ``arrivals.kind``: ``"saturate"``: every source call returns a full
  batch, or ``max_events`` when the engine passes it.
- ``keys``: ``{"dist": "zipf", "alpha": a, "ids": n, "load": bool}``.
  Ranks are drawn from Zipf(a) over ``n`` ids (ids ``0 .. n-1``) and
  mapped through a seeded permutation so hot ranks land on scattered
  ids.  With ``load``, a load phase comes first: every id once, in
  ascending order, so every key has its slate before the measured
  events start (a YCSB load phase before its run phase).
- ``values``: ``{"kind": "lane_bits", "lanes": L}``: one uniform byte per
  event whose bit ``j`` is what the event adds to lane ``j`` (0 or 1).

Nothing here imports the program under test.
"""
from __future__ import annotations

import json
import os
from dataclasses import dataclass
from typing import List, Optional

import numpy as np

BLOCK = 1 << 18          # events generated per refill


def load_mix(name: str, root: str) -> dict:
    with open(os.path.join(root, "traffic", f"{name}.json")) as f:
        return json.load(f)


def streams(seed: int):
    """Independent generators for event keys, load values, the check
    sample and the rank permutation; ``seed`` may exceed 32 bits."""
    ss = np.random.SeedSequence(int(seed))
    return dict(zip(("keys", "load", "sample", "perm"),
                    (np.random.default_rng(s) for s in ss.spawn(4))))


class Zipf:
    """Zipf(alpha) over ``ids`` ids through a seeded permutation.  The CDF
    is built once; draws are one ``searchsorted`` per block."""

    def __init__(self, alpha: float, ids: int, perm_rng):
        p = np.arange(1, ids + 1, dtype=np.float64) ** -float(alpha)
        self.cdf = np.cumsum(p)
        self.hottest_share = float(p[0] / self.cdf[-1])
        self.cdf /= self.cdf[-1]
        self.ids = perm_rng.permutation(ids).astype(np.int32)

    def draw(self, rng, n: int) -> np.ndarray:
        ranks = np.searchsorted(self.cdf, rng.random(n), side="right")
        return self.ids[np.minimum(ranks, self.ids.size - 1)]


@dataclass
class Delivered:
    """Every event a run handed to the program, in delivery order."""
    keys: np.ndarray
    bits: np.ndarray


class Generator:
    """Source of one run.  ``take(max_events)`` returns ``(keys [B],
    bits [B], n_valid)`` for one tick; rows past ``n_valid`` are
    padding."""

    def __init__(self, mix: dict, rngs: dict, *, batch: int):
        k = mix["keys"]
        if k["dist"] != "zipf":
            raise ValueError(f"unknown key distribution {k['dist']!r}")
        v = mix["values"]
        if v["kind"] != "lane_bits" or not 1 <= v["lanes"] <= 8:
            raise ValueError(f"unknown value kind {v!r}")
        if mix["arrivals"]["kind"] != "saturate":
            raise ValueError(f"unknown arrivals {mix['arrivals']!r}")
        self.zipf = Zipf(k["alpha"], k["ids"], rngs["perm"])
        self.lanes = int(v["lanes"])
        self.batch = batch
        self._krng, self._lrng = rngs["keys"], rngs["load"]
        self._next_load = 0 if k.get("load") else self.zipf.ids.size
        # generated, not yet delivered
        self._keys = np.zeros(0, np.int32)
        self._bits = np.zeros(0, np.uint8)
        self._out_k: List[np.ndarray] = []
        self._out_b: List[np.ndarray] = []
        self.n_delivered = 0

    @property
    def loading(self) -> bool:
        """Ids of the load phase are still to be delivered."""
        return self._next_load < self.zipf.ids.size

    def _refill(self, need: int):
        if self._keys.size >= need:
            return
        n = max(BLOCK, need - self._keys.size)
        self._keys = np.concatenate([self._keys,
                                     self.zipf.draw(self._krng, n)])
        self._bits = np.concatenate(
            [self._bits, self._krng.integers(0, 1 << self.lanes, n,
                                             dtype=np.uint8)])

    def _take_load(self, n: int):
        lo = self._next_load
        hi = min(lo + n, self.zipf.ids.size)
        self._next_load = hi
        keys = np.arange(lo, hi, dtype=np.int32)
        return keys, self._lrng.integers(0, 1 << self.lanes, hi - lo,
                                         dtype=np.uint8)

    def take(self, max_events: Optional[int] = None):
        B = self.batch
        n = B if max_events is None else max(0, min(B, int(max_events)))
        if self.loading:
            k, b = self._take_load(n)
        else:
            self._refill(n)
            k, b = self._keys[:n], self._bits[:n]
            self._keys, self._bits = self._keys[n:], self._bits[n:]
        n = k.size
        keys = np.zeros(B, np.int32)
        bits = np.zeros(B, np.uint8)
        keys[:n], bits[:n] = k, b
        self._out_k.append(k)
        self._out_b.append(b)
        self.n_delivered += n
        return keys, bits, n

    def delivered(self) -> Delivered:
        cat = lambda xs, dt: (np.concatenate(xs) if xs
                              else np.zeros(0, dt))
        return Delivered(keys=cat(self._out_k, np.int32),
                         bits=cat(self._out_b, np.uint8))
