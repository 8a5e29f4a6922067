"""The counting application of the paper's Examples 1 and 4, built
through the program's own front door (``App`` + ``RuntimeConfig``).

A mapper ``parse`` passes checkins on; ``U1`` (``ops.counter``) counts
them per key and ``UV`` keeps an ``[8]``-lane sum per key, lane ``j``
adding bit ``j`` of the event's value byte.  ``UV`` is the single-leaf
slate the point-lookup kernel serves.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro import App, EventBatch, RuntimeConfig, TelemetryConfig, ops

UPDATERS = ("U1", "UV")


def build(cfg: dict) -> App:
    lanes = int(cfg["lanes"])
    cap = int(cfg["table_capacity"])
    app = App(cfg["name"])
    checkins = app.source("S1", {"x": ((), jnp.float32)})

    @app.mapper(checkins, out="S2")
    def parse(batch):
        return EventBatch(sid=batch.sid, ts=batch.ts + 1, key=batch.key,
                          value=batch.value, valid=batch.valid)

    parsed = app.stream("S2")
    parsed.update(ops.counter("U1", table_capacity=cap))
    shifts = jnp.arange(lanes, dtype=jnp.int32)

    @app.updater(parsed, slate={"v": ((lanes,), jnp.float32)}, name="UV",
                 table_capacity=cap)
    def lanes_of(batch):
        byte = batch.value["x"].astype(jnp.int32)
        return {"v": ((byte[:, None] >> shifts) & 1).astype(jnp.float32)}

    return app


def runtime(cfg: dict) -> RuntimeConfig:
    rt = dict(cfg["runtime"])
    tele = rt.pop("telemetry")
    return RuntimeConfig(telemetry=TelemetryConfig(**tele), **rt)


def batch(keys: np.ndarray, bits: np.ndarray, n_valid: int, tick: int):
    """``{"S1": EventBatch}`` for one tick from ``[B]`` arrays."""
    valid = np.arange(keys.shape[0]) < n_valid
    return {"S1": EventBatch(
        sid=jnp.zeros(keys.shape, jnp.int32),
        ts=jnp.full(keys.shape, tick, jnp.int32), key=jnp.asarray(keys),
        value={"x": jnp.asarray(bits.astype(np.float32))},
        valid=jnp.asarray(valid))}


def read_lanes(handle, updater: str, keys: np.ndarray, lanes: int):
    """One batched read of a lane slate: ``(present [Q], lanes [Q, L])``,
    zeros where absent."""
    rows = handle.read_slates(updater, keys)
    present = np.asarray([r is not None for r in rows], bool)
    vec = np.zeros((len(rows), lanes), np.float64)
    for i, r in enumerate(rows):
        if r is not None:
            vec[i] = np.asarray(r["v"])
    return present, vec


def read(handle, keys: np.ndarray, lanes: int):
    """Slates of ``keys`` through the read tier, one batched read per
    updater: ``(present [Q], torn [Q], count [Q], lanes [Q, L])``, with
    zeros where absent; ``torn`` marks keys present in one updater's
    table and not the other's."""
    c = handle.read_slates("U1", keys)
    has_c = np.asarray([r is not None for r in c], bool)
    count = np.asarray([int(r["count"]) if r is not None else 0
                        for r in c], np.int64)
    has_v, vec = read_lanes(handle, "UV", keys, lanes)
    return has_c & has_v, has_c != has_v, count, vec


def telemetry_gaps(state, rows_expected: int):
    """Telemetry kernels held to what needs no hash of the program's:
    the sketch's running total equals the events every updater
    processed, each count-min row sums to the events processed since
    the sketch's last reset, and each latency histogram totals its
    updater's processed count.  Returns ``(cm_gap, hist_gap)``."""
    g = jax.device_get
    proc = {u: int(np.sum(g(state["processed"][u]))) for u in UPDATERS}
    sk = g(state["sketch"])
    counts = np.asarray(sk["counts"])
    rows = counts.reshape(-1, *counts.shape[-2:]).sum(axis=(0, 2))
    total = int(np.sum(sk["total"]))
    all_proc = sum(proc.values())
    cm_gap = max(abs(total - all_proc),
                 int(np.max(np.abs(rows - rows_expected))))
    hist_gap = 0
    for u in UPDATERS:
        h = int(np.sum(g(state["lat_hist"][u]["counts"])))
        hist_gap = max(hist_gap, abs(h - proc[u]))
    return cm_gap, hist_gap


def live_slates(state) -> dict:
    """Slates each updater's table holds."""
    return {u: int(state["tables"][u].occupancy()) for u in UPDATERS}


def processed(state) -> dict:
    g = jax.device_get
    return {u: int(np.sum(g(state["processed"][u]))) for u in UPDATERS}
