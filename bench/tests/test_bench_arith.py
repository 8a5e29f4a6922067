"""The yardstick's arithmetic on the CPU: the numpy reference against
hand counts, the generator's load phase and batches, kernel byte counts
and the exactness guard."""
import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path[:0] = [ROOT]

from bench import traffic, work  # noqa: E402
from bench.reference import (EXACT_LIMIT, Reference,  # noqa: E402
                             YardstickError, exactness_guard)


def test_reference_matches_hand_counts():
    keys = np.array([5, 3, 5, 5, 9, 3], np.int32)
    bits = np.array([0b01, 0b10, 0b11, 0b00, 0b01, 0b01], np.uint8)
    ref = Reference(keys, bits, lanes=2)
    count, lanes = ref.final(np.array([3, 5, 9, 7]))
    assert count.tolist() == [2, 3, 1, 0]
    assert lanes.tolist() == [[1, 1], [2, 1], [1, 0], [0, 0]]
    # after the first four events only
    count, lanes = ref.at(np.array([3, 5, 9]), 4)
    assert count.tolist() == [1, 3, 0]
    assert lanes.tolist() == [[0, 1], [2, 1], [0, 0]]
    assert ref.max_sum() == 3


def test_exactness_guard_stops_at_two_to_the_24():
    ok = Reference(np.zeros(10, np.int32), np.zeros(10, np.uint8), 8)
    exactness_guard(ok)

    class Hot(Reference):
        def max_sum(self):
            return EXACT_LIMIT
    with pytest.raises(YardstickError):
        exactness_guard(Hot(ok.keys, ok.bits, 8))


def _mix(load=False):
    return {"arrivals": {"kind": "saturate"},
            "keys": {"dist": "zipf", "alpha": 0.99, "ids": 4096,
                     "load": load},
            "values": {"kind": "lane_bits", "lanes": 8}}


def test_saturating_source_fills_batches_and_honours_max_events():
    g = traffic.Generator(_mix(), traffic.streams(3), batch=64)
    k, b, n = g.take()
    assert k.shape == (64,) and n == 64
    k, b, n = g.take(max_events=10)
    assert n == 10 and (k[10:] == 0).all()
    assert g.delivered().keys.size == 64 + 10
    assert not g.loading


def test_load_phase_delivers_every_id_once_first():
    g = traffic.Generator(_mix(load=True), traffic.streams(2**33 + 5),
                          batch=1000)
    got = []
    while g.loading:
        k, b, n = g.take(max_events=700)
        got.append(k[:n])
    assert np.array_equal(np.concatenate(got), np.arange(4096))
    assert n == 4096 - 5 * 700
    k, b, n = g.take()                 # then the Zipf draws
    assert n == 1000 and k.max() < 4096
    d = g.delivered()
    assert d.keys.size == 4096 + 1000
    # the same seed gives the same events
    g2 = traffic.Generator(_mix(load=True), traffic.streams(2**33 + 5),
                           batch=1000)
    while g2.loading:
        g2.take(max_events=700)
    g2.take()
    assert np.array_equal(g2.delivered().keys, d.keys)
    assert np.array_equal(g2.delivered().bits, d.bits)


def test_zipf_hottest_share():
    z = traffic.Zipf(0.99, 1 << 21, np.random.default_rng(0))
    assert z.hottest_share == pytest.approx(0.0616, rel=0.01)
    # the traffic file's exactness reckoning: 51 s below 2^24
    assert z.hottest_share * 5.3e6 * 51 < EXACT_LIMIT


def test_slate_update_bytes_at_the_cells_shapes():
    # one tick of counting.flood: 8192 events, ~4.9k distinct keys
    b1 = work.slate_update_bytes(8192, 4900, 1)
    b8 = work.slate_update_bytes(8192, 4900, 8)
    assert b1 == 4 * (2 * 8192 + 8192 + 2 * 4900)
    assert b8 == 4 * (2 * 8192 + 8 * 8192 + 2 * 4900 * 8)
    ticks = [(np.array([1, 1, 2, 9]), 3), (np.array([3, 3, 3, 3]), 4)]
    assert work.traced_slate_update_bytes(ticks, (1, 8)) == (
        work.slate_update_bytes(3, 2, 1) + work.slate_update_bytes(3, 2, 8)
        + work.slate_update_bytes(4, 1, 1)
        + work.slate_update_bytes(4, 1, 8))
