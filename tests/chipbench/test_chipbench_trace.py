"""``trace_reduce`` on a hand-made trace and on a trace recorded on one
TPU v5e (a 4.6 s window of ``tpch40-fit``: three cold fits, whose first
cover rounds run the ``span_gain`` kernel)."""

import json
import os

import numpy as np
import pytest

from chipbench import roofline, trace_reduce

FIXTURE = os.path.join(os.path.dirname(__file__), "fixtures",
                       "v5e_tpch40_fit_trace.json")


def hand_trace():
    ms = 1_000_000
    return {
        "device": [
            [0, "span_gain", 10 * ms, 4 * ms],
            [0, "span_gain", 12 * ms, 4 * ms],   # overlaps: union 10-16
            [0, "copy", 30 * ms, 5 * ms],
            [0, "copy", 95 * ms, 10 * ms],       # half outside the window
            [0, "copy", 150 * ms, 5 * ms],       # wholly outside
        ],
        "host": [
            ["window", 0, 100 * ms],
            ["fit", 5 * ms, 30 * ms],                # 5-35
            ["generate", 35 * ms, 20 * ms],          # 35-55
        ],
    }


def test_busy_idle_and_ops_on_a_hand_trace():
    r = trace_reduce.reduce(hand_trace())
    assert r["window_s"] == pytest.approx(0.100)
    # busy: 10-16, 30-35, 95-100 -> 16 ms
    assert r["busy_s"] == pytest.approx(0.016)
    assert r["idle_share"] == pytest.approx(0.84)
    assert r["ops"]["span_gain"] == pytest.approx(0.008)
    assert r["ops"]["copy"] == pytest.approx(0.010)
    assert r["calls"] == {"span_gain": 2, "copy": 2}
    # idle gaps 0-10, 16-30, 35-95: fit covers 5-10 and 16-30 (19 ms),
    # generate 35-55 (20 ms), the rest under no annotation (45 ms)
    assert r["idle"]["fit"] == pytest.approx(0.019)
    assert r["idle"]["generate"] == pytest.approx(0.020)
    assert r["idle"]["harness"] == pytest.approx(0.045)
    assert r["longest_gap_s"] == pytest.approx(0.060)


def test_reduce_wants_one_window():
    t = hand_trace()
    t["host"].append(["window", 0, 10])
    with pytest.raises(ValueError):
        trace_reduce.reduce(t)


def brute_union(rows, lo, hi):
    """Busy time by a sweep over every interval boundary."""
    pts = sorted({lo, hi} | {min(max(x, lo), hi) for s, e in rows
                             for x in (s, e)})
    busy = 0
    for a, b in zip(pts, pts[1:]):
        mid = (a + b) / 2
        if any(s <= mid < e for s, e in rows):
            busy += b - a
    return busy


def test_recorded_v5e_trace():
    with open(FIXTURE) as f:
        trace = json.load(f)
    assert os.path.getsize(FIXTURE) < 200_000
    r = trace_reduce.reduce(trace)
    (lo, hi), = [(s, s + d) for n, s, d in trace["host"] if n == "window"]
    rows = [(s, s + d) for _, _, s, d in trace["device"]]
    assert r["busy_s"] == pytest.approx(brute_union(rows, lo, hi) * 1e-9)
    assert 0 < r["idle_share"] < 1
    assert sum(r["idle"].values()) == pytest.approx(
        r["window_s"] - r["busy_s"])
    kernel = roofline.KERNELS["span_gain"]
    names = [n for n in r["ops"] if kernel.matches(n)]
    assert names and sum(r["ops"][n] for n in names) > 0
    for n in names:
        a, w2, n_parts = kernel.shapes(n)
        assert a % 8 == 0 and n_parts % 128 == 0 and w2 >= 2
        assert r["calls"][n] >= 1
    assert set(r["idle"]) <= {"fit", "harness"}
    assert np.isfinite(r["longest_gap_s"])
