"""The trace reduction, on a stretch of a trace recorded on a TPU v5e (a
paper-cnn chain launch: the idle gap between programs before the first
local phase), checked against a brute-force reading of the same events."""
import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path[:0] = [ROOT]

from bench import trace  # noqa: E402

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                    "cnn_chain_slice.json")


@pytest.fixture(scope="module")
def recorded():
    with open(DATA) as f:
        return json.load(f)


def _busy_bruteforce(events, t0, t1, step=100):
    """Busy ns on a 100 ns grid: a point is busy if any event covers it."""
    pts = range(int(t0), int(t1), step)
    cover = [any(s <= p < s + d for _, s, d in events) for p in pts]
    return sum(cover) * step


def test_busy_and_window_match_bruteforce(recorded):
    r = trace.reduce(recorded)
    t0, t1 = trace.window_of(recorded["host"])
    dev = recorded["devices"]["/device:TPU:0"]
    assert r["window_s"] == pytest.approx((t1 - t0) / 1e9)
    brute = _busy_bruteforce(dev, t0, t1) / 1e9
    edges = len(trace.merge([(s, s + d) for _, s, d in dev]))
    assert r["busy_s"] == pytest.approx(brute, abs=2 * 100e-9 * edges)
    assert 0 < r["busy_s"] < r["window_s"]


def test_own_times_add_up_to_busy(recorded):
    """Nested ops (a while op spans its body) are counted once."""
    r = trace.reduce(recorded)
    assert sum(v for _, v in r["device_ops"]) == pytest.approx(
        r["busy_s"], rel=1e-9)
    assert all(v >= 0 for _, v in r["device_ops"])


def test_idle_gaps_add_up_and_are_labelled_by_bench_spans(recorded):
    r = trace.reduce(recorded)
    idle = sum(v for _, v in r["idle_gaps"])
    assert idle == pytest.approx(r["window_s"] - r["busy_s"], rel=1e-9)
    assert all(label.startswith("bench.launch") for label, _ in
               r["idle_gaps"])
    # the longest gap is the host dispatching eagerly between programs
    assert r["idle_gaps"][0][1] > 5e-4


def test_gemm_kernel_events_found(recorded):
    from bench import harness
    roof = harness.load_module("metrics", "im2col_gemm_roofline")
    r = trace.reduce(recorded)
    gemms = [e for e in r["events"] if roof.is_gemm(e[0])]
    assert gemms and all("tpu_custom_call" in e[0] for e in gemms)
    assert not any(roof.is_gemm(e[0]) for e in r["events"]
                   if "tpu_custom_call" not in e[0])


def test_merge_and_self_times_on_hand_worked_events():
    ev = [["%while.1 = w", 0, 100], ["%a.1 = a", 10, 20],
          ["%b.2 = b", 40, 30], ["%c.3 = c", 150, 10]]
    assert trace.merge([(s, s + d) for _, s, d in ev]) == [(0, 100),
                                                          (150, 160)]
    own = trace.self_times(ev)
    assert own == {"while.1": 50, "a.1": 20, "b.2": 30, "c.3": 10}
    host = [["bench.unit", 0, 200, "python"],
            ["bench.launch", 1, 198, "python"],
            ["$plan.py:1 host_work", 100, 50, "python"]]
    r = trace.reduce({"devices": {"/device:TPU:0": ev}, "host": host})
    assert r["busy_s"] == pytest.approx(110e-9)
    assert r["window_s"] == pytest.approx(200e-9)
    assert r["idle_gaps"][0] == ["bench.launch / $plan.py:1 host_work",
                                 pytest.approx(50e-9)]
