"""The trace reduction: busy and idle time, kernel time by name,
exposed collectives and the named idle gaps, on a hand-made trace with
known answers and on a small trace recorded on a TPU v5e."""
from __future__ import annotations

import os

import pytest

from chip import trace

HERE = os.path.dirname(os.path.abspath(__file__))
RECORDED = os.path.join(HERE, "data", "booking_train_trace.json.gz")

# one device, a 100 ns window marked by the benchmark:
#   ops: matmul 10-30, fusion 25-40 (overlaps), jpq_topk_pruned 50-60,
#        all-reduce 55-70 (half under the kernel), fusion 90-120 (ends
#        past the window)
#   spans: bench.step_dispatch 0-45, bench.wait_arrival 40-95
HAND = {
    "devices": {"/device:TPU:0": [
        ["matmul", 10, 20], ["fusion.1", 25, 15],
        ["jpq_topk_pruned", 50, 10], ["all-reduce.3", 55, 15],
        ["fusion.2", 90, 30]]},
    "spans": [["bench.window", 0, 100], ["bench.step_dispatch", 0, 45],
              ["bench.wait_arrival", 40, 95]],
}


def test_busy_and_idle_by_hand():
    r = trace.Reduced(HAND)
    # busy: 10-40, 50-70, 90-100 = 30 + 20 + 10 = 60 ns of 100
    assert r.window == (0, 100)
    assert r.busy_s() == pytest.approx(60e-9)
    assert r.idle_share() == pytest.approx(0.4)


def test_kernel_time_by_name():
    r = trace.Reduced(HAND)
    assert r.op_seconds(lambda n: "jpq_topk_pruned" in n) == \
        pytest.approx(10e-9)
    # clipped to the window
    assert r.op_seconds(lambda n: n == "fusion.2") == pytest.approx(10e-9)


def test_exposed_collective_by_hand():
    # all-reduce 55-70, the kernel covers 55-60: 10 ns exposed
    assert trace.Reduced(HAND).exposed_collective_s() == \
        pytest.approx(10e-9)


def test_top_ops_and_idle_gaps_by_hand():
    r = trace.Reduced(HAND)
    top = dict(r.top_ops(10))
    assert top["matmul"] == pytest.approx(20e-9)
    assert top["fusion.1"] == pytest.approx(15e-9)
    gaps = r.idle_gaps(10)
    # gaps: 0-10 (in dispatch), 40-50 and 70-90 (waiting for arrivals)
    assert [g[1] for g in gaps] == pytest.approx([20e-9, 10e-9, 10e-9])
    assert gaps[0][0] == "bench.wait_arrival"
    names = {round(g[1] * 1e9): g[0] for g in gaps}
    assert names[20] == "bench.wait_arrival"


def test_window_falls_back_to_the_ops():
    ev = {"devices": HAND["devices"], "spans": []}
    assert trace.Reduced(ev).window == (10, 120)


def _sweep_busy(ev, window):
    """Busy ns per device by a sweep over start and end events, a
    different count from the interval union the reduction takes."""
    total = 0
    for ops in ev["devices"].values():
        edges = sorted([(max(s, window[0]), 1) for _, s, d in ops
                        if s + d > window[0] and s < window[1]] +
                       [(min(s + d, window[1]), -1) for _, s, d in ops
                        if s + d > window[0] and s < window[1]])
        depth, last = 0, None
        for t, step in edges:
            if depth > 0:
                total += t - last
            depth += step
            last = t
    return total / len(ev["devices"])


def test_recorded_trace_agrees_with_a_sweep_count():
    ev = trace.load(RECORDED)
    r = trace.Reduced(ev)
    assert r.ops and r.window[1] > r.window[0]
    assert r.busy_s() * 1e9 == pytest.approx(_sweep_busy(ev, r.window))
    assert 0.0 <= r.idle_share() <= 1.0
    assert sum(s for _, s in r.top_ops(1000)) >= r.busy_s() * 0.999
    gaps = r.idle_gaps(10)
    assert all(g[0].startswith("bench.") or g[0] == "none" for g in gaps)
    # the training step dominates; the JPQ logits loop is its largest op
    assert r.idle_share() < 0.05
    assert r.top_ops(1)[0][0].startswith("while")
