"""The trace reduction: interval arithmetic by hand, and a small trace
recorded on a TPU v5e (``data/small.xplane.pb``)."""
import os

import pytest

from rbench import trace

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                    "small.xplane.pb")


def test_union_busy_and_gaps_by_hand():
    ops = {"/device:TPU:0": [(10, 20, "a"), (15, 30, "b"), (40, 50, "a"),
                             (95, 120, "c")]}
    w, other = "worker", "submitter"
    spans = [(0, 100, trace.WINDOW_SPAN, "main"),
             (12, 28, "bench.apply_one", w), (30, 40, "bench.publish", w),
             (10, 45, "bench.micro_batch", w), (60, 95, "bench.micro_batch", w),
             (0, 100, "bench.submit", other)]
    lo, hi = trace.window(spans)
    assert trace.union(ops["/device:TPU:0"], lo, hi) == [(10, 30), (40, 50),
                                                        (95, 100)]
    assert trace.busy_seconds(ops, lo, hi) == pytest.approx(35e-9)
    top = trace.top_ops(ops, lo, hi)
    assert [n for n, _ in top] == ["a", "b", "c"]
    assert [s for _, s in top] == pytest.approx([20e-9, 15e-9, 5e-9])
    gaps = trace.idle_gaps(ops, spans, lo, hi)
    # the worker's spans name the gaps, the submitter's do not:
    # [50, 95) is 35 of 45 in bench.micro_batch, [0, 10) outside every
    # worker span, [30, 40) in bench.publish, inside bench.micro_batch
    assert gaps == [["bench.micro_batch", pytest.approx(45e-9)],
                    [trace.WORKER_IDLE, pytest.approx(10e-9)],
                    ["bench.publish", pytest.approx(10e-9)]]


def _brute_busy(ops, lo, hi):
    """Busy nanoseconds by marking every covered nanosecond boundary."""
    edges = sorted({lo, hi} | {max(min(t, hi), lo) for s, e, _ in ops
                                for t in (s, e)})
    busy = 0.0
    for a, b in zip(edges, edges[1:]):
        mid = (a + b) / 2
        if any(s <= mid < e for s, e, _ in ops):
            busy += b - a
    return busy


def test_recorded_tpu_trace():
    profile = trace.load(os.path.dirname(DATA))
    dev, host = trace.events(profile)
    assert list(dev) == ["/device:TPU:0"]
    lo, hi = trace.window(host)
    ops = dev["/device:TPU:0"]
    got = trace.reduce(profile)
    assert got["busy_s"] == pytest.approx(_brute_busy(ops, lo, hi) * 1e-9)
    assert 0 < got["busy_s"] < got["window_s"]
    assert got["window_s"] == pytest.approx((hi - lo) * 1e-9)
    total = {}
    for s, e, name in ops:
        if min(e, hi) > max(s, lo):
            total[name] = total.get(name, 0.0) + min(e, hi) - max(s, lo)
    best = max(total, key=total.get)
    assert got["device_ops"][0][0] == best
    assert got["device_ops"][0][1] == pytest.approx(total[best] * 1e-9)
    assert got["idle_gaps"], "the recorded trace has idle gaps"
    assert {n for n, _ in got["idle_gaps"]} <= {
        "bench.apply_one", "bench.publish", trace.WORKER_IDLE}
