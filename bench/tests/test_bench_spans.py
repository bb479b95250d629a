"""The reduction of the program's spans (``rbench/spans.py``) by hand, on
the recorded traces, and through ``spans.py`` on a tiny traced run."""
import json
import os
import subprocess
import sys

import numpy as np
import pytest

from rbench import spans, trace

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
DATA = os.path.join(BENCH, "tests", "data")
SMALL = os.path.join(DATA, "small.xplane.pb")
SERVED = os.path.join(DATA, "served.xplane.pb")
SRC = os.path.join(os.path.dirname(BENCH), "src")

# the spans a served micro-batch on the device engine always opens
BATCH_SPANS = {"ripple.serve.micro_batch", "ripple.serve.take",
               "ripple.graph.topology", "ripple.engine.route",
               "ripple.mirror.refresh", "ripple.engine.dispatch",
               "ripple.engine.device_wait", "ripple.engine.commit_gather",
               "ripple.serve.publish"}


def _profile(path):
    from jax.profiler import ProfileData
    return ProfileData.from_file(path)


def test_self_time_idle_and_gap_names_by_hand():
    """Nested program spans clipped to the window; the benchmark's spans
    count for idle time and gap names but not for self time; another
    thread's spans count for nothing."""
    w, other = "worker", "submitter"
    host = [(10, 80, trace.WINDOW_SPAN, "main"),
            (0, 100, "ripple.serve.micro_batch", w),
            (5, 15, "ripple.serve.take", w),
            (18, 90, "bench.apply_one", w),
            (20, 62, "ripple.engine.route", w),
            (22, 45, "ripple.graph.topology", w),
            (0, 100, "ripple.serve.take", other)]
    ops = {"/device:TPU:0": [(30, 50, "a"), (70, 75, "b")]}
    lo, hi = trace.window(host)
    worker = spans.worker_spans(host)
    assert sorted(worker) == sorted((s, e, n) for s, e, n, th in host
                                    if th == w)
    # take clipped to [10, 15); route less topology; the root keeps the
    # rest of the window, bench.apply_one's share included
    assert spans.self_seconds(worker, lo, hi) == pytest.approx({
        "ripple.engine.route": 19e-9, "ripple.graph.topology": 23e-9,
        "ripple.serve.micro_batch": 23e-9, "ripple.serve.take": 5e-9})
    # idle: [10, 30), [50, 70), [75, 80)
    assert spans.idle_by_span(ops, worker, lo, hi) == pytest.approx({
        "bench.apply_one": 15e-9, "ripple.engine.route": 14e-9,
        "ripple.graph.topology": 8e-9, "ripple.serve.take": 5e-9,
        "ripple.serve.micro_batch": 3e-9})
    assert spans.idle_gaps(ops, worker, lo, hi) == [
        ["ripple.graph.topology", pytest.approx(20e-9)],
        ["ripple.engine.route", pytest.approx(20e-9)],
        ["bench.apply_one", pytest.approx(5e-9)]]


def test_innermost_is_the_shortest_covering_span():
    """Every instant goes to the shortest span covering it, as gap naming
    counts it, on random spans; the pieces cover what the spans cover."""
    rng = np.random.default_rng(7)
    for _ in range(20):
        sp = []
        for _ in range(rng.integers(1, 6)):
            s, length = int(rng.integers(0, 400)), int(rng.integers(1, 200))
            for _ in range(int(rng.integers(1, 4))):
                sp.append((s, s + length, f"s{len(sp)}"))
                s += int(rng.integers(0, max(length // 3, 1)))
                length = max(length // 2 - 1, 1)
        lo, hi = 50, 350
        pieces = spans.innermost(sp, lo, hi)
        for a, b, n in pieces:
            mid = (a + b) / 2
            cover = [(e - s, name) for s, e, name in sp if s <= mid < e]
            assert lo <= a < b <= hi and min(cover)[1] == n
        assert sum(b - a for a, b, _ in pieces) == sum(
            e - s for s, e in trace.union(sp, lo, hi))


def test_benchmark_numbers_unchanged_on_the_first_recorded_trace():
    """On a trace with no program spans, ``reduce`` gives ``trace.reduce``'s
    numbers, as the benchmark first recorded them, and puts every idle
    instant down once."""
    got = spans.reduce(_profile(SMALL))
    assert got == dict(trace.reduce(_profile(SMALL)), spans={},
                       idle_by_span=got["idle_by_span"])
    assert (got["busy_s"], got["window_s"]) == (8.385900000000001e-05,
                                                0.030899107000000002)
    assert got["device_ops"] == [
        ["jit__lambda/fusion", 3.5457e-05],
        ["jit__lambda/add_reduce_fusion", 3.0658000000000004e-05],
        ["jit__lambda/copy-done", 1.7704000000000002e-05],
        ["jit__lambda/copy-start", 4e-08]]
    idle = trace.WORKER_IDLE
    assert got["idle_gaps"] == [
        [idle, 0.005509719000000001], [idle, 0.004412442],
        [idle, 0.004347800000000001], [idle, 0.004341229],
        ["bench.publish", 0.0033144370000000004],
        ["bench.publish", 0.003236725], ["bench.publish", 0.003202045],
        ["bench.publish", 0.002450844], [idle, 2e-09], [idle, 2e-09]]
    assert sum(got["idle_by_span"].values()) == pytest.approx(
        got["window_s"] - got["busy_s"])


def test_recorded_served_trace():
    """A tiny served run on a TPU v5e (``record_served_trace.py``): the
    worker's program spans are found, each on its micro-batch, and the
    device's idle time is put down to them."""
    profile = _profile(SERVED)
    dev, host = trace.events(profile)
    prog = spans.program_spans(profile)
    assert list(dev) == ["/device:TPU:0"]
    assert len({th for *_, th in prog}) == 1, "program spans off the worker"
    got = spans.reduce(profile)
    assert set(got["spans"]) >= BATCH_SPANS
    lo, hi = trace.window(host)
    roots = [(s, e) for s, e, n, _ in prog if n == spans.ROOT]
    assert sum(got["spans"].values()) == pytest.approx(sum(
        min(e, hi) - max(s, lo) for s, e in roots if e > lo and s < hi)
        * 1e-9)
    assert 0 < got["busy_s"] < got["window_s"]
    assert sum(got["idle_by_span"].values()) == pytest.approx(
        got["window_s"] - got["busy_s"])
    assert set(got["idle_by_span"]) & BATCH_SPANS
    assert {n for n, _ in got["idle_gaps"]} <= BATCH_SPANS | {
        "ripple.mirror.rebuild", "ripple.engine.retry", trace.WORKER_IDLE}


def test_tiny_traced_run_reports_every_span_and_counter(tmp_path):
    """``spans.measure`` on a tiny cell on the CPU: every span a
    micro-batch opens has self time, the spans fill the worker's cycle,
    and the counters are window deltas; the run's own result line is the
    harness's. The cell warms up as the benchmark's cells do, at least
    100 micro-batches with no compile: a shorter warm-up can open the
    window while the worker compiles a shape the tiny graph first needs
    some 50 micro-batches in, which on a loaded CPU with a cold compile
    cache outlasts a short window."""
    code = f"""
import json, sys, time
T = time.perf_counter()
sys.path[:0] = [{BENCH!r}, {SRC!r}]
import spans as cmd
from rbench import harness
with open({os.path.join(BENCH, "configs", "arxiv-gcs.json")!r}) as f:
    cfg = json.load(f)
cfg.update(n=300, m=3000, d_in=16, d_hidden=16, n_classes=8,
           holdout_frac=0.5)
with open({os.path.join(BENCH, "traffic", "sat.json")!r}) as f:
    traffic = json.load(f)
traffic.update(chunk=5, max_batch=40, readd_after=40, capacity=160,
               query_rate_per_s=40.0, prefill_updates=500)
cell = harness.Cell("tiny-gcs.sat", cfg, traffic, 1, [], [])
result, breakdown = cmd.measure(cell, seed=2**31 + 7, seconds=3.0,
                                t_start=T)
print(json.dumps(result))
print(json.dumps(breakdown))
"""
    p = subprocess.run([sys.executable, "-c", code], cwd=tmp_path,
                       env=dict(os.environ, JAX_PLATFORMS="cpu"),
                       capture_output=True, text=True, timeout=300)
    assert p.returncode == 0, p.stderr[-3000:]
    result, got = (json.loads(x) for x in p.stdout.strip().splitlines()[-2:])
    assert result["correct"] is True and list(result)[-1] == "checks"
    assert got["batches"] > 0 and got["published"] > 0, p.stderr[-3000:]
    assert set(got["span_ms"]) >= BATCH_SPANS
    # the worker is busy all through a closed loop: its spans fill the
    # cycle but for the loop's own queue check between micro-batches
    assert 0.9 * got["cycle_ms"] <= sum(got["span_ms"].values()) \
        <= 1.01 * got["cycle_ms"]
    c = got["counters"]
    assert set(c) == {"retries", "rebuilds", "shape_misses"}
    assert set(c["shape_misses"]) == {"propagate", "mirror_scatter",
                                      "commit_gather"}
    assert min(c["retries"], c["rebuilds"],
               *c["shape_misses"].values()) >= 0
    assert got["idle_by_span"] == {}          # no device plane on the CPU
