"""Reduction of a JAX profiler trace to the benchmark's device numbers.

- busy: the union of the intervals in which an operation ran on a device
  (events of the ``XLA Ops`` line of each ``/device:`` plane), clipped to
  the traced window and averaged over the devices;
- idle share: 1 - busy / window;
- top device operations by summed device time;
- the longest idle gaps, each named by what the serving worker thread did
  in most of it: the innermost benchmark span (``bench.*``
  ``TraceAnnotation``) it was in.

The window is the host span ``bench.window`` that the harness opens and
closes around the measured seconds.
"""
from __future__ import annotations

import bisect
import glob
import os
from collections import defaultdict

import numpy as np

WINDOW_SPAN = "bench.window"
WORKER_SPAN = "bench.apply_one"
SPAN_PREFIX = "bench."
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
WORKER_IDLE = "worker outside bench spans"


def load(trace_dir: str):
    """The newest ``.xplane.pb`` under ``trace_dir`` as ProfileData."""
    from jax.profiler import ProfileData

    files = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return ProfileData.from_file(files[-1])


def events(profile):
    """(device_ops, host_spans): device op events per device plane as
    ``{plane: [(start_ns, end_ns, name)]}``, each op named
    ``<module>/<instruction>`` after the XLA module it ran in, and the
    benchmark's host spans as ``[(start_ns, end_ns, name, thread)]``."""
    dev: dict[str, list] = {}
    host: list = []
    for plane in profile.planes:
        if plane.name.startswith("/device:"):
            lines = {line.name: line for line in plane.lines}
            if OPS_LINE not in lines:
                continue
            mods = sorted((e.start_ns, e.start_ns + e.duration_ns,
                           e.name.split("(")[0])
                          for e in lines[MODULES_LINE].events) \
                if MODULES_LINE in lines else []
            starts = [m[0] for m in mods]
            ops = []
            for e in lines[OPS_LINE].events:
                i = bisect.bisect_right(starts, e.start_ns) - 1
                mod = mods[i][2] if i >= 0 and e.start_ns < mods[i][1] \
                    else "?"
                inst = e.name.split(" = ")[0].lstrip("%")
                ops.append((e.start_ns, e.start_ns + e.duration_ns,
                            f"{mod}/{inst}"))
            if ops:
                dev[plane.name] = ops
        elif plane.name.startswith("/host:"):
            for k, line in enumerate(plane.lines):
                host += [(e.start_ns, e.start_ns + e.duration_ns, e.name,
                          (plane.name, k)) for e in line.events
                         if e.name.startswith(SPAN_PREFIX)]
    return dev, host


def window(host_spans) -> tuple[float, float]:
    spans = [(s, e) for s, e, n, _ in host_spans if n == WINDOW_SPAN]
    if len(spans) != 1:
        raise ValueError(f"trace holds {len(spans)} {WINDOW_SPAN} spans")
    return spans[0]


def union(intervals, lo: float, hi: float) -> list[tuple[float, float]]:
    """Merged [start, end) intervals clipped to [lo, hi]."""
    out: list[list[float]] = []
    for s, e in sorted((max(s, lo), min(e, hi)) for s, e, *_ in intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def busy_seconds(dev_ops: dict, lo: float, hi: float) -> float:
    """Busy seconds in [lo, hi], averaged over the device planes."""
    if not dev_ops:
        return 0.0
    per = [sum(e - s for s, e in union(ops, lo, hi)) for ops in
           dev_ops.values()]
    return float(np.mean(per)) * 1e-9


def top_ops(dev_ops: dict, lo: float, hi: float, k: int = 10) -> list:
    """[name, seconds] of the ``k`` device operations with the most
    device time in the window (summed over devices, averaged per device)."""
    tot: dict[str, float] = defaultdict(float)
    for ops in dev_ops.values():
        for s, e, name in ops:
            d = min(e, hi) - max(s, lo)
            if d > 0:
                tot[name] += d
    n_dev = max(len(dev_ops), 1)
    best = sorted(tot.items(), key=lambda kv: -kv[1])[:k]
    return [[name, ns * 1e-9 / n_dev] for name, ns in best]


def idle_gaps(dev_ops: dict, host_spans, lo: float, hi: float,
              k: int = 10) -> list:
    """[name, seconds] of the ``k`` longest idle gaps of the first device
    in [lo, hi], each named by what the serving worker (the thread that
    runs ``bench.apply_one``) did in most of it: the innermost benchmark
    span it was in, or ``WORKER_IDLE`` outside them."""
    if not dev_ops:
        return []
    busy = union(next(iter(dev_ops.values())), lo, hi)
    gaps, t = [], lo
    for s, e in busy:
        if s > t:
            gaps.append((t, s))
        t = max(t, e)
    if hi > t:
        gaps.append((t, hi))
    gaps.sort(key=lambda g: g[0] - g[1])
    threads = [th for _, _, n, th in host_spans if n == WORKER_SPAN]
    worker = max(set(threads), key=threads.count) if threads else None
    spans = [(s, e, n) for s, e, n, th in host_spans
             if th == worker and n != WINDOW_SPAN]
    return [[_name(g, spans), (g[1] - g[0]) * 1e-9] for g in gaps[:k]]


def _name(gap, spans) -> str:
    """The span in which most of the gap passed, counting each instant for
    the innermost (shortest) span that covers it."""
    lo, hi = gap
    inside = [(max(s, lo), min(e, hi), e - s, n) for s, e, n in spans
              if s < hi and e > lo]
    cuts = sorted({lo, hi} | {t for s, e, _, _ in inside for t in (s, e)})
    time: dict[str, float] = defaultdict(float)
    for a, b in zip(cuts, cuts[1:]):
        cover = [(length, n) for s, e, length, n in inside
                 if s <= a and b <= e]
        time[min(cover)[1] if cover else WORKER_IDLE] += b - a
    return max(time, key=time.get)


def reduce(profile) -> dict:
    """busy_s, window_s and the breakdown of one traced window."""
    dev, host = events(profile)
    lo, hi = window(host)
    return {"busy_s": busy_seconds(dev, lo, hi), "window_s": (hi - lo) * 1e-9,
            "devices": len(dev),
            "device_ops": top_ops(dev, lo, hi),
            "idle_gaps": idle_gaps(dev, host, lo, hi)}
