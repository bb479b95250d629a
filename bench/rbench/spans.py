"""The program's own spans in a traced window.

``trace.py`` reduces a profile to the device's busy time and names idle
gaps after the benchmark's spans (``bench.*``). The program records spans
of its own (``ripple.*``, ``repro.utils.trace``) on the same clock, on the
serving worker's thread, one ``ripple.serve.micro_batch`` root per
micro-batch. This module reads them:

- ``spans``: each program span's self seconds on the worker thread in the
  window: the instants at which it was the innermost ``ripple.*`` span,
  which is its duration less what its program child spans cover;
- ``idle_by_span``: the first device's idle seconds in the window, each
  instant put down to the innermost worker span of either prefix, or to
  ``trace.WORKER_IDLE`` outside them;
- ``idle_gaps``: the longest idle gaps, each named as ``trace.idle_gaps``
  names it, with the program's spans among the candidates.

``reduce`` returns ``trace.reduce``'s numbers unchanged, with these three
in place of or beside them.
"""
from __future__ import annotations

import bisect
import heapq
from collections import defaultdict

from . import trace

PREFIX = "ripple."
ROOT = "ripple.serve.micro_batch"


def program_spans(profile) -> list:
    """The program's host spans as ``[(start_ns, end_ns, name, thread)]``,
    threads named as ``trace.events`` names them."""
    return [(e.start_ns, e.start_ns + e.duration_ns, e.name,
             (plane.name, k))
            for plane in profile.planes if plane.name.startswith("/host:")
            for k, line in enumerate(plane.lines)
            for e in line.events if e.name.startswith(PREFIX)]


def worker_spans(host_spans) -> list:
    """``[(start, end, name)]`` of every span but the window on the serving
    worker's thread: the one that ran the most ``bench.apply_one`` or
    ``ripple.serve.micro_batch`` spans."""
    threads = [th for _, _, n, th in host_spans
               if n in (trace.WORKER_SPAN, ROOT)]
    worker = max(set(threads), key=threads.count) if threads else None
    return [(s, e, n) for s, e, n, th in host_spans
            if th == worker and n != trace.WINDOW_SPAN]


def innermost(spans, lo: float, hi: float) -> list:
    """``[(start, end, name)]``: the pieces of [lo, hi] that ``spans``
    cover, each named by the innermost (shortest) span covering it, as
    ``trace._name`` counts them."""
    cuts = sorted({lo, hi} | {min(max(t, lo), hi) for s, e, _ in spans
                               for t in (s, e)})
    starts = sorted((s, e - s, e, n) for s, e, n in spans)
    live: list = []                  # (length, name, end), shortest first
    out: list = []
    i = 0
    for a, b in zip(cuts, cuts[1:]):
        while i < len(starts) and starts[i][0] <= a:
            _, length, e, n = starts[i]
            heapq.heappush(live, (length, n, e))
            i += 1
        while live and live[0][2] <= a:
            heapq.heappop(live)
        if live:
            out.append((a, b, live[0][1]))
    return out


def self_seconds(spans, lo: float, hi: float) -> dict:
    """Self seconds in [lo, hi] of each program span name."""
    tot: dict[str, float] = defaultdict(float)
    program = [sp for sp in spans if sp[2].startswith(PREFIX)]
    for a, b, n in innermost(program, lo, hi):
        tot[n] += b - a
    return {n: ns * 1e-9 for n, ns in sorted(tot.items())}


def _gaps(dev_ops: dict, lo: float, hi: float) -> list:
    """The first device's idle intervals in [lo, hi], in time order."""
    if not dev_ops:
        return []
    gaps, t = [], lo
    for s, e in trace.union(next(iter(dev_ops.values())), lo, hi):
        if s > t:
            gaps.append((t, s))
        t = max(t, e)
    if hi > t:
        gaps.append((t, hi))
    return gaps


def idle_by_span(dev_ops: dict, spans, lo: float, hi: float) -> dict:
    """The first device's idle seconds in [lo, hi] by the innermost worker
    span each instant fell in, largest first."""
    gaps = _gaps(dev_ops, lo, hi)
    if not gaps:
        return {}
    tot: dict[str, float] = defaultdict(float)
    tot[trace.WORKER_IDLE] = sum(e - s for s, e in gaps)
    ends = [e for _, e in gaps]
    for a, b, n in innermost(spans, lo, hi):
        j = bisect.bisect_right(ends, a)
        while j < len(gaps) and gaps[j][0] < b:
            d = min(b, gaps[j][1]) - max(a, gaps[j][0])
            tot[n] += d
            tot[trace.WORKER_IDLE] -= d
            j += 1
    return {n: ns * 1e-9 for n, ns in
            sorted(tot.items(), key=lambda kv: -kv[1]) if ns > 0}


def idle_gaps(dev_ops: dict, spans, lo: float, hi: float,
              k: int = 10) -> list:
    """[name, seconds] of the ``k`` longest idle gaps of the first device,
    each named by the worker span in which most of it passed."""
    gaps = sorted(_gaps(dev_ops, lo, hi), key=lambda g: g[0] - g[1])
    return [[trace._name(g, spans), (g[1] - g[0]) * 1e-9] for g in gaps[:k]]


def reduce(profile) -> dict:
    """``trace.reduce(profile)`` with ``idle_gaps`` named by the program's
    spans too, and ``spans`` and ``idle_by_span`` beside them."""
    out = trace.reduce(profile)
    dev, host = trace.events(profile)
    lo, hi = trace.window(host)
    worker = worker_spans(host + program_spans(profile))
    out.update(idle_gaps=idle_gaps(dev, worker, lo, hi),
               spans=self_seconds(worker, lo, hi),
               idle_by_span=idle_by_span(dev, worker, lo, hi))
    return out
