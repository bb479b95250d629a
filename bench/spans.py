#!/usr/bin/env python3
"""Where the served path's time goes, by the program's own spans and
counters, in one traced run of a cell.

    python3 bench/spans.py --workload <cell> --seed <n> --seconds <s>

Runs the cell as ``run.py --trace 1`` does (``harness.run``), and reads
beside it the profile the harness records (``rbench/spans.py``) and the
engine's counters as the profiler starts and stops. Prints the run's own
result line, then one JSON line:

- ``batches``, ``published``, ``window_s``, ``cycle_ms``: the window's
  micro-batches and published updates, its length on the trace's clock,
  and window milliseconds per micro-batch;
- ``span_ms``: each program span's self milliseconds per micro-batch;
- ``idle_by_span``: the device's idle seconds by the innermost worker span;
- ``idle_gaps``: the longest idle gaps, named by the innermost span;
- ``counters``: the window's ladder retries, mirror re-layouts over both
  halves and first sightings of a static key by jit call site
  (``DeviceEngine.retries``, ``DeviceCSRMirror.rebuilds``,
  ``DeviceEngine.shape_misses``), where the program keeps them.

It needs a TPU, as ``run.py`` does. Neither the run nor its result line
changes: this command only observes.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from unittest import mock  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)
sys.path.insert(0, os.path.join(os.path.dirname(HERE), "src"))


def counters(session, server) -> dict:
    """The served path's counters, as far as the program keeps them."""
    engine = session.engine.impl
    mirrors = [m for m in (engine.out_mirror,
                           getattr(engine, "in_mirror", None))
               if m is not None]
    out = {"batches": len(server.batch_sizes),
           "published": server.published_updates,
           "retries": engine.retries,
           "rebuilds": sum(m.rebuilds for m in mirrors)}
    misses = getattr(engine, "shape_misses", None)
    if misses is not None:
        out["shape_misses"] = dict(misses)
    return out


def _delta(a: dict, b: dict) -> dict:
    return {k: _delta(a[k], v) if isinstance(v, dict) else v - a[k]
            for k, v in b.items()}


def measure(cell, *, seed: int, seconds: float,
            t_start: float) -> tuple[dict, dict]:
    """(the run's result line, the span breakdown) of one traced run."""
    import jax

    from rbench import harness, spans, trace

    served, profiles, marks = [], [], []
    start, stop, load = (jax.profiler.start_trace, jax.profiler.stop_trace,
                         trace.load)

    def start_trace(*a, **kw):
        start(*a, **kw)
        marks.append(counters(*served[0]))

    def stop_trace():
        marks.append(counters(*served.pop()))   # the run frees the engine
        stop()

    def keep(trace_dir):
        profiles.append(load(trace_dir))
        return profiles[-1]

    with mock.patch.object(jax.profiler, "start_trace", start_trace), \
            mock.patch.object(jax.profiler, "stop_trace", stop_trace), \
            mock.patch.object(trace, "load", keep):
        result = harness.run(
            cell, seed=seed, seconds=seconds, trace=True, t_start=t_start,
            hooks=lambda session, server: served.append((session, server)))
    red = spans.reduce(profiles[0])
    win = _delta(*marks)
    n = max(win["batches"], 1)
    return result, {
        "batches": win.pop("batches"), "published": win.pop("published"),
        "window_s": red["window_s"],
        "cycle_ms": 1e3 * red["window_s"] / n,
        "span_ms": {k: 1e3 * v / n for k, v in red["spans"].items()},
        "idle_by_span": red["idle_by_span"],
        "idle_gaps": red["idle_gaps"],
        "counters": win}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)

    import jax

    from rbench import harness

    cell = harness.load_cell(args.workload)
    if jax.devices()[0].platform != "tpu":
        print(f"spans: needs a TPU, JAX found {jax.devices()[0].platform}",
              file=sys.stderr)
        return 1
    result, breakdown = measure(cell, seed=args.seed, seconds=args.seconds,
                                t_start=T_START)
    print(json.dumps(result), flush=True)
    print(json.dumps(breakdown), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
