#!/usr/bin/env python3
"""Run one benchmark cell once and print its result line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell, its configuration, traffic mix and per-layer metric readers are
found by name from ``BENCHMARK.json`` (see ``rbench/harness.py``). The run
builds the graph, features and weights from ``--seed``, bootstraps an
``InferenceSession`` on the ``device`` engine behind a ``GraphServer``,
warms up on the cell's own traffic until no compile or cap-ladder retry
happens for a stretch, measures for ``--seconds``, drains, and compares
the published snapshot and the engine's state with a plain float32
reference. The last stdout line is one JSON object; the compared numbers
and their limits are the last lines of stderr. It needs a TPU: with none,
or fewer chips than the cell asks for, it exits 1 and prints no result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)
sys.path.insert(0, os.path.join(os.path.dirname(HERE), "src"))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    import jax

    from rbench import harness

    cell = harness.load_cell(args.workload)
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"bench: needs a TPU, JAX found {devices[0].platform}",
              file=sys.stderr)
        return 1
    if len(devices) < cell.chips:
        print(f"bench: {cell.name} needs {cell.chips} chips, JAX found "
              f"{len(devices)}", file=sys.stderr)
        return 1
    result = harness.run(cell, seed=args.seed, seconds=args.seconds,
                         trace=bool(args.trace), t_start=T_START)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
