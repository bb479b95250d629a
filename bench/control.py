#!/usr/bin/env python3
"""The correctness check's control, and a second witness for its reference.

    python3 bench/control.py --workload <cell> --seeds 1,2,3 --seconds 10 \\
        [--keep DIR]
    JAX_PLATFORMS=cpu python3 bench/control.py --workload <cell> \\
        --witness DIR/<seed>.npz

The first form makes, for each seed, one whole run of the cell at its own
size and load (``harness.run``), and then checks the reference computed
with its products in three bfloat16 passes (``reference.py``, ``bf16x3``)
in the program's place: the line's ``correct`` is the control's, and has to
come out false. The program's own readings of the same run are under
``program``. ``--keep`` writes each run's graph, inputs, program output and
reference to ``DIR/<seed>.npz``.

The second form recomputes the float32 reference of a kept run on this
process's backend (the CPU, under ``JAX_PLATFORMS=cpu``) and compares it
with the reference and the program output of the kept run.

One process reads every seed. The benchmark's own runs run neither.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)
sys.path.insert(0, os.path.join(os.path.dirname(HERE), "src"))

CONTROL = "bf16x3"


def control_run(cell, seed: int, seconds: float, keep: str | None) -> dict:
    from rbench import harness

    path = os.path.join(keep, f"{seed}.npz") if keep else None
    out = harness.run(cell, seed=seed, seconds=seconds, trace=False,
                      t_start=time.perf_counter(), control=CONTROL, keep=path)
    return {"cell": cell.name, "seed": seed, "correct": out["correct"],
            "program": out["program"],
            "control": {k: c["value"] for k, c in out["checks"].items()},
            "updates_per_s": out["metrics"]["updates_per_s"]["value"]}


def witness(cell, path: str) -> dict:
    import jax
    import numpy as np

    from rbench import datagen, harness, reference

    cfg = cell.config
    z = np.load(path)
    params = [{k.split(".", 1)[1]: z[k] for k in z.files
               if k.startswith(f"p{l}.")} for l in range(cfg["n_layers"])]
    H, S = reference.forward(datagen.family(cfg), datagen.aggregator(cfg),
                             params, z["x"], z["src"], z["dst"])
    layers = range(1, cfg["n_layers"] + 1)
    pad = [z["x"]]
    kept = {w: pad + [z[f"{w}{l}"] for l in layers]
            for w in ("ref_H", "ref_S", "got_H", "got_S")}
    chip_ref = harness.compare(cfg, kept["ref_H"], kept["ref_S"],
                               kept["ref_H"][-1], H, S)
    program = harness.compare(cfg, kept["got_H"], kept["got_S"], z["snap"],
                              H, S)
    return {"cell": cell.name, "file": os.path.basename(path),
            "backend": jax.default_backend(),
            "kept_reference": {k: c["value"] for k, c in chip_ref.items()},
            "program": {k: c["value"] for k, c in program.items()}}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds")
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--keep")
    ap.add_argument("--witness")
    args = ap.parse_args(argv)

    from rbench import harness

    cell = harness.load_cell(args.workload)
    if args.witness:
        print(json.dumps(witness(cell, args.witness)), flush=True)
        return 0
    if args.keep:
        os.makedirs(args.keep, exist_ok=True)
    for seed in (int(s) for s in args.seeds.split(",")):
        print(json.dumps(control_run(cell, seed, args.seconds, args.keep)),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
