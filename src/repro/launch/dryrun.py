"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

MUST be the very first lines — before ANY other import — because jax locks
the device count at first init:
"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
os.environ.setdefault("JAX_PLATFORMS", "cpu")

import argparse        # noqa: E402
import json            # noqa: E402
import re              # noqa: E402
import sys             # noqa: E402
import time            # noqa: E402
import traceback       # noqa: E402

import jax             # noqa: E402

jax.config.update("jax_persistent_cache_min_compile_time_secs", 2.0)

from repro.configs.registry import ARCHS, get_arch   # noqa: E402
from repro.launch.mesh import (HBM_BW, ICI_BW, PEAK_FLOPS,  # noqa: E402
                               make_production_mesh)
from repro.utils import (human_bytes, human_count,   # noqa: E402
                         use_compile_cache)

_DTYPE_BYTES = {"f64": 8, "f32": 4, "bf16": 2, "f16": 2, "s64": 8, "u64": 8,
                "s32": 4, "u32": 4, "s16": 2, "u16": 2, "s8": 1, "u8": 1,
                "pred": 1, "f8e4m3fn": 1, "f8e5m2": 1}

_COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
                "collective-permute")

_SHAPE_RE = re.compile(r"(f64|f32|bf16|f16|s64|u64|s32|u32|s16|u16|s8|u8|pred|"
                       r"f8e4m3fn|f8e5m2)\[([0-9,]*)\]")


def _shape_bytes(text: str) -> float:
    """Sum byte sizes of every typed shape literal in `text`."""
    total = 0.0
    for dt, dims in _SHAPE_RE.findall(text):
        n = 1
        if dims:
            for d in dims.split(","):
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def collective_bytes(hlo: str) -> dict:
    """Per-collective byte totals parsed from (post-SPMD) HLO text.

    Counts the OUTPUT shape of each collective op — for all-reduce /
    all-to-all output==input; for all-gather it is the gathered size, for
    reduce-scatter the scattered size (both the wire-dominant side).
    """
    out: dict[str, float] = {c: 0.0 for c in _COLLECTIVES}
    for line in hlo.splitlines():
        s = line.strip()
        if "=" not in s:
            continue
        for c in _COLLECTIVES:
            # match the op at its call site ("all-gather(", "...-start(",
            # "...-done(" excluded: -done re-lists the payload shapes)
            m = re.search(rf" {c}(?:-start)?\(", s)
            if m and f"{c}-done" not in s[:m.end()]:
                # sum every shape literal in the RESULT type, which for
                # variadic (tuple) collectives lists all payload shapes
                lhs = s[: m.start()]
                out[c] += _shape_bytes(lhs.split("=", 1)[1]
                                       if "=" in lhs else lhs)
                break
    out["total"] = sum(out[c] for c in _COLLECTIVES)
    return out


def _compile_costs(built) -> tuple[float, float, float, object]:
    """(flops, bytes_accessed, collective_bytes, memory_analysis)."""
    jfn = jax.jit(built.fn, in_shardings=built.in_shardings)
    compiled = jfn.lower(*built.args).compile()
    cost = compiled.cost_analysis() or {}
    coll = collective_bytes(compiled.as_text())
    flops = float(cost.get("flops", 0.0))
    bytes_acc = float(sum(v for k, v in cost.items()
                          if k.startswith("bytes accessed")) or
                      cost.get("bytes accessed", 0.0))
    return flops, bytes_acc, coll["total"], (compiled.memory_analysis(), coll)


def run_cell(cell, mesh, mesh_label: str, chips: int) -> dict:
    import numpy as np
    t0 = time.time()
    built = cell.build(mesh)
    flops, bytes_acc, coll_total, (mem, coll) = _compile_costs(built)

    if built.probes:
        # layer-scanned program: solve cost = row . c over unrolled probes
        rows, y_f, y_b, y_c = [], [], [], []
        for row, probe_builder in built.probes:
            pb = probe_builder(mesh)
            f, b, c, _ = _compile_costs(pb)
            rows.append(row)
            y_f.append(f)
            y_b.append(b)
            y_c.append(c)
        A = np.array(rows)
        full = np.array(built.design_full)
        # drop all-zero design columns (dense-only archs have no moe column)
        keep = ~np.all(A == 0.0, axis=0)
        A = A[:, keep]
        full = full[keep]
        sol = lambda y: float(full @ np.linalg.lstsq(A, np.array(y),
                                                     rcond=None)[0])
        flops, bytes_acc, coll_total = sol(y_f), sol(y_b), sol(y_c)
        coll = dict(coll, total=coll_total, extrapolated=True)
    # terms are per-chip seconds (cost analysis is of the per-device program)
    t_compute = flops / PEAK_FLOPS
    t_memory = bytes_acc / HBM_BW
    t_coll = coll_total / ICI_BW
    dominant = max(("compute", t_compute), ("memory", t_memory),
                   ("collective", t_coll), key=lambda kv: kv[1])[0]
    model_flops_per_chip = built.model_flops / chips
    rec = {
        "cell": cell.name, "kind": cell.kind, "mesh": mesh_label,
        "chips": chips,
        "compile_s": round(time.time() - t0, 1),
        "flops_per_chip": flops,
        "bytes_per_chip": bytes_acc,
        "collective_bytes_per_chip": coll["total"],
        "collectives": {k: v for k, v in coll.items() if k != "total"},
        "t_compute_s": t_compute, "t_memory_s": t_memory,
        "t_collective_s": t_coll, "dominant": dominant,
        "model_flops": built.model_flops,
        "useful_compute_frac": (model_flops_per_chip / flops) if flops else 0.0,
        "mem_per_device": {
            "argument_bytes": mem.argument_size_in_bytes,
            "output_bytes": mem.output_size_in_bytes,
            "temp_bytes": mem.temp_size_in_bytes,
            "peak_bytes": (mem.argument_size_in_bytes + mem.temp_size_in_bytes
                           + mem.output_size_in_bytes
                           - mem.alias_size_in_bytes),
        },
        "notes": built.notes,
    }
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="all",
                    help="arch id, 'all' (assigned 40) or 'extra' (ripple)")
    ap.add_argument("--shape", default=None)
    ap.add_argument("--mesh", choices=["single", "multi", "both"],
                    default="single")
    ap.add_argument("--out", default=None, help="append JSONL here")
    args = ap.parse_args()
    use_compile_cache()

    if args.arch == "all":
        names = [a for a in ARCHS if a != "ripple-papers"]
    elif args.arch == "extra":
        names = ["ripple-papers"]
    else:
        names = [args.arch]

    meshes = []
    if args.mesh in ("single", "both"):
        meshes.append(("pod16x16", make_production_mesh(multi_pod=False), 256))
    if args.mesh in ("multi", "both"):
        meshes.append(("2pod 2x16x16", make_production_mesh(multi_pod=True), 512))

    failures = 0
    for name in names:
        mod = get_arch(name)
        for cell in mod.CELLS:
            if args.shape and cell.shape != args.shape:
                continue
            for label, mesh, chips in meshes:
                try:
                    rec = run_cell(cell, mesh, label, chips)
                    print(f"[OK] {cell.name:40s} {label:12s} "
                          f"flops/chip={human_count(rec['flops_per_chip'])} "
                          f"bytes/chip={human_bytes(rec['bytes_per_chip'])} "
                          f"coll/chip={human_bytes(rec['collective_bytes_per_chip'])} "
                          f"peakmem={human_bytes(rec['mem_per_device']['peak_bytes'])} "
                          f"dom={rec['dominant']} "
                          f"compile={rec['compile_s']}s", flush=True)
                    if args.out:
                        with open(args.out, "a") as f:
                            f.write(json.dumps(rec) + "\n")
                except Exception as e:
                    failures += 1
                    print(f"[FAIL] {cell.name} {label}: {e}", flush=True)
                    traceback.print_exc()
    sys.exit(1 if failures else 0)


if __name__ == "__main__":
    main()
