"""Streaming-inference driver: the production serving loop for RIPPLE.

A thin CLI over ``repro.api.InferenceSession``: graph snapshot -> bootstrap
-> journaled update batches -> incremental engine -> latency report; with
checkpoint/restart and deadline-driven micro-batching (straggler
mitigation).  Engine selection goes through the registry — any registered
backend name works, no per-engine wiring here.

    PYTHONPATH=src python -m repro.launch.stream --workload gc-s --n 2000 \
        --updates 3000 --batch-size 100 --engine ripple
"""
from __future__ import annotations

import argparse

from repro.api import InferenceSession, SessionConfig, engine_names
from repro.utils import use_compile_cache


def build(args) -> InferenceSession:
    return InferenceSession.build(SessionConfig(
        workload=args.workload, engine=args.engine, graph=args.graph,
        n=args.n, m=args.m, n_layers=args.layers, d_in=args.d_in,
        d_hidden=args.d_hidden, n_classes=args.classes,
        deadline_ms=args.deadline_ms, ckpt_dir=args.ckpt_dir,
        ckpt_every=args.ckpt_every))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", default="gc-s")
    ap.add_argument("--engine", choices=engine_names(), default="ripple")
    ap.add_argument("--graph", choices=["er", "powerlaw"], default="powerlaw")
    ap.add_argument("--n", type=int, default=2000)
    ap.add_argument("--m", type=int, default=8000)
    ap.add_argument("--layers", type=int, default=2)
    ap.add_argument("--d-in", type=int, default=32)
    ap.add_argument("--d-hidden", type=int, default=32)
    ap.add_argument("--classes", type=int, default=8)
    ap.add_argument("--updates", type=int, default=3000)
    ap.add_argument("--batch-size", type=int, default=100)
    ap.add_argument("--deadline-ms", type=float, default=0.0,
                    help="straggler mitigation: split batches that exceed "
                         "this latency budget (0 = off)")
    ap.add_argument("--ckpt-dir", default="")
    ap.add_argument("--ckpt-every", type=int, default=10)
    args = ap.parse_args()

    use_compile_cache()
    session = build(args)
    stream = session.make_stream(args.updates, seed=1)
    report = session.ingest(stream, batch_size=args.batch_size,
                            keep_results=False)
    print(f"engine={session.engine_name} workload={args.workload} "
          f"updates={report.n_updates} throughput={report.throughput:.1f} up/s "
          f"median_latency={report.median_latency_ms:.2f}ms "
          f"p99={report.p99_latency_ms:.2f}ms "
          f"final_batch_size={report.final_batch_size}")


if __name__ == "__main__":
    main()
