"""Distributed RP-vs-RC benchmark (paper Figs 12/13) over the local devices.

Measures warm-path steady-state throughput separately from the
compile-inclusive cold path: every configuration ingests a few warmup
batches (warm-sentinel compile + cap-ladder settling), snapshots the
engine's shard_map compile counter, then streams the remainder through
ONE ``session.ingest`` call so the async host/device pipeline never
drains mid-run.  Alongside the wall numbers it records the warm-path
accounting — compile events, cap-ladder rung transitions, overflow
retries, partitioned-CSR uploads — plus the exchanged message slots for
RIPPLE vs pull-based RC across partition counts (the paper's throughput
and comm-cost scaling study).

Partition counts and meshes come from ``jax.device_count()``: every
power-of-two count from 2 up to the device count, each on a
``(parts, devices // parts)`` ("data", "model") mesh.  Only when the run
is pinned to the CPU (``JAX_PLATFORMS=cpu``) does the script ask XLA for
eight virtual host devices, so the sweep has devices to span.

Writes ``BENCH_dist.json`` at the repo root: per (partition count, mode)
steady ``updates_per_sec`` vs ``cold_updates_per_sec``, compile/ladder
counters, comm slots, and CSR maintenance stats, with the platform and
device kind they were measured on.
"""
import json
import os
import sys
import time

if os.environ.get("JAX_PLATFORMS") == "cpu" and \
        "device_count" not in os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") + " "
                               "--xla_force_host_platform_device_count=8"
                               ).strip()
sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import jax  # noqa: E402
import numpy as np  # noqa: E402

from repro.api import InferenceSession, SessionConfig  # noqa: E402
from repro.launch.mesh import make_local_mesh  # noqa: E402
from repro.utils import next_bucket, use_compile_cache  # noqa: E402

D = 64
WARMUP_BATCHES = 4
OUT_PATH = os.path.join(os.path.dirname(__file__), "..", "BENCH_dist.json")


def partition_counts(devices: int) -> tuple[int, ...]:
    """Powers of two from 2 up to ``devices`` (just 1 on one device)."""
    return tuple(p for p in (2, 4, 8, 16, 32) if p <= devices) or (1,)


def run(parts: int, mode: str, n=1500, m=30000, batch=100, n_updates=1200,
        workload="gc-s", mix=(1.0, 1.0, 1.0)):
    mesh = make_local_mesh(parts, jax.device_count() // parts)
    engine = "dist" if mode == "ripple" else "dist-rc"
    session = InferenceSession.build(SessionConfig(
        workload=workload, engine=engine,
        engine_options={"mesh": mesh, "async_dispatch": True,
                        "min_bucket": next_bucket(batch)},
        graph="er", n=n, m=m, n_layers=3, d_in=D, d_hidden=D, n_classes=16,
        seed=0))
    updates = list(session.make_stream(n_updates, seed=1, mix=mix))
    eng = session.engine.impl
    warm_n = WARMUP_BATCHES * batch

    t0 = time.perf_counter()
    session.ingest(updates[:warm_n], batch_size=batch)
    warm_wall = time.perf_counter() - t0
    warm_compiles = eng.compiles

    # steady state: ONE ingest call so the async pipeline stays full
    # (ingest flushes on return; per-batch calls would drain the overlap)
    rep = session.ingest(updates[warm_n:], batch_size=batch)
    steady_wall = rep.wall_seconds
    steady_compiles = eng.compiles - warm_compiles

    monotonic = session.workload.spec.monotonic
    lat = rep.latencies
    comm, pull_req, pull_resp = [], [], []
    shrinks, reaggs, dims, recovers = [], [], [], []
    for r in rep.results:
        slots = r.messages_per_hop
        if not slots:        # async: comm lags one batch behind dispatch
            continue
        comm.append(sum(slots))
        # monotonic comm interleaves [halo, pull_req, pull_resp] per hop;
        # the pull split carries the SHRINK-only dim-masked vs
        # pull-everything row-sized contrast (resp units are scalars:
        # 1 per request for per-dim RIPPLE, d_loc per request for RC)
        pull_req.append(sum(slots[1::3]) if monotonic else 0)
        pull_resp.append(sum(slots[2::3]) if monotonic else 0)
        shrinks.append(r.shrink_events)
        reaggs.append(r.rows_reaggregated)
        dims.append(r.dims_reaggregated)
        recovers.append(r.recover_hits)
    # the headline steady number is median-latency based (one straggler or
    # late cap-ladder recompile shouldn't define "steady state"); the
    # wall-clock variant including every straggler rides along
    thr_wall = (n_updates - warm_n) / max(steady_wall, 1e-9)
    thr = batch / max(float(np.median(lat)), 1e-9)
    cold = n_updates / max(warm_wall + steady_wall, 1e-9)
    csr = session.engine.impl.out_csr
    print(f"fig12/{workload}/{mode}/p{parts},{np.median(lat) * 1e6:.1f},"
          f"steady={thr:.0f}ups (wall {thr_wall:.0f}) cold={cold:.0f}ups "
          f"compiles={eng.compiles} steady_compiles={steady_compiles} "
          f"rungs={eng.ladder_rungs} retries={eng.retries} "
          f"comm_slots={np.mean(comm):.0f} "
          f"host_us={eng.last_host_seconds * 1e6:.0f} "
          f"csr={csr.rebuilds}r/{csr.uploads}u", flush=True)
    return {"parts": parts, "mode": mode, "workload": workload,
            "median_latency_s": float(np.median(lat)),
            "updates_per_sec": float(thr),
            "updates_per_sec_wall": float(thr_wall),
            "cold_updates_per_sec": float(cold),
            "steady_wall_seconds": float(steady_wall),
            "warm_wall_seconds": float(warm_wall),
            "compile_events": int(eng.compiles),
            "steady_compile_events": int(steady_compiles),
            "cap_transitions": int(eng.cap_transitions),
            "ladder_rungs": int(eng.ladder_rungs),
            "retries": int(eng.retries),
            "mean_comm_slots": float(np.mean(comm)),
            "mean_pull_slots": float(np.mean(pull_req) + np.mean(pull_resp)),
            "mean_pull_req_slots": float(np.mean(pull_req)),
            "mean_pull_resp_units": float(np.mean(pull_resp)),
            "shrink_events_per_batch": float(np.mean(shrinks)),
            "rows_reaggregated_per_batch": float(np.mean(reaggs)),
            "shrink_dims_per_batch": float(np.mean(dims)),
            "recover_hits_per_batch": float(np.mean(recovers)),
            "last_host_seconds": float(eng.last_host_seconds),
            "csr_rebuilds": int(csr.rebuilds),
            "csr_row_refreshes": int(csr.row_refreshes),
            "csr_uploads": int(csr.uploads)}


def main():
    use_compile_cache()
    sweep = partition_counts(jax.device_count())
    records = []
    for parts in sweep:
        for mode in ("ripple", "rc"):
            records.append(run(parts, mode))
    by = {(r["parts"], r["mode"]): r for r in records}
    reduction = {}
    for parts in sweep:
        ratio = by[(parts, "rc")]["mean_comm_slots"] \
            / max(by[(parts, "ripple")]["mean_comm_slots"], 1e-9)
        reduction[str(parts)] = ratio
        print(f"fig12/comm-reduction/p{parts},0.0,rc_over_rp={ratio:.1f}x",
              flush=True)
    # monotonic aggregators: candidate-extrema mailboxes + shrink-only
    # re-aggregation pulls vs the pull-everything RC baseline.  The
    # candidate halo is identical in both modes, so the GROW/SHRINK
    # classification shows up in the *pull* slots (odd comm entries):
    # RIPPLE requests re-aggregation only for covered-removal rows, RC for
    # every affected row.  Deletion-heavy stream (bench_single's monotonic
    # regime) on a sparse graph with small batches keeps the propagation in
    # the incremental regime; gc-min because the non-self-dependent family
    # lets filtered propagation actually shed rows (SAGE's h^{l-1}
    # dependence keeps every frontier row alive regardless of aggregator).
    mono_parts = max(p for p in sweep if p <= 4)
    mono = []
    for mode in ("ripple", "rc"):
        mono.append(run(mono_parts, mode, workload="gc-min", n=3000,
                        m=15000, batch=20, n_updates=300, mix=(1, 3, 1)))
    mono_ratio = mono[1]["mean_comm_slots"] \
        / max(mono[0]["mean_comm_slots"], 1e-9)
    pull_ratio = mono[1]["mean_pull_slots"] \
        / max(mono[0]["mean_pull_slots"], 1e-9)
    # the per-dim payoff in isolation: response payload scalars (RC ships
    # d_loc-wide rows per request, RIPPLE one scalar per shrunk-dim pull)
    resp_ratio = mono[1]["mean_pull_resp_units"] \
        / max(mono[0]["mean_pull_resp_units"], 1e-9)
    print(f"fig12/comm-reduction/gc-min-p{mono_parts},0.0,"
          f"rc_over_rp={mono_ratio:.1f}x pull_rc_over_rp={pull_ratio:.1f}x "
          f"resp_rc_over_rp={resp_ratio:.1f}x", flush=True)
    with open(OUT_PATH, "w") as f:
        json.dump({"bench": "dist", "workload": "gc-s", "n": 1500,
                   "device": {"platform": jax.devices()[0].platform,
                              "kind": jax.devices()[0].device_kind,
                              "count": jax.device_count()},
                   "m": 30000, "batch": 100, "n_updates": 1200, "d": D,
                   "warmup_batches": WARMUP_BATCHES,
                   "results": records,
                   "comm_reduction_rc_over_rp": reduction,
                   "monotonic": {"workload": "gc-min", "n": 3000, "m": 15000,
                                 "batch": 20, "n_updates": 300,
                                 "mix": [1, 3, 1], "results": mono,
                                 "comm_reduction_rc_over_rp": mono_ratio,
                                 "pull_reduction_rc_over_rp": pull_ratio,
                                 "pull_resp_reduction_rc_over_rp":
                                     resp_ratio}},
                  f, indent=2)
    print(f"wrote {os.path.relpath(OUT_PATH)}", flush=True)


if __name__ == "__main__":
    main()
