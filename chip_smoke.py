#!/usr/bin/env python3
"""Smoke run of the served incremental path on a TPU at ogbn-arxiv scale.

    python3 chip_smoke.py              # one chip
    python3 chip_smoke.py --chips 4    # the mesh engines on a 2x2 host

One chip: an ``InferenceSession`` on the ``device`` engine, over a graph
of ogbn-arxiv's size (169,343 vertices, 1,166,243 edges, 128-wide input
features, 40 classes, 2 layers of width 128; graph, features and weights
drawn from ``--seed``), takes a paper-protocol update stream through a
4-tenant ``GraphServer`` that answers snapshot queries during ingest, and
must then match the ``full`` oracle.  gc-s, gs-max, gi-s and gp-m then
repeat ingest and the oracle check with ``use_pallas``, and each hop
kernel must appear in the compiled propagate as a TPU kernel.

Four chips: ``dist`` and ``dist-rc`` for gc-s and gs-max on ("data",
"model") meshes of shape (4, 1) and (2, 2), each checked against the
oracle, with the mesh state spread over every chip.

No phase catches its own failure: any exception or mismatch ends the run
with a non-zero exit.  Without a TPU the script exits 1 before any phase.
The last line of stdout is one JSON object naming the device, printed only
when every phase passed.
"""
from __future__ import annotations

import argparse
import contextlib
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.api import InferenceSession, SessionConfig  # noqa: E402
from repro.core import full_inference  # noqa: E402
from repro.launch.mesh import make_local_mesh  # noqa: E402
from repro.serve import GraphServer, split_stream  # noqa: E402
from repro.utils import next_bucket, use_compile_cache  # noqa: E402

# ogbn-arxiv's vertex/edge counts, feature width and classes; the hidden
# width is the repo's own (configs/ripple_stream.py)
ARXIV = dict(graph="er", n=169_343, m=1_166_243, n_layers=2, d_in=128,
             d_hidden=128, n_classes=40)
# the repo's oracle tolerance for every incremental engine (f32 deltas
# accumulated in a different order than the from-scratch pass)
TOL = 2e-3
# workload, stream mix (adds, deletes, features), hop kernel it runs
KERNEL_PHASES = (("gc-s", (1, 1, 1), "delta_apply"),
                 ("gs-max", (1, 3, 1), "extremum_apply_masked"),
                 ("gi-s", (1, 1, 1), "mlp_apply"),
                 ("gp-m", (1, 1, 1), "embedding_bag"))
MESH_SHAPES = ((4, 1), (2, 2))
_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


@contextlib.contextmanager
def timed(name: str):
    """Print a phase's wall time and backend compile count when it ends."""
    compiles = [0]

    def on_event(event, _secs, **_kw):
        if event == _COMPILE_EVENT:
            compiles[0] += 1

    jax.monitoring.register_event_duration_secs_listener(on_event)
    t0 = time.perf_counter()
    try:
        yield
    finally:
        jax.monitoring.unregister_event_duration_listener(on_event)
    print(f"phase {name}: {time.perf_counter() - t0:.3f} s wall, "
          f"{compiles[0]} compiles", flush=True)


def build_session(workload: str, engine: str, *, size: dict, seed: int,
                  engine_options: dict | None = None) -> InferenceSession:
    return InferenceSession.build(SessionConfig(
        workload=workload, engine=engine,
        engine_options=dict(engine_options or {}), seed=seed, **size))


def serve_stream(session: InferenceSession, *, n_updates: int, batch: int,
                 tenants: int, seed: int) -> dict:
    """Stream updates through a threaded ``GraphServer``, with snapshot
    queries from every tenant while ingest runs; then require the
    published snapshot to equal the engine's state bit for bit."""
    updates = list(session.make_stream(n_updates, seed=seed))
    names = [f"t{i}" for i in range(tenants)]
    per = split_stream(updates, tenants, seed=seed)
    chunk = max(batch // tenants, 1)
    n = session.graph.n
    rng = np.random.default_rng(seed)
    queries = 0
    with GraphServer(session, tenants=names, max_batch=batch) as server:
        for i in range(0, max(len(p) for p in per), chunk):
            for name, ups in zip(names, per):
                if ups[i:i + chunk]:
                    server.submit(name, ups[i:i + chunk])
                server.query(name, rng.integers(0, n, size=8))
                queries += 1
        server.drain()
    snapshot = server.query(names[0], np.arange(n)).values
    m = server.metrics()
    if m["published_updates"] != len(updates):
        raise AssertionError(f"published {m['published_updates']} of "
                             f"{len(updates)} updates")
    if not np.array_equal(snapshot, session.query()):
        raise AssertionError("published snapshot differs from engine state")
    return {"updates": len(updates), "batches": m["batches"],
            "queries": queries}


def check_oracle(session: InferenceSession, label: str) -> float:
    """Final-layer embeddings vs a from-scratch ``full_inference`` pass
    (float32 products, ``workloads.matmul_f32``) over the synced graph and
    features; returns the max abs error."""
    st = session.sync()
    H, _ = full_inference(session.workload, session.params,
                          jnp.asarray(st.H[0]), *session.graph.coo(),
                          session.graph.in_degree)
    ref = np.asarray(H[-1])
    got = session.query()
    np.testing.assert_allclose(got, ref, atol=TOL, rtol=TOL,
                               err_msg=f"{label} vs the full oracle")
    return float(np.abs(got - ref).max())


def tpu_kernel_calls(session: InferenceSession, kernel: str) -> int:
    """Count the TPU kernels in the engine's compiled propagate; fail if
    the engine runs its kernels in interpret mode or ``kernel`` is not
    among them."""
    eng = session.engine.impl
    if eng.interpret:
        raise AssertionError(f"{kernel} runs in interpret mode on "
                             f"{jax.devices()[0].platform}")
    calls = [line for line in eng.compiled_propagate_text().splitlines()
             if 'custom_call_target="tpu_custom_call"' in line]
    if not any(f"%{kernel}" in line for line in calls):
        raise AssertionError(f"{kernel} is not a tpu_custom_call in the "
                             f"compiled propagate")
    return len(calls)


def kernel_phase(workload: str, mix: tuple, kernel: str, *, size: dict,
                 n_updates: int, batch: int, seed: int,
                 on_tpu: bool) -> dict:
    """Ingest through the Pallas hop kernels, then the oracle check."""
    session = build_session(workload, "device", size=size, seed=seed,
                            engine_options={"use_pallas": True})
    rep = session.ingest(session.make_stream(n_updates, seed=seed + 1,
                                             mix=mix), batch_size=batch)
    shrink = sum(r.shrink_events for r in rep.results)
    if session.workload.spec.monotonic and not shrink:
        raise AssertionError(f"{workload}: the stream caused no SHRINK")
    out = {"updates": rep.n_updates, "shrink_events": shrink,
           "max_err": check_oracle(session, f"{workload} pallas")}
    if on_tpu:
        out["tpu_kernels"] = tpu_kernel_calls(session, kernel)
    return out


def _comm_slots(results, *, monotonic: bool, rc: bool) -> tuple:
    """Mean (halo, pull) slots per batch from the mesh engines' counters:
    a monotonic hop reports [halo, pull requests, pull responses]; an
    invertible hop reports its halo slots (dist) or its pulled ids,
    requests and responses (dist-rc)."""
    halo, pull = [], []
    for r in results:
        c = np.asarray(r.messages_per_hop, dtype=np.float64)
        if not c.size:
            continue
        if monotonic:
            halo.append(c[0::3].sum())
            pull.append(c[1::3].sum() + c[2::3].sum())
        else:
            halo.append(0.0 if rc else c.sum())
            pull.append(c.sum() if rc else 0.0)
    return float(np.mean(halo)), float(np.mean(pull))


def _assert_spread(session: InferenceSession, n_devices: int) -> None:
    """Every mesh-resident array spans all ``n_devices`` chips."""
    eng = session.engine.impl
    arrays = list(eng.H) + list(eng.S) + list(eng.out_csr.device())
    for arr in arrays:
        if len(arr.sharding.device_set) != n_devices:
            raise AssertionError(f"array {arr.shape} lives on "
                                 f"{len(arr.sharding.device_set)} of "
                                 f"{n_devices} devices")


def mesh_phase(workload: str, mix: tuple, *, size: dict, n_updates: int,
               batch: int, seed: int, mesh_shapes=MESH_SHAPES) -> list:
    """``dist`` and ``dist-rc`` on each mesh shape, one session migrated
    between them by ``swap_engine``; each leg ingests a fresh stream and
    is checked against the oracle.  The batch buffers start at the
    stream's batch bucket, so the cap ladder compiles no rung for a
    smaller batch shape."""
    n_dev = int(np.prod(mesh_shapes[0]))
    session = None
    out = []
    for shape in mesh_shapes:
        opts = {"mesh": make_local_mesh(*shape),
                "min_bucket": next_bucket(batch)}
        for engine in ("dist", "dist-rc"):
            if session is None:
                session = build_session(workload, engine, size=size,
                                        seed=seed, engine_options=opts)
            else:
                session.swap_engine(engine, **opts)
            rep = session.ingest(session.make_stream(
                n_updates, seed=seed + len(out) + 1, mix=mix),
                batch_size=batch)
            _assert_spread(session, n_dev)
            halo, pull = _comm_slots(rep.results,
                                     monotonic=session.workload.spec.monotonic,
                                     rc=engine == "dist-rc")
            err = check_oracle(session, f"{workload} {engine} {shape}")
            out.append({"mesh": shape, "engine": engine, "halo_slots": halo,
                        "pull_slots": pull, "max_err": err})
            print(f"  {workload} {engine} mesh={shape}: halo slots/batch "
                  f"{halo:.1f}, pull slots/batch {pull:.1f}, max err "
                  f"{err:.3e}", flush=True)
    return out


def run_one_chip(size: dict, *, seed: int, n_serve: int, n_kernel: int,
                 batch: int, tenants: int, on_tpu: bool) -> None:
    with timed("build gc-s device"):
        session = build_session("gc-s", "device", size=size, seed=seed)
    with timed("serve gc-s"):
        info = serve_stream(session, n_updates=n_serve, batch=batch,
                            tenants=tenants, seed=seed + 1)
    print(f"  served {info}", flush=True)
    with timed("oracle gc-s"):
        err = check_oracle(session, "gc-s served")
    print(f"  gc-s served max err {err:.3e}", flush=True)
    del session
    for workload, mix, kernel in KERNEL_PHASES:
        with timed(f"pallas {workload} ({kernel})"):
            info = kernel_phase(workload, mix, kernel, size=size,
                                n_updates=n_kernel, batch=batch, seed=seed,
                                on_tpu=on_tpu)
        print(f"  {workload}: {info}", flush=True)


def run_four_chips(size: dict, *, seed: int, n_updates: int,
                   batch: int) -> None:
    for workload, mix in (("gc-s", (1, 1, 1)), ("gs-max", (1, 3, 1))):
        with timed(f"mesh {workload}"):
            mesh_phase(workload, mix, size=size, n_updates=n_updates,
                       batch=batch, seed=seed)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {dev.platform}",
              file=sys.stderr)
        return 1
    if len(jax.devices()) < args.chips:
        print(f"chip_smoke: needs {args.chips} chips, JAX found "
              f"{len(jax.devices())}", file=sys.stderr)
        return 1
    print(f"device_kind={dev.device_kind} count={len(jax.devices())} "
          f"compile_cache={use_compile_cache()}", flush=True)
    if args.chips == 4:
        run_four_chips(ARXIV, seed=args.seed, n_updates=300, batch=100)
    else:
        run_one_chip(ARXIV, seed=args.seed, n_serve=2000, n_kernel=500,
                     batch=100, tenants=4, on_tpu=True)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
