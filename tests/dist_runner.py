"""Standalone distributed-session checker (run in a subprocess with 8 virtual
CPU devices; see test_distributed.py).  Exits non-zero on any mismatch.

Everything goes through ``repro.api`` — the distributed path is exercised
exactly the way a serving deployment reaches it: ``InferenceSession`` with
``engine="dist"`` / ``"dist-rc"`` and a mesh in ``engine_options``.  Covers:

  * oracle exactness of the dist session for all workload families — the
    paper's five plus the monotonic pair (gs-max/gc-min, whose mailboxes
    ship candidate extrema and whose SHRINK rows issue re-aggregation
    pulls) — in both modes, including the multi-pod ("pod", "data")
    partition geometry;
  * ``swap_engine`` ripple -> dist -> device round-trip equivalence;
  * sharded checkpoint -> restore onto a *different* mesh geometry.
"""
import os
import sys

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import tempfile  # noqa: E402

import numpy as np  # noqa: E402
import jax  # noqa: E402

from repro.api import InferenceSession, SessionConfig, engine_names  # noqa: E402
from repro.core import full_inference  # noqa: E402
from repro.core.graph import EdgeUpdate, FeatureUpdate  # noqa: E402
from repro.launch.mesh import make_local_mesh  # noqa: E402

ATOL = 3e-3


def build(name: str, engine: str, options: dict, **over) -> InferenceSession:
    cfg = dict(workload=name, engine=engine, engine_options=options,
               graph="er", n=60, m=260, d_in=8, d_hidden=12, n_classes=4,
               seed=0)
    cfg.update(over)
    return InferenceSession.build(SessionConfig(**cfg))


def oracle_H(session) -> list[np.ndarray]:
    st = session.sync()
    H, _ = full_inference(session.workload, session.params,
                          jax.numpy.asarray(st.H[0]), *session.graph.coo(),
                          session.graph.in_degree)
    return [np.asarray(h) for h in H]


def assert_exact(session, label: str) -> None:
    H_ref = oracle_H(session)
    for l, (a, b) in enumerate(zip(session.state.H, H_ref)):
        err = np.abs(a - b).max()
        assert err < ATOL, f"{label} layer {l} err={err}"
    q = session.query()
    assert np.abs(q - H_ref[-1]).max() < ATOL, f"{label} query mismatch"


def run(mode: str, name: str) -> None:
    """Session oracle exactness per batch, one workload x one dist mode."""
    mesh = make_local_mesh(4, 2)
    engine = "dist" if mode == "ripple" else "dist-rc"
    s = build(name, engine, {"mesh": mesh})
    updates = list(s.make_stream(15, seed=1))
    comm = None
    for step in range(3):
        rep = s.ingest(updates[step * 5:(step + 1) * 5])
        comm = rep.results[-1].messages_per_hop
        assert_exact(s, f"{mode}/{name} step {step}")
    # monotonic comm interleaves [halo, pull_req, pull_resp] per hop
    # -> 3 slots per layer
    n_slots = 6 if s.workload.spec.monotonic else 2
    assert comm is not None and len(comm) == n_slots
    print(f"OK {mode} {name} comm={comm}")


def run_multipod() -> None:
    """Vertex partition spanning two mesh axes: ("pod", "data") x model."""
    mesh = jax.make_mesh((2, 2, 2), ("pod", "data", "model"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 3)
    s = build("gc-m", "dist", {"mesh": mesh, "data_axes": ("pod", "data")})
    assert s.engine.impl.n_parts == 4 and s.engine.impl.M == 2
    s.ingest(s.make_stream(15, seed=1), batch_size=5)
    assert_exact(s, "multipod")
    # hierarchical halo: combining co-destined deltas intra-pod must never
    # INCREASE the slots that cross the pod boundary
    xp = s.engine.impl.last_xpod
    assert xp is not None and xp[1] <= xp[0], \
        f"hier halo grew cross-pod traffic: {xp}"
    print(f"OK multipod data_axes=('pod','data') xpod={list(map(int, xp))}")


def run_warm_equiv() -> None:
    """Donated-vs-fresh and async-vs-sync propagation must be BIT-exact on
    every workload at 2 and 8 virtual shards — the gated-commit contract
    behind donation and overlap."""
    for parts in (2, 8):
        mesh = make_local_mesh(parts, 8 // parts)
        for name in ("gc-s", "gs-s", "gc-m", "gi-s", "gc-w",
                     "gs-max", "gc-min"):
            variants = ({"donate": False, "warm": False},
                        {"donate": True, "warm": False},
                        {"donate": True, "async_dispatch": True,
                         "warm": False})
            outs = []
            for opts in variants:
                s = build(name, "dist", {"mesh": mesh, **opts})
                s.ingest(s.make_stream(12, seed=2), batch_size=4)
                outs.append(s.engine.impl.gather_H())  # drains the pipeline
            for tag, hs in zip(("donate", "donate+async"), outs[1:]):
                for l, (a, b) in enumerate(zip(outs[0], hs)):
                    assert np.array_equal(a, b), \
                        f"warm-equiv {name}@{parts} shards: {tag} " \
                        f"layer {l} not bit-exact"
    print("OK warm-path bit-exact equivalence (donate, async) x (2, 8)")


def run_overflow_commit() -> None:
    """An overflowing attempt on the donated mesh path commits NOTHING: the
    buffers it returns bit-exactly equal the pre-attempt state, and the
    ladder retry then lands the batch exactly."""
    from repro.core.graph import UpdateBatch

    mesh = make_local_mesh(4, 2)
    s = build("gs-max", "dist", {"mesh": mesh})
    ups = list(s.make_stream(12, seed=3))
    s.ingest(ups[:6])
    eng = s.engine.impl
    H_before = eng.gather_H()

    batch = UpdateBatch(
        edges=[u for u in ups[6:] if hasattr(u, "src")],
        features=[u for u in ups[6:] if not hasattr(u, "src")])
    np_b, out_rows, in_rows = eng._route(batch)
    eng.out_csr.refresh_rows(out_rows)
    eng.in_csr.refresh_rows(in_rows)
    db, k = eng._upload_batch(np_b)
    L = s.workload.spec.n_layers
    tiny = (((2, 4),) * L, 4, 4, 4)   # deliberately too small
    st, final, ovf, *_ = eng._run(db, k, tiny)
    eng._commit_state(st)
    assert float(ovf) > 0, "tiny caps unexpectedly fit the batch"
    for l, (a, b) in enumerate(zip(H_before, eng.gather_H())):
        assert np.array_equal(a, b), \
            f"overflowing attempt mutated layer {l} state"
    # now land the same batch through the ladder and check exactness
    eng._dispatch(db, k)
    eng._resolve()
    assert_exact(s, "overflow-commit")
    print("OK overflow on the donated path commits nothing")


def run_swap_roundtrip() -> None:
    """ripple -> dist -> device mid-stream == never swapping at all."""
    mesh = make_local_mesh(4, 2)
    a = build("gc-m", "ripple", {})
    b = build("gc-m", "ripple", {})
    ups_a = list(a.make_stream(30, seed=1))
    ups_b = list(b.make_stream(30, seed=1))
    a.ingest(ups_a, batch_size=5)

    b.ingest(ups_b[:10], batch_size=5)
    b.swap_engine("dist", mesh=mesh)
    assert b.engine_name == "dist"
    b.ingest(ups_b[10:20], batch_size=5)
    b.swap_engine("device")
    b.ingest(ups_b[20:], batch_size=5)

    for l, (ha, hb) in enumerate(zip(a.sync().H, b.sync().H)):
        err = np.abs(ha - hb).max()
        assert err < ATOL, f"swap layer {l} err={err}"
    assert_exact(b, "swap")
    print("OK swap ripple->dist->device round trip")


def run_ckpt_geometry_change() -> None:
    """Sharded checkpoint under one mesh restores onto a different one."""
    import glob
    import json

    mesh_a = make_local_mesh(4, 2)
    mesh_b = make_local_mesh(2, 4)
    tmp = tempfile.mkdtemp(prefix="dist_ckpt_")
    s = build("gc-s", "dist", {"mesh": mesh_a}, ckpt_dir=tmp,
              ckpt_every=10_000)
    updates = list(s.make_stream(30, seed=1))
    s.ingest(updates[:15], batch_size=5)
    s.checkpoint()
    H_ckpt = [h.copy() for h in s.sync().H]

    # the manifest records the per-shard layout: one file per data shard
    man = json.load(open(glob.glob(os.path.join(tmp, "step_*",
                                                "manifest.json"))[0]))
    assert man["n_shards"] == 4
    assert len(man["leaves"][0]["files"]) == 4

    s.ingest(updates[15:], batch_size=5)  # diverge past the snapshot
    # "worker loss": come back up on a 2-partition mesh
    s.engine_options = {"mesh": mesh_b}
    assert s.restore() >= 0
    for l, (h, href) in enumerate(zip(s.sync().H, H_ckpt)):
        err = np.abs(h - href).max()
        assert err < 1e-6, f"restore layer {l} err={err}"
    assert s.engine.impl.n_parts == 2
    # the restored session keeps serving exactly on the new geometry
    s.ingest(updates[15:], batch_size=5)
    assert_exact(s, "post-restore")
    print("OK sharded ckpt restore across mesh geometry change")


def run_elastic_resize() -> None:
    """elastic_resize is a pure gather -> re-scatter: same embeddings on a
    different partition count, and the resized engine keeps serving."""
    from repro.core.elastic import elastic_resize

    mesh_a = make_local_mesh(4, 2)
    mesh_b = make_local_mesh(2, 4)
    s = build("gs-s", "dist", {"mesh": mesh_a})
    updates = list(s.make_stream(20, seed=1))
    s.ingest(updates[:10], batch_size=5)
    H_before = s.engine.impl.gather_H()
    resized = elastic_resize(s.engine.impl, mesh_b)
    assert resized.n_parts == 2 and resized.data_axes == ("data",)
    for l, (a, b) in enumerate(zip(H_before, resized.gather_H())):
        err = np.abs(a - b).max()
        assert err < 1e-6, f"elastic layer {l} err={err}"
    print("OK elastic_resize 4 -> 2 partitions")


def run_delete_then_readd() -> None:
    """A batch that deletes an edge and adds it back leaves the edge in the
    mesh engine's relabeled graph too, so a later update through it stays
    exact."""
    s = build("gc-s", "dist", {"mesh": make_local_mesh(4, 2)})
    src, dst, _ = s.graph.coo()
    u, v = int(src[0]), int(dst[0])
    s.ingest([EdgeUpdate(u, v, False), EdgeUpdate(u, v, True)])
    s.ingest([FeatureUpdate(u, np.full(8, 3.0, np.float32))])
    assert_exact(s, "delete then re-add")
    print("OK delete then re-add in one batch")


if __name__ == "__main__":
    assert {"dist", "dist-rc"} <= set(engine_names())
    for mode in ("ripple", "rc"):
        for name in ("gc-s", "gs-s", "gc-m", "gi-s", "gc-w",
                     "gs-max", "gc-min"):
            run(mode, name)
    run_multipod()
    run_warm_equiv()
    run_overflow_commit()
    run_swap_roundtrip()
    run_ckpt_geometry_change()
    run_elastic_resize()
    run_delete_then_readd()
    print("ALL DIST OK")
