"""The serving layer: snapshot consistency against a pause-ingest oracle,
read-your-writes per tenant, staleness policies, overlap-beats-blocking,
admission backpressure, deadline-driven micro-batching, the latency model,
and the load generators."""
import threading
import time

import numpy as np
import pytest

from repro.api import InferenceSession, SessionConfig
from repro.core.graph import EdgeUpdate, FeatureUpdate
from repro.serve import (AdmissionError, ClosedLoopLoad, GraphServer,
                         LatencyModel, OpenLoopLoad, StaleReadError,
                         TenantConfig, split_stream, tenant_shares)

ATOL = 2e-3
RTOL = 2e-3


def _small_cfg(engine, **over):
    base = dict(workload="gc-s", engine=engine, graph="er", n=40, m=160,
                d_in=8, d_hidden=12, n_classes=5, seed=0)
    base.update(over)
    return SessionConfig(**base)


def _session(engine, **over):
    return InferenceSession.build(_small_cfg(engine, **over))


# -- snapshot consistency vs a pause-ingest oracle --------------------------
# the oracle: a twin session fed the same prefix synchronously, with ingest
# fully stopped before every read.  A snapshot at version v must equal the
# oracle after exactly v micro-batches — bit-exact, never a half-batch.
@pytest.mark.parametrize("engine,options", [
    ("ripple", {}),
    ("device", {"donate": True}),
    ("device", {"donate": False}),
    ("device", {"async_dispatch": True}),
])
def test_snapshot_never_observes_half_batch(engine, options):
    s = _session(engine, engine_options=options)
    oracle = _session("ripple")
    srv = GraphServer(s, tenants=["a"], threaded=False, max_batch=6)
    updates = list(s.make_stream(36, seed=1))
    srv.submit("a", updates)
    applied = 0
    while srv.pump(max_batches=1):
        srv.drain()                      # force pipelined tails out too
        v = srv.version
        assert v > applied               # every pump commits >= 1 batch
        # oracle replays exactly the updates covered by the published
        # version (6 per micro-batch, as the controller sliced them)
        oracle.ingest(updates[applied * 6:v * 6], batch_size=6)
        got = srv.query("a", np.arange(40)).values
        want = oracle.query()
        np.testing.assert_allclose(got, want, atol=ATOL, rtol=RTOL)
        applied = v
    assert srv.version * 6 >= len(updates)


def test_threaded_snapshot_is_always_a_committed_prefix():
    """Under a live worker, every concurrent read must equal the oracle
    state after exactly `version` micro-batches — interleaving with an
    in-flight batch must never show through."""
    s = _session("ripple")
    updates = list(s.make_stream(60, seed=1))
    # oracle states after every 4-update micro-batch, precomputed
    oracle = _session("ripple")
    states = [oracle.query().copy()]
    for i in range(0, len(updates), 4):
        oracle.ingest(updates[i:i + 4], batch_size=4)
        states.append(oracle.query().copy())

    srv = GraphServer(s, tenants=["a"], max_batch=4,
                      controller=None).start()
    errs = []

    def reader():
        for _ in range(200):
            with srv._scv:               # pin (version, values) atomically
                v = srv.version
                got = srv._H_pub.copy()
            if not np.allclose(got, states[v], atol=ATOL, rtol=RTOL):
                errs.append(v)

    th = threading.Thread(target=reader)
    th.start()
    for i in range(0, len(updates), 4):
        srv.submit("a", updates[i:i + 4])
    srv.drain()
    th.join()
    srv.stop()
    assert not errs, f"readers saw non-committed states at versions {errs}"
    assert srv.version == len(updates) // 4


def test_read_your_writes_per_tenant():
    s = _session("ripple")
    srv = GraphServer(s, tenants=[TenantConfig("a", staleness="wait"),
                                  TenantConfig("b", staleness="wait")],
                      threaded=False)
    ups = list(s.make_stream(20, seed=1))
    seq_a = srv.submit("a", ups[:12])
    srv.pump()
    # a's reads cover everything a submitted; b never submitted anything
    r = srv.query("a", np.arange(5))
    assert r.seen_seq >= seq_a and r.staleness == 0
    assert srv.tenant("a").behind() == 0
    seq_b = srv.submit("b", ups[12:])
    assert srv.tenant("b").behind() == seq_b   # queued, not yet visible
    srv.pump()
    assert srv.query("b", np.arange(5)).staleness == 0


def test_swap_engine_preserves_snapshot_and_sequences():
    s = _session("ripple")
    srv = GraphServer(s, tenants=["a"], threaded=False)
    ups = list(s.make_stream(30, seed=1))
    srv.submit("a", ups[:18])
    srv.pump()
    before = srv.query("a", np.arange(40))
    srv.swap_engine("device")
    after = srv.query("a", np.arange(40))
    np.testing.assert_allclose(before.values, after.values,
                               atol=ATOL, rtol=RTOL)
    assert after.seen_seq == before.seen_seq       # read-your-writes survives
    # and the swapped engine keeps serving consistently
    srv.submit("a", ups[18:])
    srv.pump()
    srv.drain()
    oracle = _session("ripple")
    oracle.ingest(ups, batch_size=256)
    np.testing.assert_allclose(srv.query("a", np.arange(40)).values,
                               oracle.query(), atol=ATOL, rtol=RTOL)
    assert srv.tenant("a").behind() == 0


def test_threaded_swap_mid_traffic():
    s = _session("ripple")
    srv = GraphServer(s, tenants=["a"], max_batch=4).start()
    ups = list(s.make_stream(40, seed=1))
    srv.submit("a", ups[:20])
    srv.swap_engine("device")              # worker may be mid-batch
    srv.submit("a", ups[20:])
    srv.drain()
    srv.stop()
    oracle = _session("ripple")
    oracle.ingest(ups, batch_size=4)
    np.testing.assert_allclose(srv.query("a", np.arange(40)).values,
                               oracle.query(), atol=ATOL, rtol=RTOL)


# -- staleness policies -----------------------------------------------------
def test_reject_policy_raises_when_behind():
    s = _session("ripple")
    srv = GraphServer(s, tenants=[TenantConfig("a", staleness="reject")],
                      threaded=False)
    srv.submit("a", list(s.make_stream(8, seed=1)))
    with pytest.raises(StaleReadError):
        srv.query("a", [0, 1])
    assert srv.tenant("a").rejected_queries == 1
    srv.pump()
    assert srv.query("a", [0, 1]).staleness == 0   # caught up -> serves


def test_max_staleness_slack_allows_bounded_lag():
    s = _session("ripple")
    srv = GraphServer(s, tenants=[TenantConfig("a", staleness="reject",
                                               max_staleness=100)],
                      threaded=False)
    srv.submit("a", list(s.make_stream(8, seed=1)))
    r = srv.query("a", [0])                # 8 behind but slack is 100
    assert 0 < r.staleness <= 100


def test_wait_policy_blocks_until_published():
    s = _session("ripple")
    srv = GraphServer(s, tenants=[TenantConfig("a", staleness="wait",
                                               wait_timeout_s=10.0)],
                      max_batch=4).start()
    srv.submit("a", list(s.make_stream(12, seed=1)))
    r = srv.query("a", [0, 1])             # blocks until its writes publish
    assert r.staleness == 0
    srv.stop()


def test_wait_policy_times_out_without_ingest():
    s = _session("ripple")
    srv = GraphServer(s, tenants=[TenantConfig("a", staleness="wait",
                                               wait_timeout_s=0.05)],
                      threaded=False)       # nothing will ever pump
    srv.submit("a", list(s.make_stream(4, seed=1)))
    with pytest.raises(StaleReadError, match="gave up"):
        srv.query("a", [0])


# -- overlap: snapshot reads vs blocking reads ------------------------------
def test_snapshot_query_overlaps_ingest_faster_than_blocking():
    """The tentpole's measurable claim: while a batch is propagating, a
    snapshot read returns immediately but a blocking read waits the batch
    out.  Engine apply is artificially slowed so the contrast is
    deterministic on any machine."""
    s = _session("ripple")
    srv = GraphServer(s, tenants=["a"], max_batch=4)
    real_apply = s.apply_one
    def slow_apply(batch):
        time.sleep(0.05)
        return real_apply(batch)
    s.apply_one = slow_apply
    srv.start()
    srv.submit("a", list(s.make_stream(24, seed=1)))
    time.sleep(0.01)                        # let the worker pick up a batch
    t0 = time.perf_counter()
    snap = srv.query("a", [0, 1], mode="snapshot")
    t_snap = time.perf_counter() - t0
    t0 = time.perf_counter()
    srv.query("a", [0, 1], mode="blocking")
    t_block = time.perf_counter() - t0
    srv.stop()
    assert t_snap < t_block, (t_snap, t_block)
    assert t_snap < 0.05 / 2                # didn't wait out the batch
    assert snap.values.shape == (2, 5)


# -- admission control ------------------------------------------------------
def test_backpressure_reject_policy():
    s = _session("ripple")
    srv = GraphServer(s, tenants=["a"], threaded=False, capacity=10,
                      overload="reject")
    ups = list(s.make_stream(16, seed=1))
    srv.submit("a", ups[:10])              # fills the queue exactly
    with pytest.raises(AdmissionError):
        srv.submit("a", ups[10:])
    assert srv.tenant("a").rejected_updates == 6
    srv.pump()                             # drains -> admits again
    assert srv.submit("a", ups[10:]) == 16


def test_backpressure_block_policy_waits_for_drain():
    s = _session("ripple")
    srv = GraphServer(s, tenants=["a"], capacity=8, max_batch=4,
                      overload="block").start()
    ups = list(s.make_stream(40, seed=1))
    for i in range(0, len(ups), 8):
        srv.submit("a", ups[i:i + 8])      # would overflow without draining
    srv.drain()
    srv.stop()
    assert srv.tenant("a").submitted == 40
    assert srv.tenant("a").behind() == 0


# -- deadline-driven micro-batching -----------------------------------------
def test_session_deadline_shrinks_realized_batch():
    """The dead-knob fix: a tight deadline_ms must reduce the realized
    micro-batch size on plain session.ingest (no serving layer involved)."""
    loose = _session("ripple")
    tight = _session("ripple")
    ups = list(loose.make_stream(40, seed=1))
    rep_loose = loose.ingest(list(ups), batch_size=16)
    rep_tight = tight.ingest(list(ups), batch_size=16, deadline_ms=1e-6)
    assert rep_loose.n_batches == 3        # 16/16/8, deadline off
    assert rep_tight.final_batch_size == 1
    assert rep_tight.n_batches > rep_loose.n_batches


def test_server_deadline_shrinks_micro_batches():
    s = _session("ripple")
    srv = GraphServer(s, tenants=["a"], threaded=False,
                      deadline_ms=1e-6, max_batch=16)
    srv.submit("a", list(s.make_stream(32, seed=1)))
    srv.pump()
    sizes = srv.metrics()["batch_sizes"]
    assert sizes[0] == 16                  # no latency model yet -> hi
    assert sizes[-1] == 1                  # model learned: impossible budget
    assert len(sizes) > 2


# -- latency model ----------------------------------------------------------
def test_latency_model_learns_affine_cost():
    m = LatencyModel(alpha=0.5)
    for bs in (1, 8, 64, 8, 1, 64) * 20:
        m.observe(bs, 1e-3 + 1e-4 * bs)    # a=1ms, b=0.1ms/update
    assert m.predict(32) == pytest.approx(1e-3 + 3.2e-3, rel=0.2)
    # deadline 2ms -> roughly (2*0.85-1)/0.1 = 7 updates
    assert 2 <= m.batch_for(2e-3) <= 12
    assert m.batch_for(0.5e-3) == 1        # under the fixed overhead -> lo
    assert LatencyModel().batch_for(1.0, hi=99) == 99   # no obs -> hi


# -- load generators --------------------------------------------------------
def test_tenant_shares_power_law():
    sh = tenant_shares(4, skew=1.0)
    assert sh[0] > sh[1] > sh[3] and sh.sum() == pytest.approx(1.0)
    flat = tenant_shares(4, skew=0.0)
    np.testing.assert_allclose(flat, 0.25)


def test_split_stream_partitions_everything():
    s = _session("ripple")
    ups = list(s.make_stream(50, seed=1))
    per = split_stream(ups, 3, skew=1.0, seed=0)
    assert sum(len(p) for p in per) == 50
    assert len(per[0]) > len(per[2])       # hot tenant gets more


@pytest.mark.parametrize("loader", [ClosedLoopLoad, OpenLoopLoad])
def test_load_generators_deliver_everything(loader):
    s = _session("ripple")
    names = ["a", "b"]
    srv = GraphServer(s, tenants=names, max_batch=8).start()
    ups = list(s.make_stream(40, seed=1))
    per = dict(zip(names, split_stream(ups, 2, seed=0)))
    kw = {"rate": 2000.0} if loader is OpenLoopLoad else {}
    rep = loader(srv, per, chunk=4, query_every=2, seed=0, **kw).run()
    srv.stop()
    assert rep.n_updates == 40 and rep.n_rejected == 0
    assert rep.n_queries > 0 and len(rep.query_latencies) == rep.n_queries
    assert srv.version > 0
    # every update was applied AND published (cross-tenant interleaving is
    # loader-dependent, so compare against the server's own engine state:
    # the published snapshot must bit-match it once the queue is drained)
    assert srv.metrics()["published_updates"] == 40
    np.testing.assert_array_equal(srv._H_pub,
                                  np.asarray(srv.session.query()))


def test_worker_error_surfaces_on_api_calls():
    s = _session("ripple")
    srv = GraphServer(s, tenants=["a"]).start()
    def boom(batch):
        raise RuntimeError("engine exploded")
    s.apply_one = boom
    srv.submit("a", list(s.make_stream(4, seed=1)))
    with pytest.raises(RuntimeError, match="engine exploded"):
        for _ in range(100):
            time.sleep(0.01)
            srv.query("a", [0])
    srv._error = None
    srv.stop(drain=False)


# -- weighted-deficit tenant scheduling -------------------------------------
def test_weighted_deficit_tenant_share():
    """Under saturation (both queues backlogged), a 3:1-weighted tenant
    pair gets a ~3:1 share of the served slots; once the heavy tenant
    drains, the scheduler is work-conserving and the light tenant takes
    every slot."""
    s = _session("ripple")
    srv = GraphServer(s, tenants=[TenantConfig("heavy", weight=3.0),
                                  TenantConfig("light", weight=1.0)],
                      threaded=False, max_batch=8)
    updates = list(s.make_stream(200, seed=2))
    srv.submit("heavy", updates[:100])
    srv.submit("light", updates[100:])
    srv.pump(max_batches=10)             # both backlogs still non-empty
    m = srv.metrics()["tenants"]
    h, l = m["heavy"]["committed"], m["light"]["committed"]
    assert h + l >= 40, "pump served too little to measure the share"
    assert h < 100 and l < 100, "a backlog drained: not saturated"
    ratio = h / max(l, 1)
    assert 2.2 <= ratio <= 3.8, \
        f"3:1-weighted pair served at {ratio:.2f}:1 ({h} vs {l})"
    srv.pump()                           # drain everything
    m = srv.metrics()["tenants"]
    assert m["heavy"]["committed"] == 100
    assert m["light"]["committed"] == 100


# -- spans and counters of the served device path -----------------------------
_SPANS = ("ripple.serve.micro_batch", "ripple.serve.take",
          "ripple.graph.topology", "ripple.engine.route",
          "ripple.mirror.refresh", "ripple.mirror.rebuild",
          "ripple.engine.dispatch", "ripple.engine.device_wait",
          "ripple.engine.retry", "ripple.engine.commit_gather",
          "ripple.serve.publish")


def _program_spans(trace_dir):
    """{thread: [(start_ns, end_ns, name, batch)]} of the ripple.* spans in
    the profile written under ``trace_dir``."""
    import glob
    import os

    from jax.profiler import ProfileData

    path, = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    out = {}
    for plane in ProfileData.from_file(path).planes:
        for k, line in enumerate(plane.lines):
            for e in line.events:
                if e.name.startswith("ripple."):
                    out.setdefault((plane.name, k), []).append(
                        (e.start_ns, e.start_ns + e.duration_ns, e.name,
                         dict(e.stats).get("batch")))
    return out


def test_device_path_spans_and_shape_misses(tmp_path):
    """Under a profiler session the served device path records every span
    on the worker thread, each inside the micro-batch root that carries
    its batch number; a repeated refresh shape is no new mirror key, a
    larger one is, and a row outgrowing its slack is a rebuild."""
    import jax

    s = _session("device", engine_options={"min_bucket": 4})
    eng = s.engine.impl
    g = s.graph
    srv = GraphServer(s, tenants=["a"], max_batch=24).start()

    def new_edges(src, k):
        return [EdgeUpdate(src, v, True, 1.0) for v in range(g.n)
                if v != src and not g.has_edge(src, v)][:k]

    def scatter_misses(updates):
        before = eng.shape_misses["mirror_scatter"]
        srv.submit("a", updates)
        srv.drain()
        return eng.shape_misses["mirror_scatter"] - before

    try:
        with jax.profiler.trace(str(tmp_path)):
            # the first batch meets the geometric caps of min_bucket 4 and
            # overflows; a fresh refresh shape is one miss
            srv.submit("a", [FeatureUpdate(v, np.ones(8, np.float32))
                             for v in range(8)])
            srv.drain()
            assert scatter_misses(new_edges(1, 1)) == 1
            assert scatter_misses(new_edges(2, 1)) == 0
            rebuilds = eng.out_mirror.rebuilds
            # 8 adds outgrow vertex 0's slack (1.5 x degree + 4): a rebuild
            # and no scatter; then a refresh of more than 64 slots
            srv.submit("a", new_edges(0, 8))
            srv.drain()
            assert eng.out_mirror.rebuilds == rebuilds + 1
            wide = [e for u in range(3, 23) for e in new_edges(u, 1)]
            assert scatter_misses(wide) == 1
    finally:
        srv.stop()
    assert eng.retries > 0
    assert eng.shape_misses["propagate"] >= 2    # warm-up + the retry rung
    by_thread = _program_spans(str(tmp_path))
    assert len(by_thread) == 1, "program spans off the worker thread"
    spans, = by_thread.values()
    assert {n for _, _, n, _ in spans} == set(_SPANS)
    roots = {b: (s0, e0) for s0, e0, n, b in spans
             if n == "ripple.serve.micro_batch"}
    assert len(roots) == len(srv.batch_sizes)
    for s0, e0, n, b in spans:
        assert b in roots and roots[b][0] <= s0 <= e0 <= roots[b][1], n
