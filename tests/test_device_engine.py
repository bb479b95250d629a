"""Jitted device engine must match the host engine and the full oracle."""
import numpy as np
import pytest

import jax

from repro.core import (DynamicGraph, EdgeUpdate, FeatureUpdate, InferenceState,
                        UpdateBatch, WORKLOAD_NAMES, erdos_renyi,
                        full_inference, make_workload)
from repro.core.device_engine import DeviceEngine

ATOL = 2e-3


def _setup(name, n=48, m=200, n_layers=2, seed=0):
    wl = make_workload(name, n_layers=n_layers, d_in=8, d_hidden=12, n_classes=5)
    src, dst, w = erdos_renyi(n, m, seed=seed, weighted=wl.spec.weighted)
    g = DynamicGraph(n, src, dst, w)
    rng = np.random.default_rng(seed + 1)
    x = rng.normal(size=(n, 8)).astype(np.float32)
    params = wl.init_params(jax.random.PRNGKey(seed))
    state = InferenceState.bootstrap(wl, params, x, g)
    return wl, g, params, state


def _oracle_H(wl, params, g, x_current):
    H, _ = full_inference(wl, params, jax.numpy.asarray(x_current), *g.coo(),
                          g.in_degree)
    return [np.asarray(h) for h in H]


@pytest.mark.parametrize("name", WORKLOAD_NAMES)
def test_device_engine_matches_oracle(name):
    wl, g, params, state = _setup(name)
    eng = DeviceEngine(wl, params, g, state, min_bucket=16)
    rng = np.random.default_rng(3)
    for step in range(4):
        batch = UpdateBatch()
        u, v = int(rng.integers(0, g.n)), int(rng.integers(0, g.n))
        if u != v:
            batch.edges.append(EdgeUpdate(u, v, not g.has_edge(u, v),
                                          float(rng.uniform(0.2, 1.0))))
        batch.features.append(FeatureUpdate(
            int(rng.integers(0, g.n)), rng.normal(size=8).astype(np.float32)))
        eng.apply_batch(batch)
        H_ref = _oracle_H(wl, params, g, eng.host_H()[0])
        for l, (h, href) in enumerate(zip(eng.host_H(), H_ref)):
            np.testing.assert_allclose(h, href, atol=ATOL, rtol=ATOL,
                                       err_msg=f"{name} layer {l} step {step}")


@pytest.mark.parametrize("name", ["gs-max", "gc-min", "gc-s"])
def test_device_add_then_delete_in_one_batch(name):
    """A new edge added and deleted again in one batch changes nothing."""
    wl, g, params, state = _setup(name)
    eng = DeviceEngine(wl, params, g, state, min_bucket=16)
    u, v = 0, 1
    while g.has_edge(u, v) or u == v:
        v += 1
    eng.apply_batch(UpdateBatch(edges=[EdgeUpdate(u, v, True, 1.0),
                                       EdgeUpdate(u, v, False)]))
    assert not g.has_edge(u, v)
    H_ref = _oracle_H(wl, params, g, eng.host_H()[0])
    for h, href in zip(eng.host_H(), H_ref):
        np.testing.assert_allclose(h, href, atol=ATOL, rtol=ATOL)


def test_device_engine_3layer():
    wl, g, params, state = _setup("gs-s", n_layers=3)
    eng = DeviceEngine(wl, params, g, state, min_bucket=16)
    batch = UpdateBatch(edges=[EdgeUpdate(0, 1, True, 1.0),
                               EdgeUpdate(1, 2, True, 1.0)])
    affected = eng.apply_batch(batch)
    assert affected.size > 0
    H_ref = _oracle_H(wl, params, g, eng.host_H()[0])
    for h, href in zip(eng.host_H(), H_ref):
        np.testing.assert_allclose(h, href, atol=ATOL, rtol=ATOL)


def test_overflow_retry_small_buckets():
    """Force tiny initial buckets; ladder must retry and stay exact."""
    wl, g, params, state = _setup("gc-s", n=64, m=700)
    eng = DeviceEngine(wl, params, g, state, min_bucket=4)
    rng = np.random.default_rng(0)
    batch = UpdateBatch(features=[
        FeatureUpdate(int(v), rng.normal(size=8).astype(np.float32))
        for v in rng.choice(g.n, size=20, replace=False)])
    eng.apply_batch(batch)
    assert eng.retries > 0  # the tiny buckets must actually have overflowed
    H_ref = _oracle_H(wl, params, g, eng.host_H()[0])
    for h, href in zip(eng.host_H(), H_ref):
        np.testing.assert_allclose(h, href, atol=ATOL, rtol=ATOL)


# ---------------------------------------------------------------------------
# PR 4: device-resident pipeline (persistent mirror, donation, pallas, async)
# ---------------------------------------------------------------------------
def _stream(g, rng, n_batches=6, d0=8):
    batches = []
    for _ in range(n_batches):
        b = UpdateBatch()
        for _ in range(4):
            u, v = int(rng.integers(0, g.n)), int(rng.integers(0, g.n))
            if u != v:
                b.edges.append(EdgeUpdate(u, v, not g.has_edge(u, v),
                                          float(rng.uniform(0.2, 1.0))))
        b.features.append(FeatureUpdate(
            int(rng.integers(0, g.n)), rng.normal(size=d0).astype(np.float32)))
        batches.append(b)
    return batches


def test_mirror_single_upload_across_stream():
    """The CSR mirror uploads the full pool exactly once; every batch after
    is touched-row refreshes only (no O(E) host->device transfer)."""
    wl, g, params, state = _setup("gs-max")
    eng = DeviceEngine(wl, params, g, state, min_bucket=16)
    rng = np.random.default_rng(5)
    for b in _stream(g, rng, n_batches=8):
        eng.apply_batch(b)
    for mirror in (eng.out_mirror, eng.in_mirror):
        assert mirror.uploads == 1, "pool re-uploaded mid-stream"
        assert mirror.rebuilds == 0
        assert mirror.row_refreshes > 0
    # and the state is still oracle-exact after all those refreshes
    H_ref = _oracle_H(wl, params, g, eng.host_H()[0])
    for h, href in zip(eng.host_H(), H_ref):
        np.testing.assert_allclose(h, href, atol=ATOL, rtol=ATOL)


def test_mirror_rebuild_on_slack_overflow():
    """Concentrated appends outgrow one row's slack; the mirror must do a
    full rebuild and stay consistent with the host adjacency."""
    wl, g, params, state = _setup("gc-s")
    eng = DeviceEngine(wl, params, g, state, min_bucket=16)
    hot = 0
    batch = UpdateBatch(edges=[
        EdgeUpdate(hot, v, True, 1.0) for v in range(1, 40)
        if not g.has_edge(hot, v)])
    eng.apply_batch(batch)
    assert eng.out_mirror.rebuilds >= 1, "slack overflow did not rebuild"
    # device pool content must equal the host half row-for-row
    m = eng.out_mirror
    col = np.asarray(m.col)
    start = np.asarray(m.start)
    length = np.asarray(m.length)
    for v in range(g.n):
        dev_row = np.sort(col[start[v]: start[v] + length[v]])
        host_row = np.sort(g.out.row(v)[0])
        np.testing.assert_array_equal(dev_row, host_row, err_msg=f"row {v}")
    H_ref = _oracle_H(wl, params, g, eng.host_H()[0])
    for h, href in zip(eng.host_H(), H_ref):
        np.testing.assert_allclose(h, href, atol=ATOL, rtol=ATOL)


@pytest.mark.parametrize("name", ["gc-s", "gs-max"])
def test_overflow_commits_nothing(name):
    """An overflowing attempt must leave the (donated) state bit-identical
    — the gated-commit contract behind the lazy ladder retry."""
    from repro.core.device_engine import (propagate_donated,
                                          propagate_monotonic_donated)
    wl, g, params, state = _setup(name, n=64, m=700)
    eng = DeviceEngine(wl, params, g, state, min_bucket=16, warm=False)
    rng = np.random.default_rng(0)
    batch = UpdateBatch(features=[
        FeatureUpdate(int(v), rng.normal(size=8).astype(np.float32))
        for v in rng.choice(g.n, size=16, replace=False)])
    dev_batch, out_rows, in_rows = eng._route(batch)
    before = {"H": eng.host_H(), "S": [np.array(s) for s in eng.state.S],
              "k": np.array(eng.state.k)}
    caps = ((4, 4, 4, 4), (4, 4, 4, 4)) if eng.monotonic \
        else ((4, 4), (4, 4))
    if eng.monotonic:
        new_state, final, ovf, sizes, _stats = propagate_monotonic_donated(
            wl, eng.n, caps, eng.params, eng.state,
            eng.out_mirror.device(), eng.in_mirror.device(), dev_batch,
            interpret=eng.interpret)
    else:
        new_state, final, ovf, sizes = propagate_donated(
            wl, eng.n, caps, eng.params, eng.state,
            eng.out_mirror.device(), dev_batch, interpret=eng.interpret)
    assert bool(ovf), "tiny caps should overflow"
    for l, h in enumerate(new_state.H):
        np.testing.assert_array_equal(np.asarray(h), before["H"][l])
    for l, s in enumerate(new_state.S):
        np.testing.assert_array_equal(np.asarray(s), before["S"][l])
    np.testing.assert_array_equal(np.asarray(new_state.k), before["k"])
    assert np.all(np.asarray(final) == eng.n)  # no affected rows reported


@pytest.mark.parametrize("name", WORKLOAD_NAMES)
def test_donated_path_matches_fresh_nondonated(name):
    """Donated-buffer (in-place) propagation must match a fresh non-donated
    engine on the same stream — all 7 workloads."""
    wl, g, params, state = _setup(name)
    wl2, g2, params2, state2 = _setup(name)
    don = DeviceEngine(wl, params, g, state, min_bucket=16, donate=True)
    ref = DeviceEngine(wl2, params2, g2, state2, min_bucket=16, donate=False)
    r1, r2 = np.random.default_rng(9), np.random.default_rng(9)
    for b1, b2 in zip(_stream(g, r1), _stream(g2, r2)):
        a1 = don.apply_batch(b1)
        a2 = ref.apply_batch(b2)
        np.testing.assert_array_equal(a1, a2)
    for l, (h1, h2) in enumerate(zip(don.host_H(), ref.host_H())):
        np.testing.assert_allclose(h1, h2, atol=1e-6, rtol=1e-6,
                                   err_msg=f"{name} layer {l}")


@pytest.mark.parametrize("name", ["gc-s", "gc-m", "gs-s", "gi-s", "gc-min",
                                  "gs-max", "ga-s", "gp-m"])
def test_pallas_hop_apply_matches_jnp(name):
    """The fused Pallas hop-apply (interpret mode off-TPU) must match the
    jnp oracle path for all three algebra families (gp-m routes its
    feature gather through the EmbeddingBag kernel)."""
    wl, g, params, state = _setup(name)
    wl2, g2, params2, state2 = _setup(name)
    pal = DeviceEngine(wl, params, g, state, min_bucket=16, use_pallas=True)
    ref = DeviceEngine(wl2, params2, g2, state2, min_bucket=16)
    r1, r2 = np.random.default_rng(11), np.random.default_rng(11)
    for b1, b2 in zip(_stream(g, r1, n_batches=4), _stream(g2, r2, n_batches=4)):
        pal.apply_batch(b1)
        ref.apply_batch(b2)
    for l, (h1, h2) in enumerate(zip(pal.host_H(), ref.host_H())):
        np.testing.assert_allclose(h1, h2, atol=1e-4, rtol=1e-4,
                                   err_msg=f"{name} layer {l}")
    H_ref = _oracle_H(wl, params, g, pal.host_H()[0])
    for h, href in zip(pal.host_H(), H_ref):
        np.testing.assert_allclose(h, href, atol=ATOL, rtol=ATOL)


@pytest.mark.parametrize("name", ["gc-s", "gs-max"])
def test_async_dispatch_pipeline_equivalence(name):
    """Pipelined dispatch (lazy overflow check) must drain to the same
    state as the synchronous engine; k stays consistent on device."""
    wl, g, params, state = _setup(name)
    wl2, g2, params2, state2 = _setup(name)
    asy = DeviceEngine(wl, params, g, state, min_bucket=16,
                       async_dispatch=True, debug_checks=True)
    ref = DeviceEngine(wl2, params2, g2, state2, min_bucket=16)
    r1, r2 = np.random.default_rng(13), np.random.default_rng(13)
    for b1, b2 in zip(_stream(g, r1), _stream(g2, r2)):
        asy.apply_batch(b1)
        ref.apply_batch(b2)
    asy.flush()
    np.testing.assert_allclose(np.array(asy.state.k), g.in_degree)
    for l, (h1, h2) in enumerate(zip(asy.host_H(), ref.host_H())):
        np.testing.assert_allclose(h1, h2, atol=1e-6, rtol=1e-6,
                                   err_msg=f"{name} layer {l}")


def test_device_k_maintained_without_host_reupload():
    """The in-degree vector is maintained on device from the batch's
    add/delete counts — it must track the host graph exactly through a
    mixed add/delete stream (debug_checks asserts per batch)."""
    wl, g, params, state = _setup("gc-m")  # mean: k actually normalizes
    eng = DeviceEngine(wl, params, g, state, min_bucket=16,
                       debug_checks=True)
    rng = np.random.default_rng(17)
    for b in _stream(g, rng, n_batches=8):
        eng.apply_batch(b)
    np.testing.assert_allclose(np.array(eng.state.k), g.in_degree)
    H_ref = _oracle_H(wl, params, g, eng.host_H()[0])
    for h, href in zip(eng.host_H(), H_ref):
        np.testing.assert_allclose(h, href, atol=ATOL, rtol=ATOL)


@pytest.mark.parametrize("name", ["gs-max", "gc-min"])
def test_accelerator_lowerings_match_oracle(name):
    """The two lowerings an accelerator takes — pair-flattened element
    gathers for shrunk (row, dim) cells, and the device-side commit-row
    gather that feeds the serving snapshot — steered on the CPU: the
    state must stay oracle-exact and every logged commit must equal the
    committed final-layer rows."""
    wl, g, params, state = _setup(name)
    eng = DeviceEngine(wl, params, g, state, min_bucket=16)
    eng.interpret = False        # what interpret_mode() gives on a TPU
    eng._host_backend = False    # what a device-memory backend gives
    eng.enable_commit_log()
    shrinks = 0
    for b in _stream(g, np.random.default_rng(19), n_batches=8):
        eng.apply_batch(b)
        shrinks += eng.last_shrink_events
        H_last = eng.host_H()[-1]
        for _idx, aff, rows in eng.drain_commits():
            np.testing.assert_array_equal(rows, H_last[aff])
    assert shrinks > 0, "the stream never exercised the shrink pull"
    H_ref = _oracle_H(wl, params, g, eng.host_H()[0])
    for h, href in zip(eng.host_H(), H_ref):
        np.testing.assert_allclose(h, href, atol=ATOL, rtol=ATOL)


# ---------------------------------------------------------------------------
# SHRINK counters, ladder retries by channel, and the in-half mirror's spans
# ---------------------------------------------------------------------------
_SHRINK = ("shrink_events", "rows_reaggregated", "dims_reaggregated",
           "recover_hits")


def _recorded_spans(monkeypatch):
    """The names of the spans the device engine opens, in order."""
    import contextlib

    import repro.core.device_engine as de
    names = []

    def record(name):
        names.append(name)
        return contextlib.nullcontext()
    monkeypatch.setattr(de, "span", record)
    return names


def test_shrink_counters_sum_the_batches_and_count_one_dim(monkeypatch):
    """The cumulative SHRINK counters are the per-batch ``last_*`` values
    summed; a delete of an edge whose source is the witness of exactly one
    dimension of its destination's max re-aggregates that one cell, and
    the in-half mirror's work has spans of its own."""
    spans = _recorded_spans(monkeypatch)
    wl, g, params, state = _setup("gs-max", n_layers=1)
    eng = DeviceEngine(wl, params, g, state, min_bucket=16)
    C = np.asarray(state.C[1])
    src, dst = g.coo()[:2]
    one = [(int(u), int(v)) for u, v in zip(src, dst)
           if np.count_nonzero(C[v] == u) == 1]
    assert one, "no edge witnesses exactly one dimension"
    u, v = one[0]
    eng.apply_batch(UpdateBatch(edges=[EdgeUpdate(u, v, False)]))
    assert eng.last_dims_reaggregated == 1
    assert eng.last_shrink_events == 1 and eng.last_rows_reaggregated == 1
    assert eng.last_recover_hits == 0
    total = {k: getattr(eng, "last_" + k) for k in _SHRINK}
    for b in _stream(g, np.random.default_rng(23)):
        eng.apply_batch(b)
        for k in _SHRINK:
            total[k] += getattr(eng, "last_" + k)
    assert total["dims_reaggregated"] > 1
    assert {k: getattr(eng, k) for k in _SHRINK} == total
    assert {"ripple.mirror.in_refresh", "ripple.mirror.refresh"} <= set(spans)
    H_ref = _oracle_H(wl, params, g, eng.host_H()[0])
    for h, href in zip(eng.host_H(), H_ref):
        np.testing.assert_allclose(h, href, atol=ATOL, rtol=ATOL)


def test_pair_overflow_counts_under_its_channel():
    """A batch whose (row, dim) pairs overflow their cap while every other
    channel fits is one retry, counted once under ``pairs``."""
    wl, g, params, state = _setup("gs-max", n=64, m=700)
    eng = DeviceEngine(wl, params, g, state, min_bucket=16)
    eng.interpret = False        # the pair-flattened lowering of a TPU
    for b in _stream(g, np.random.default_rng(29), n_batches=2):
        eng.apply_batch(b)
    before = eng.retries
    by_channel = dict(eng.retries_by_channel)
    # rows, edges and pulls at their ceilings; pairs at the smallest bucket
    eng._hw = np.array([[1 << 20] * 3 + [0]] * wl.spec.n_layers)
    rng = np.random.default_rng(0)
    eng.apply_batch(UpdateBatch(features=[
        FeatureUpdate(int(v), rng.normal(size=8).astype(np.float32))
        for v in rng.choice(g.n, size=20, replace=False)]))
    assert eng.last_dims_reaggregated > eng.min_bucket
    assert eng.retries == before + 1
    by_channel["pairs"] += 1
    assert eng.retries_by_channel == by_channel
    H_ref = _oracle_H(wl, params, g, eng.host_H()[0])
    for h, href in zip(eng.host_H(), H_ref):
        np.testing.assert_allclose(h, href, atol=ATOL, rtol=ATOL)


def test_invertible_engine_keeps_no_shrink_counts(monkeypatch):
    """A gc-s engine has no in-half mirror: its SHRINK counters stay 0,
    its ladder has two channels, and no ``ripple.mirror.in_*`` span opens."""
    spans = _recorded_spans(monkeypatch)
    wl, g, params, state = _setup("gc-s", n=64, m=700)
    eng = DeviceEngine(wl, params, g, state, min_bucket=4)
    for b in _stream(g, np.random.default_rng(31)):
        eng.apply_batch(b)
    assert eng.in_mirror is None
    assert all(getattr(eng, k) == 0 for k in _SHRINK)
    assert set(eng.retries_by_channel) == {"rows", "edges"}
    assert eng.retries > 0
    assert sum(eng.retries_by_channel.values()) >= eng.retries
    assert "ripple.mirror.refresh" in spans
    assert not [s for s in spans if s.startswith("ripple.mirror.in_")]


@pytest.mark.parametrize("name", ["gs-max", "gc-s"])
def test_settled_caps_move_on_overflow_or_crossing(name):
    """Once the ladder has settled, a monotonic engine keeps its caps for a
    record that still fits them and moves a channel's mark to a batch's
    need only when that batch overflowed it; an invertible engine keeps the
    crossing rule (a record whose headroomed bucket grows takes 2x)."""
    wl, g, params, state = _setup(name, n=64, m=700)
    eng = DeviceEngine(wl, params, g, state, min_bucket=16)
    L, k = wl.spec.n_layers, 4 if name == "gs-max" else 3
    eng._hw = np.full((L, k), 100, dtype=np.int64)
    eng._notes = eng._SETTLE_NOTES + 1
    caps = eng._caps(0)
    assert caps[0][1] == 128         # edges: the bucket over 100 * 1.25
    record = eng._hw.copy()
    record[0, 1] = 110               # past 128 / 1.25, still inside 128
    eng._note_sizes(record)
    if name == "gs-max":
        assert eng._caps(0) == caps
        over = eng._hw.copy()
        over[L - 1, 3] = 140         # past the pair cap of the last hop
        eng._note_sizes(over)
        want = np.full((L, k), 100)
        want[L - 1, 3] = 140
        np.testing.assert_array_equal(eng._hw, want)
        assert eng._caps(0)[L - 1][3] == 256
    else:
        assert eng._hw[0, 1] == 220
        assert eng._caps(0)[0][1] == 512
