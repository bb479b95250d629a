"""The correctness check fails what it must: the control (the reference in
bfloat16 passes) and a timed path broken underneath."""
import json
import os
import time

import jax
import pytest

from rbench import harness

from repro.core.graph import UpdateBatch

# the cell's configuration, and the same with the GraphSAGE + max layers
# that the reference also computes
WORKLOADS = ("gc-s", "gs-max")


@pytest.fixture(autouse=True)
def _own_jax_config(monkeypatch, tmp_path):
    """A run sets JAX's compile cache up; keep it off this worker's other
    tests (the cache directory named in the environment is only read)."""
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    key = "jax_persistent_cache_min_compile_time_secs"
    old = getattr(jax.config, key)
    yield
    jax.config.update(key, old)


def _small(workload, n, m):
    """A saturating cell of ``arxiv-gcs`` at its published widths and
    limits, with the layers of ``workload``, on a smaller graph."""
    with open(os.path.join(harness.BENCH_DIR, "configs",
                           "arxiv-gcs.json")) as f:
        config = json.load(f)
    with open(os.path.join(harness.BENCH_DIR, "traffic", "sat.json")) as f:
        traffic = json.load(f)
    return harness.Cell(name=workload + ".test",
                        config=dict(config, workload=workload, n=n, m=m),
                        traffic=traffic, chips=1, end_to_end=[],
                        per_layer=[])


TEST_TRAFFIC = dict(chunk=5, max_batch=40, readd_after=40, capacity=160,
                    query_rate_per_s=20.0, prefill_updates=500)


@pytest.mark.parametrize("name", WORKLOADS)
def test_control_fails_the_limits(name):
    """A whole run with the reference in three bfloat16 passes put in the
    program's place for the check (as ``control.py`` runs it on the chip)
    comes out not correct, at the configuration's widths and limits; the
    same run's program readings are within them."""
    cell = _small(name, 3000, 20000)
    cell.traffic = dict(cell.traffic, **TEST_TRAFFIC)
    out = harness.run(cell, seed=2**31 + 11, seconds=0.5, trace=False,
                      t_start=time.perf_counter(), control="bf16x3",
                      warm=harness.WarmPolicy(0.2, 0.3, 3, 60.0))
    assert out["correct"] is False, out["checks"]
    assert any(c["value"] > c["limit"] for c in out["checks"].values())
    limits = cell.config["limits"]
    assert all(v <= limits[k] for k, v in out["program"].items()), out


def _unchanged(session, server):
    apply = session.apply_one
    session.apply_one = lambda batch: apply(UpdateBatch())


def _half(session, server):
    apply = session.apply_one

    def half(batch):
        return apply(UpdateBatch(batch.edges[:len(batch.edges) // 2],
                                 batch.features[:len(batch.features) // 2]))
    session.apply_one = half


def _altered(session, server):
    drain = session.engine.drain_commits

    def altered():
        out = drain()
        for i, (idx, aff, rows) in enumerate(out):
            if rows.size:
                rows = rows.copy()
                rows[0, 0] += 1.0
                out[i] = (idx, aff, rows)
        return out
    session.engine.drain_commits = altered


@pytest.mark.parametrize("name", WORKLOADS)
@pytest.mark.parametrize("fault", [None, _unchanged, _half, _altered],
                         ids=["sound", "state-unchanged", "half-batch",
                              "answer-altered"])
def test_broken_timed_path_is_not_correct(name, fault):
    cell = _small(name, 400, 4000)
    cell.config.update(d_in=16, d_hidden=16, n_classes=8, holdout_frac=0.5)
    cell.traffic = dict(cell.traffic, **TEST_TRAFFIC)
    out = harness.run(cell, seed=2**31 + 3, seconds=0.5, trace=False,
                      t_start=time.perf_counter(), hooks=fault,
                      warm=harness.WarmPolicy(0.2, 0.3, 3, 60.0))
    assert out["correct"] is (fault is None), out["checks"]
