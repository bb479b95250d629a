"""Every cell of ``BENCHMARK.json`` runs whole on the CPU at a small size:
its own workload, traffic mix and limits on a graph of 3,000 vertices,
with the micro-batches and queue of the checks' small cells."""
import time

import pytest

from rbench import harness

from test_bench_check import TEST_TRAFFIC, _own_jax_config  # noqa: F401
from test_bench_spec import CELLS


@pytest.mark.parametrize("name", CELLS)
def test_cell_runs_correct_at_a_small_size(name):
    cell = harness.load_cell(name)
    cell.config = dict(cell.config, n=3000, m=20000)
    cell.traffic = dict(cell.traffic, **TEST_TRAFFIC)
    held = []
    out = harness.run(cell, seed=2**31 + 17, seconds=0.5, trace=False,
                      t_start=time.perf_counter(),
                      hooks=lambda session, server: held.append(
                          session.engine.impl),
                      warm=harness.WarmPolicy(0.2, 0.3, 3, 60.0))
    assert out["correct"] is True, out["checks"]
    assert out["failed"] == 0
    engine, = held
    if cell.config["workload"] == "gs-max":
        # the served run re-aggregated shrunk (row, dim) cells
        assert engine.dims_reaggregated > 0
    else:
        assert engine.dims_reaggregated == 0
