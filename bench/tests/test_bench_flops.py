"""The FLOP counter against a hand count, and the peak table."""
import numpy as np
import pytest

from rbench import flops
from rbench.peaks import peak

from repro.core.graph import EdgeUpdate, FeatureUpdate, UpdateBatch

# 6 vertices; the first batch updates vertex 0's features and adds 2->5
EDGES = [(0, 1), (0, 2), (1, 3), (2, 3), (3, 4), (4, 5), (5, 0), (2, 5)]
DIMS = (4, 3, 2)


def _graph(edges):
    src, dst = (np.array(c) for c in zip(*edges))
    return flops.Graph(6, src, dst)


def _batch(*ups):
    return UpdateBatch([u for u in ups if isinstance(u, EdgeUpdate)],
                       [u for u in ups if isinstance(u, FeatureUpdate)])


def _feature(v):
    return FeatureUpdate(v, np.zeros(4, np.float32))


@pytest.mark.parametrize("family,expected", [
    # gc, layer 1: rows {1,2,5} x 2*4*3 = 72, messages 2*4*(1 edge + 2
    # out-edges of 0) = 24; layer 2: rows {0,3,5} x 2*3*2 = 36, messages
    # 2*3*(1 + 4 out-edges of {1,2,5}) = 30
    ("gc", 72 + 24 + 36 + 30),
    # sage (two weight matrices, reads its own row): layer 1 rows
    # {0,1,2,5} x 2 x 24 = 192 + 24; layer 2 rows {0,1,2,3,5} x 2 x 12 =
    # 120, messages 2*3*(1 + 6 out-edges of {0,1,2,5}) = 42
    ("sage", 192 + 24 + 120 + 42),
])
def test_batch_flops_hand_count(family, expected):
    graph = _graph(EDGES[:-1])
    batch = flops.batch_arrays(_batch(_feature(0), EdgeUpdate(2, 5, True)))
    got = flops.batch_flops(graph, *batch, family=family, dims=DIMS)
    assert got == expected


def test_window_flops_walks_the_graph():
    """Each batch is counted against the graph the batches before it left:
    the add of 2->5 before the window, and a delete of it in it."""
    # gc, feature 2 after the add: layer 1 rows out(2) = {3,5} x 24 = 48,
    # messages 2*4*2 = 16; layer 2 rows out({3,5}) = {4,0} x 12 = 24,
    # messages 2*3*2 = 12
    added = [_batch(EdgeUpdate(2, 5, True)), _batch(_feature(2))]
    assert flops.window_flops(_graph(EDGES[:-1]), added, 1, 2, family="gc",
                              dims=DIMS) == 48 + 16 + 24 + 12
    # feature 2 with 2->5 deleted: layer 1 rows {5} | out(2) = {3,5} x 24
    # = 48, messages 2*4*(1 + 1) = 16; layer 2 rows {5} | out({3,5}) =
    # {0,4,5} x 12 = 36, messages 2*3*(1 + 2) = 18
    deleted = [_batch(_feature(1)),
               _batch(EdgeUpdate(2, 5, False), _feature(2))]
    assert flops.window_flops(_graph(EDGES), deleted, 1, 2, family="gc",
                              dims=DIMS) == 48 + 16 + 36 + 18


def test_peak_table_refuses_unknown_chip():
    assert peak("TPU v5 lite", "bf16_flops") == 197e12
    with pytest.raises(KeyError):
        peak("TPU v9 imaginary", "bf16_flops")
