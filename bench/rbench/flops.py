"""Operations a micro-batch requires, counted from its topological reach.

For the configuration's layer equations (``reference.py``): an update
batch changes the layer-0 rows F (its feature updates) and the edges D (its
adds and deletes). At layer l the aggregate can change in

    A_l = dst(D) | out(C_{l-1})          with C_0 = F,

the rows recomputed are R_l = A_l, plus C_{l-1} where the layer reads its
own previous embedding (GraphSAGE), and C_l = R_l. The count is

    |R_l| * (2 * d_{l-1} * d_l per weight matrix of the layer)
    + 2 * d_{l-1} * (|D| + sum of out-degrees over C_{l-1})

that is, the update products of every recomputed row plus one
multiply-add per element of every changed message. ``out`` is taken on
the graph as the batch leaves it. The batches are the ones the session
applied, in the order it applied them (the harness records each), and
the graph is walked from the bootstrap snapshot through every one of
them, so each batch is counted against the graph it met. The count
depends on the graph and the batches alone, not on the engine's caps,
padding, retries or kernels, so it reads the same work whatever
implements the step.
"""
from __future__ import annotations

import numpy as np

from .traffic import ADD, DELETE, FEATURE

WEIGHTS = {"gc": 1, "sage": 2}
SELF_DEPENDENT = {"gc": False, "sage": True}


def batch_arrays(batch):
    """(kinds, a, b) of an ``UpdateBatch``: an edge update's (src, dst), a
    feature update's (vertex, -1)."""
    e, f = batch.edges, batch.features
    kinds = np.array([ADD if u.add else DELETE for u in e]
                     + [FEATURE] * len(f), np.int64)
    a = np.array([u.src for u in e] + [u.vertex for u in f], np.int64)
    b = np.array([u.dst for u in e] + [-1] * len(f), np.int64)
    return kinds, a, b


class Graph:
    """Out-adjacency of a base edge list (a CSR) under later adds and
    deletes (kept beside it)."""

    def __init__(self, n: int, src: np.ndarray, dst: np.ndarray):
        self.n = n
        order = np.argsort(src, kind="stable")
        self.indptr = np.zeros(n + 1, np.int64)
        np.cumsum(np.bincount(src, minlength=n), out=self.indptr[1:])
        self.idx = np.asarray(dst, np.int64)[order]
        self.base = np.sort(np.asarray(src, np.int64) * n + dst)
        self.removed: set[int] = set()          # base edges now absent
        self.added: dict[int, set[int]] = {}    # src -> dsts not in base
        self._removed = np.empty(0, np.int64)

    def apply(self, kinds, a, b) -> None:
        edge = kinds != FEATURE
        keys = a[edge] * self.n + b[edge]
        pos = np.searchsorted(self.base, keys)
        in_base = (pos < self.base.size) & \
            (self.base[np.minimum(pos, self.base.size - 1)] == keys)
        for key, u, v, add, base in zip(keys.tolist(), a[edge].tolist(),
                                        b[edge].tolist(),
                                        (kinds[edge] == ADD).tolist(),
                                        in_base.tolist()):
            if base:
                (self.removed.discard if add else self.removed.add)(key)
            elif add:
                self.added.setdefault(u, set()).add(v)
            else:
                self.added[u].discard(v)
        self._removed = np.fromiter(self.removed, np.int64,
                                    len(self.removed))

    def out(self, rows: np.ndarray) -> tuple[np.ndarray, int]:
        """(unique out-neighbours of ``rows``, number of out-edges)."""
        starts, ends = self.indptr[rows], self.indptr[rows + 1]
        lens = ends - starts
        offs = np.arange(int(lens.sum())) - np.repeat(np.cumsum(lens) - lens,
                                                      lens)
        nbrs = self.idx[np.repeat(starts, lens) + offs]
        if self._removed.size:
            keep = ~np.isin(np.repeat(rows, lens) * self.n + nbrs,
                            self._removed)
            nbrs = nbrs[keep]
        extra = [v for u in rows.tolist() if u in self.added
                 for v in self.added[u]]
        nbrs = np.concatenate([nbrs, np.array(extra, np.int64)])
        return np.unique(nbrs), int(nbrs.size)


def batch_flops(graph: Graph, kinds, a, b, *, family: str, dims) -> float:
    """Operations one batch requires; applies the batch to ``graph``."""
    graph.apply(kinds, a, b)
    feat = kinds == FEATURE
    changed = np.unique(a[feat])
    edges = ~feat
    n_edges = int(edges.sum())
    dsts = np.unique(b[edges])
    total = 0.0
    for l in range(1, len(dims)):
        reach, n_msgs = graph.out(changed)
        rows = np.union1d(dsts, reach)
        if SELF_DEPENDENT[family]:
            rows = np.union1d(rows, changed)
        d_in, d_out = dims[l - 1], dims[l]
        total += rows.size * WEIGHTS[family] * 2.0 * d_in * d_out
        total += 2.0 * d_in * (n_edges + n_msgs)
        changed = rows
    return total


def window_flops(graph: Graph, batches, start: int, stop: int, *,
                 family: str, dims) -> float:
    """Operations of ``batches[start:stop]``, walking ``graph`` (the
    bootstrap snapshot) through every batch before them first."""
    total = 0.0
    for i, batch in enumerate(batches[:stop]):
        arrays = batch_arrays(batch)
        if i < start:
            graph.apply(*arrays)
        else:
            total += batch_flops(graph, *arrays, family=family, dims=dims)
    return total
