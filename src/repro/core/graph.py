"""Dynamic directed graph store for streaming updates.

The paper (RIPPLE §6) uses "lightweight edge list structures designed to
efficiently handle streaming updates" on the host, in contrast to DGL's
heavyweight graph mutation.  We mirror that: a host-side NumPy CSR with
per-row slack capacity, which applies a batch of edge updates with a fixed
number of array operations (and a single edge in O(degree)), plus
mirrored in-adjacency (needed by the layer-wise recompute baseline to pull
*all* in-neighbors) and an incrementally maintained in-degree vector (needed
for exact ``mean`` aggregation under topology change).

Vertex set is fixed (vertex add/delete is future work in the paper, §8).
Edges are unique (u, v) pairs; each carries a float weight (the static
per-edge weight alpha used by the weighted-sum aggregator; 1.0 otherwise).
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Iterable, Sequence

import numpy as np

from repro.utils.trace import span

_GROW = 1.5  # row slack growth factor
_MIN_SLACK = 4
# Batches with fewer edge updates take the single-edge path: below this
# the batch path's fixed cost (~50 array calls) exceeds per-edge Python
# (~20 us an edge); the two cross at ~16 edges on an x86 host CPU.
_BATCH_MIN_EDGES = 16


def edge_columns(edges: Sequence[EdgeUpdate]):
    """``(src, dst, add, weight)`` arrays of a list of edge updates."""
    return (np.array([e.src for e in edges], dtype=np.int64),
            np.array([e.dst for e in edges], dtype=np.int64),
            np.array([e.add for e in edges], dtype=bool),
            np.array([e.weight for e in edges], dtype=np.float32))


def flat_row_indices(starts: np.ndarray, lengths: np.ndarray) -> np.ndarray:
    """Vectorized ragged expansion: for each row i, emit
    ``starts[i] + [0..lengths[i])`` concatenated.  O(total) without a
    Python loop — the hot primitive for frontier edge gathering."""
    csum = np.cumsum(lengths)
    total = int(csum[-1]) if csum.size else 0
    if total == 0:
        return np.empty(0, dtype=np.int64)
    # arange(total) runs on through the rows; shift each row to its start
    return (np.arange(total, dtype=np.int64)
            + np.repeat(starts - (csum - lengths), lengths))


def _ordered_net(idx: Iterable[int], src: Sequence[int], dst: Sequence[int],
                 add: Sequence[bool], held: Sequence[bool]) -> list[int]:
    """Of the edge updates ``idx`` (in batch order; ``src``, ``dst``,
    ``add`` and the store's ``held`` at batch start aligned to it), those
    that change the store, in order, less each add whose edge a later
    update deletes (and that delete)."""
    present: dict[tuple[int, int], bool] = {}
    added_at: dict[tuple[int, int], int] = {}
    net: list[int] = []
    for i, s, t, a, h in zip(idx, src, dst, add, held):
        key = (s, t)
        if a == present.get(key, h):
            continue  # duplicate add or missing delete
        present[key] = a
        if a:
            added_at[key] = len(net)
            net.append(i)
        elif key in added_at:
            net[added_at.pop(key)] = -1
        else:
            net.append(i)
    return [i for i in net if i >= 0]


class _AdjHalf:
    """One direction of adjacency (out- or in-) as slacked CSR.

    Rows are stored in a flat ``col``/``w`` pool; ``start[v]`` and ``length[v]``
    delimit vertex v's row; rows have slack so appends are O(1) amortized.
    """

    def __init__(self, n: int, col: np.ndarray, offsets: np.ndarray, w: np.ndarray):
        self.n = n
        deg = np.diff(offsets).astype(np.int64)
        cap = np.maximum((deg * _GROW).astype(np.int64) + _MIN_SLACK, deg)
        start = np.zeros(n, dtype=np.int64)
        np.cumsum(cap[:-1], out=start[1:])
        pool = int(start[-1] + cap[-1]) if n else 0
        self.col = np.full(pool, -1, dtype=np.int64)
        self.w = np.zeros(pool, dtype=np.float32)
        self.start = start
        self.length = deg.copy()
        self.cap = cap
        self.grows = 0  # pool extensions
        if deg.sum():
            flat = flat_row_indices(start, deg)
            srcidx = flat_row_indices(offsets[:-1], deg)
            self.col[flat] = col[srcidx]
            self.w[flat] = w[srcidx]

    def row(self, v: int) -> tuple[np.ndarray, np.ndarray]:
        s, d = self.start[v], self.length[v]
        return self.col[s : s + d], self.w[s : s + d]

    def append(self, v: int, u: int, weight: float) -> None:
        if self.length[v] == self.cap[v]:
            self._relocate(np.array([v]), np.array([self.length[v] + 1]))
        s = self.start[v] + self.length[v]
        self.col[s] = u
        self.w[s] = weight
        self.length[v] += 1

    def remove(self, v: int, u: int) -> float:
        s, d = self.start[v], self.length[v]
        row = self.col[s : s + d]
        hits = np.nonzero(row == u)[0]
        if hits.size == 0:
            raise KeyError(f"edge endpoint {u} not in row {v}")
        i = int(hits[0])
        weight = float(self.w[s + i])
        # swap-with-last delete
        self.col[s + i] = self.col[s + d - 1]
        self.w[s + i] = self.w[s + d - 1]
        self.col[s + d - 1] = -1
        self.length[v] -= 1
        return weight

    def _relocate(self, rows: np.ndarray, need: np.ndarray) -> None:
        """Move ``rows`` (distinct) to the end of the pool, each with its
        capacity grown by the ``_GROW``/``_MIN_SLACK`` rule until it holds
        ``need`` entries, in one pool extension.  The old slots leak."""
        cap = self.cap[rows].copy()
        while (short := cap < need).any():
            cap[short] = (cap[short] * _GROW).astype(np.int64) + _MIN_SLACK
        end = self.col.shape[0]
        new_start = end + np.cumsum(cap) - cap
        total = int(cap.sum())
        self.col = np.concatenate([self.col, np.full(total, -1, dtype=np.int64)])
        self.w = np.concatenate([self.w, np.zeros(total, dtype=np.float32)])
        deg = self.length[rows]
        old = flat_row_indices(self.start[rows], deg)
        new = flat_row_indices(new_start, deg)
        self.col[new] = self.col[old]
        self.w[new] = self.w[old]
        self.start[rows] = new_start
        self.cap[rows] = cap
        self.grows += 1

    def append_many(self, rows: np.ndarray, cols: np.ndarray,
                    ws: np.ndarray) -> None:
        """Append the entries ``(rows[i], cols[i], ws[i])``, none of which
        the half holds yet, growing every row that overflows at once."""
        order = np.argsort(rows, kind="stable")
        rows = rows[order]
        first = np.flatnonzero(np.r_[True, rows[1:] != rows[:-1]])
        ur = rows[first]
        cnt = np.r_[first[1:], rows.size] - first
        need = self.length[ur] + cnt
        over = need > self.cap[ur]
        if over.any():
            self._relocate(ur[over], need[over])
        pos = flat_row_indices(self.start[ur] + self.length[ur], cnt)
        self.col[pos] = cols[order]
        self.w[pos] = ws[order]
        self.length[ur] = need

    def holds(self, rows: np.ndarray, cols: np.ndarray) -> np.ndarray:
        """Whether each ``(rows[i], cols[i])`` is held, by one scan of the
        rows."""
        deg = self.length[rows]
        flat = flat_row_indices(self.start[rows], deg)
        hit = self.col[flat] == np.repeat(cols, deg)
        out = np.zeros(rows.size, dtype=bool)
        out[np.repeat(np.arange(rows.size), deg)[hit]] = True
        return out

    def remove_many(self, rows: np.ndarray, cols: np.ndarray) -> np.ndarray:
        """Remove the entries ``(rows[i], cols[i])``, distinct and all held,
        and return their weights.  Each touched row is compacted in place,
        keeping its survivors' order, and ``-1`` fills the freed tail."""
        deg = self.length[rows]
        flat = flat_row_indices(self.start[rows], deg)
        hit = flat[self.col[flat] == np.repeat(cols, deg)]
        if hit.size != rows.size:
            raise KeyError("edge endpoint not in its row")
        weights = self.w[hit]
        self.col[hit] = -1
        ur = np.unique(rows)
        start, old = self.start[ur], self.length[ur]
        np.subtract.at(self.length, rows, 1)
        was = flat_row_indices(start, old)
        col, w = self.col[was], self.w[was]
        live = col >= 0
        self.col[was] = -1
        kept = flat_row_indices(start, self.length[ur])
        self.col[kept] = col[live]
        self.w[kept] = w[live]
        return weights

    def to_csr(self) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
        """Compact to (indptr, col, w)."""
        deg = self.length
        indptr = np.zeros(self.n + 1, dtype=np.int64)
        np.cumsum(deg, out=indptr[1:])
        flat = flat_row_indices(self.start, deg)
        return indptr, self.col[flat].copy(), self.w[flat].copy()


@dataclass
class EdgeUpdate:
    """One streaming topology update."""

    src: int
    dst: int
    add: bool  # True = addition, False = deletion
    weight: float = 1.0


@dataclass
class FeatureUpdate:
    """One streaming vertex-feature update."""

    vertex: int
    value: np.ndarray  # new feature vector, shape [d0]


@dataclass
class UpdateBatch:
    """A batch of updates, as routed to the engine by the stream driver."""

    edges: list[EdgeUpdate] = field(default_factory=list)
    features: list[FeatureUpdate] = field(default_factory=list)

    def __len__(self) -> int:
        return len(self.edges) + len(self.features)


class DynamicGraph:
    """Streaming directed graph with batched edge add/delete.

    Maintains out- and in-adjacency (both needed: out- for RIPPLE's
    look-forward propagation, in- for the recompute baseline and for full
    layer-wise inference) and the in-degree vector.
    """

    def __init__(self, n: int, src: np.ndarray, dst: np.ndarray,
                 weight: np.ndarray | None = None):
        src = np.asarray(src, dtype=np.int64)
        dst = np.asarray(dst, dtype=np.int64)
        if weight is None:
            weight = np.ones(src.shape[0], dtype=np.float32)
        weight = np.asarray(weight, dtype=np.float32)
        self.n = n
        # build CSR out (rows keyed by src) and in (rows keyed by dst)
        order = np.argsort(src, kind="stable")
        out_off = np.zeros(n + 1, dtype=np.int64)
        np.cumsum(np.bincount(src, minlength=n), out=out_off[1:])
        self.out = _AdjHalf(n, dst[order], out_off, weight[order])
        order_in = np.argsort(dst, kind="stable")
        in_off = np.zeros(n + 1, dtype=np.int64)
        np.cumsum(np.bincount(dst, minlength=n), out=in_off[1:])
        self.inn = _AdjHalf(n, src[order_in], in_off, weight[order_in])
        self.in_degree = np.bincount(dst, minlength=n).astype(np.float32)
        self.num_edges = int(src.shape[0])
        self.ordered_updates = 0  # batch-path updates whose key repeated

    # -- queries ---------------------------------------------------------
    def has_edge(self, u: int, v: int) -> bool:
        return bool((self.out.row(u)[0] == v).any())

    @property
    def _edge_set(self) -> set[tuple[int, int]]:
        """The held edges as ``(u, v)`` pairs, built from the out-rows: for
        checks, not for the update path."""
        src, dst, _ = self.coo()
        return set(zip(src.tolist(), dst.tolist()))

    def out_nbrs(self, u: int) -> tuple[np.ndarray, np.ndarray]:
        return self.out.row(u)

    def in_nbrs(self, v: int) -> tuple[np.ndarray, np.ndarray]:
        return self.inn.row(v)

    @property
    def pool_grows(self) -> int:
        """Pool extensions of both halves: one per grown row on the
        single-edge path, one per half and batch on the batch path."""
        return self.out.grows + self.inn.grows

    # -- mutation --------------------------------------------------------
    def add_edge(self, u: int, v: int, weight: float = 1.0) -> bool:
        """Returns False (no-op) if the edge already exists."""
        if self.has_edge(u, v):
            return False
        self._insert(u, v, weight)
        return True

    def _insert(self, u: int, v: int, weight: float) -> None:
        self.out.append(u, v, weight)
        self.inn.append(v, u, weight)
        self.in_degree[v] += 1.0
        self.num_edges += 1

    def delete_edge(self, u: int, v: int) -> float | None:
        """Returns the removed edge's weight, or None if absent (no-op)."""
        try:
            weight = self.out.remove(u, v)
        except KeyError:
            return None
        self.inn.remove(v, u)
        self.in_degree[v] -= 1.0
        self.num_edges -= 1
        return weight

    def apply_topology(self, edges: Sequence[EdgeUpdate]) -> tuple[list[EdgeUpdate], list[EdgeUpdate]]:
        """``apply_edges`` over ``EdgeUpdate`` objects; returns
        (effective_adds, effective_deletes): the batch's own add objects,
        and each delete with the weight the store held."""
        a, d, del_w = self._apply(*edge_columns(edges))
        return ([edges[i] for i in a.tolist()],
                [EdgeUpdate(edges[i].src, edges[i].dst, False, w)
                 for i, w in zip(d.tolist(), del_w.tolist())])

    def apply_edges(self, src: np.ndarray, dst: np.ndarray, add: np.ndarray,
                    weight: np.ndarray):
        """Apply a batch of edge updates, given as columns in batch order;
        returns the effective adds ``(src, dst, weight)`` and deletes
        ``(src, dst, weight)`` as arrays, each delete with the weight the
        store held, which the engine needs to retract the old contribution
        exactly.

        No-ops (duplicate adds, missing deletes) are dropped, matching the
        idempotent semantics a production ingest layer provides.  So is an
        edge the batch adds and then deletes again: the engines take a
        batch's adds and deletes as simultaneous, and a max/min aggregate
        would fold in the add while the delete, of an edge it never
        recorded as a contributor, retracts nothing.  What is left keeps
        the batch's order.  Slot order within a row is not kept: nothing
        may rely on it.
        """
        a, d, del_w = self._apply(src, dst, add, weight)
        return ((src[a], dst[a], weight[a]), (src[d], dst[d], del_w))

    def _apply(self, src: np.ndarray, dst: np.ndarray, add: np.ndarray,
               weight: np.ndarray):
        """Apply the batch; return the batch indices of its effective adds
        and deletes, and the deletes' stored weights."""
        with span("ripple.graph.topology"):
            if src.size < _BATCH_MIN_EDGES:
                return self._apply_each(src, dst, add, weight)
            return self._apply_batch(src, dst, add, weight)

    def _apply_each(self, src, dst, add, weight):
        """The single-edge path, for batches too small to pay for the
        batch path's fixed cost."""
        a, d, del_w = [], [], []
        sl, dl = src.tolist(), dst.tolist()
        al, wl = add.tolist(), weight.tolist()
        held = [self.has_edge(u, v) for u, v in zip(sl, dl)]
        for i in _ordered_net(range(src.size), sl, dl, al, held):
            if al[i]:
                self._insert(sl[i], dl[i], wl[i])
                a.append(i)
            else:
                del_w.append(self.delete_edge(sl[i], dl[i]))
                d.append(i)
        return (np.array(a, dtype=np.int64), np.array(d, dtype=np.int64),
                np.array(del_w, dtype=np.float32))

    def _apply_batch(self, src, dst, add, weight):
        """The batch path: a fixed number of array operations per batch.

        A key that occurs once in the batch changes the store iff its add
        flag differs from the store's presence; only updates whose key
        repeats go through the ordered resolution.  Per key, the net is at
        most one delete, of the edge the store held, and one later add, so
        all deletes can go before all adds."""
        held = self.out.holds(src, dst)
        key = src * self.n + dst
        by_key = np.argsort(key)
        ks = key[by_key]
        same = ks[1:] == ks[:-1]
        rep = np.zeros(src.size, dtype=bool)
        rep[by_key[1:][same]] = True
        rep[by_key[:-1][same]] = True
        keep = (add != held) & ~rep
        if rep.any():
            idx = np.flatnonzero(rep)
            self.ordered_updates += idx.size
            net = _ordered_net(idx.tolist(), src[idx].tolist(),
                               dst[idx].tolist(), add[idx].tolist(),
                               held[idx].tolist())
            keep[net] = True
        order = np.flatnonzero(keep)
        is_add = add[order]
        a, d = order[is_add], order[~is_add]
        del_w = np.empty(0, dtype=np.float32)
        if d.size:
            ds, dd = src[d], dst[d]
            del_w = self.out.remove_many(ds, dd)
            self.inn.remove_many(dd, ds)
            np.subtract.at(self.in_degree, dd, np.float32(1))
        if a.size:
            as_, ad, aw = src[a], dst[a], weight[a]
            self.out.append_many(as_, ad, aw)
            self.inn.append_many(ad, as_, aw)
            np.add.at(self.in_degree, ad, np.float32(1))
        self.num_edges += a.size - d.size
        return a, d, del_w

    # -- export ----------------------------------------------------------
    def csr_out(self) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
        return self.out.to_csr()

    def csr_in(self) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
        return self.inn.to_csr()

    def coo(self) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
        """(src, dst, w) with edges grouped by src."""
        indptr, col, w = self.csr_out()
        src = np.repeat(np.arange(self.n, dtype=np.int64), np.diff(indptr))
        return src, col, w


def erdos_renyi(n: int, m: int, seed: int = 0, weighted: bool = False
                ) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Random simple directed graph with ~m edges (host-side generator)."""
    rng = np.random.default_rng(seed)
    # oversample then dedupe to get close to m unique non-self edges
    k = int(m * 1.3) + 16
    src = rng.integers(0, n, size=k)
    dst = rng.integers(0, n, size=k)
    keep = src != dst
    src, dst = src[keep], dst[keep]
    packed = src * n + dst
    _, idx = np.unique(packed, return_index=True)
    idx = np.sort(idx)[:m]
    src, dst = src[idx].astype(np.int64), dst[idx].astype(np.int64)
    if weighted:
        w = rng.uniform(0.1, 1.0, size=src.shape[0]).astype(np.float32)
    else:
        w = np.ones(src.shape[0], dtype=np.float32)
    return src, dst, w


def powerlaw_graph(n: int, m: int, seed: int = 0, exponent: float = 1.2,
                   weighted: bool = False) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Preferential-attachment-ish generator: in-degree follows a power law.

    Mimics the skew of social graphs like Reddit (avg in-degree 492, heavy
    tail) at configurable scale for benchmarks.
    """
    rng = np.random.default_rng(seed)
    ranks = np.arange(1, n + 1, dtype=np.float64)
    p = ranks ** (-exponent)
    p /= p.sum()
    k = int(m * 1.3) + 16
    dst = rng.choice(n, size=k, p=p)
    src = rng.integers(0, n, size=k)
    keep = src != dst
    src, dst = src[keep], dst[keep]
    packed = src * n + dst
    _, idx = np.unique(packed, return_index=True)
    idx = np.sort(idx)[:m]
    src, dst = src[idx].astype(np.int64), dst[idx].astype(np.int64)
    if weighted:
        w = rng.uniform(0.1, 1.0, size=src.shape[0]).astype(np.float32)
    else:
        w = np.ones(src.shape[0], dtype=np.float32)
    return src, dst, w
