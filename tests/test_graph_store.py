"""The host graph store's batch path against a per-edge replay.

``DynamicGraph.apply_topology`` / ``apply_edges`` apply a batch with a
fixed number of array operations (above ``_BATCH_MIN_EDGES`` updates) or
edge by edge (below it).  Either way the store must end where a replay of
the batch through ``add_edge``/``delete_edge`` on a copy ends, and return
the same effective adds and deletes, in batch order, with the deletes'
stored weights.  Slot order within a row is free, so rows are compared as
multisets.
"""
import copy

import numpy as np
import pytest

from repro.core.graph import (_BATCH_MIN_EDGES, _GROW, _MIN_SLACK,
                              DynamicGraph, EdgeUpdate, erdos_renyi)

N, M = 40, 240


def _graph(seed: int = 0) -> DynamicGraph:
    src, dst, w = erdos_renyi(N, M, seed=seed, weighted=True)
    return DynamicGraph(N, src, dst, w)


def _present(g, rng, k):
    edges = sorted(g._edge_set)
    pick = rng.choice(len(edges), size=k, replace=False)
    return [edges[i] for i in pick]


def _absent(g, rng, k):
    out = set()
    while len(out) < k:
        u, v = (int(x) for x in rng.integers(0, N, size=2))
        if u != v and not g.has_edge(u, v):
            out.add((u, v))
    return sorted(out)


def _w(rng) -> float:
    return float(rng.uniform(0.1, 1.0))


def _distinct(g, rng, k=40):
    ups = [EdgeUpdate(u, v, True, _w(rng)) for u, v in _absent(g, rng, k // 2)]
    ups += [EdgeUpdate(u, v, False) for u, v in _present(g, rng, k // 2)]
    rng.shuffle(ups)
    return ups


def _duplicate_adds(g, rng):
    new = [EdgeUpdate(u, v, True, _w(rng)) for u, v in _absent(g, rng, 10)]
    held = [EdgeUpdate(u, v, True, _w(rng)) for u, v in _present(g, rng, 6)]
    ups = new + new[:5] + held + _distinct(g, rng, 10)
    rng.shuffle(ups)
    return ups


def _missing_deletes(g, rng):
    ups = [EdgeUpdate(u, v, False) for u, v in _absent(g, rng, 12)]
    ups += _distinct(g, rng, 20)
    rng.shuffle(ups)
    return ups


def _add_then_delete(g, rng):
    pairs = _absent(g, rng, 8)
    ups = _distinct(g, rng, 20)
    for i, (u, v) in enumerate(pairs):
        ups.insert(2 * i, EdgeUpdate(u, v, True, _w(rng)))
        ups.append(EdgeUpdate(u, v, False))
    # and one that comes back after its delete: add, delete, add
    u, v = pairs[0]
    ups.append(EdgeUpdate(u, v, True, _w(rng)))
    return ups


def _delete_then_readd(g, rng):
    pairs = _present(g, rng, 8)
    ups = [EdgeUpdate(u, v, False) for u, v in pairs]
    ups += _distinct(g, rng, 16)
    ups += [EdgeUpdate(u, v, True, _w(rng)) for u, v in pairs]
    # delete, re-add, delete again: only the first delete is left
    u, v = pairs[1]
    ups.append(EdgeUpdate(u, v, False))
    return ups


def _pool_grows(g, rng):
    # one out-row and one in-row each take more adds than their slack holds
    hub_out = [EdgeUpdate(0, v, True, _w(rng)) for v in range(1, N)
               if not g.has_edge(0, v)]
    hub_in = [EdgeUpdate(u, 1, True, _w(rng)) for u in range(2, N)
              if not g.has_edge(u, 1)]
    ups = hub_out + hub_in + _distinct(g, rng, 10)
    rng.shuffle(ups)
    return ups


def _random_repeats(g, rng, k=60):
    keys = _present(g, rng, 6) + _absent(g, rng, 6)
    return [EdgeUpdate(*keys[rng.integers(len(keys))], bool(rng.integers(2)),
                       _w(rng)) for _ in range(k)]


def _below_crossover(g, rng):
    return _random_repeats(g, rng, _BATCH_MIN_EDGES - 1)


def _at_crossover(g, rng):
    return _random_repeats(g, rng, _BATCH_MIN_EDGES)


CASES = {
    "distinct": _distinct,
    "duplicate_adds": _duplicate_adds,
    "missing_deletes": _missing_deletes,
    "add_then_delete": _add_then_delete,
    "delete_then_readd": _delete_then_readd,
    "pool_grows": _pool_grows,
    "random_repeats": _random_repeats,
    "below_crossover": _below_crossover,
    "at_crossover": _at_crossover,
}


def _replay(g: DynamicGraph, edges):
    """Edge by edge through the single-edge API; an add that a later
    update of the batch deletes vanishes with that delete."""
    adds, dels, pending = [], [], {}
    for e in edges:
        key = (e.src, e.dst)
        if e.add:
            if g.add_edge(e.src, e.dst, e.weight):
                pending[key] = len(adds)
                adds.append((e.src, e.dst, np.float32(e.weight)))
        else:
            w = g.delete_edge(e.src, e.dst)
            if w is None:
                continue
            if key in pending:
                adds[pending.pop(key)] = None
            else:
                dels.append((e.src, e.dst, np.float32(w)))
    return [a for a in adds if a is not None], dels


def _rows(half):
    indptr, col, w = half.to_csr()
    row = np.repeat(np.arange(half.n), np.diff(indptr))
    order = np.lexsort((w, col, row))
    return indptr, col[order], w[order]


def _assert_same_store(got: DynamicGraph, want: DynamicGraph):
    for a, b in ((got.out, want.out), (got.inn, want.inn)):
        for x, y in zip(_rows(a), _rows(b)):
            np.testing.assert_array_equal(x, y)
    np.testing.assert_array_equal(got.in_degree, want.in_degree)
    assert got._edge_set == want._edge_set
    assert got.num_edges == want.num_edges == len(want._edge_set)


def _apply(g, edges, api):
    if api == "apply_topology":
        adds, dels = g.apply_topology(edges)
        return ([(e.src, e.dst, np.float32(e.weight)) for e in adds],
                [(e.src, e.dst, np.float32(e.weight)) for e in dels])
    cols = (np.array([e.src for e in edges], dtype=np.int64),
            np.array([e.dst for e in edges], dtype=np.int64),
            np.array([e.add for e in edges], dtype=bool),
            np.array([e.weight for e in edges], dtype=np.float32))
    (a_src, a_dst, a_w), (d_src, d_dst, d_w) = g.apply_edges(*cols)
    assert a_w.dtype == d_w.dtype == np.float32
    return (list(zip(a_src.tolist(), a_dst.tolist(), a_w)),
            list(zip(d_src.tolist(), d_dst.tolist(), d_w)))


@pytest.mark.parametrize("api", ["apply_topology", "apply_edges"])
@pytest.mark.parametrize("case", sorted(CASES))
def test_batch_matches_per_edge_replay(case, api):
    rng = np.random.default_rng(sorted(CASES).index(case))
    g = _graph(seed=1)
    for _ in range(3):  # successive batches: rows already moved or grown
        edges = CASES[case](g, rng)
        want = copy.deepcopy(g)
        want_adds, want_dels = _replay(want, edges)
        got_adds, got_dels = _apply(g, edges, api)
        assert got_adds == want_adds
        assert got_dels == want_dels
        _assert_same_store(g, want)


def test_counters_engage_only_when_needed():
    rng = np.random.default_rng(7)
    g = _graph(seed=2)
    g.apply_topology(_distinct(g, rng, 40))
    assert (g.ordered_updates, g.pool_grows) == (0, 0)

    # three keys each twice, among distinct ones: six ordered updates
    batch = _distinct(g, rng, 20)
    taken = {(e.src, e.dst) for e in batch}
    keys = [k for k in _absent(g, rng, 6) if k not in taken][:3]
    batch += [EdgeUpdate(u, v, True) for u, v in keys]
    batch += [EdgeUpdate(u, v, False) for u, v in keys]
    g.apply_topology(batch)
    assert (g.ordered_updates, g.pool_grows) == (6, 0)

    # one out-row past its cap, with deletes elsewhere to fill the batch:
    # one extension of the out pool
    u = 3
    free = int(g.out.cap[u] - g.out.length[u])
    over = [EdgeUpdate(u, v, True) for v in range(N)
            if v != u and not g.has_edge(u, v)][:free + 1]
    assert len(over) == free + 1
    over += [EdgeUpdate(s, t, False) for s, t in sorted(g._edge_set)
             if s != u][:_BATCH_MIN_EDGES]
    cap = int(g.out.cap[u])
    g.apply_topology(over)
    assert (g.ordered_updates, g.pool_grows) == (6, 1)
    assert g.out.cap[u] == int(cap * _GROW) + _MIN_SLACK  # the row's rule

    # an out-row and an in-row past their caps: one extension per half
    u, v = 5, 6
    over = [EdgeUpdate(u, x, True) for x in range(N)
            if x != u and not g.has_edge(u, x)]
    over += [EdgeUpdate(x, v, True) for x in range(N)
             if x not in (u, v) and not g.has_edge(x, v)]
    assert g.out.length[u] + N - 2 > g.out.cap[u]
    g.apply_topology(over)
    assert (g.ordered_updates, g.pool_grows) == (6, 3)

    # below the crossover each grown row is its own extension, and no
    # update goes through the batch path's ordered resolution
    h = _graph(seed=3)
    u = int(np.argmin(h.out.length))
    free = int(h.out.cap[u] - h.out.length[u])
    assert free + 2 < _BATCH_MIN_EDGES
    few = [EdgeUpdate(u, x, True) for x in range(N)
           if x != u and not h.has_edge(u, x)][:free + 1]
    h.apply_topology(few + [few[0]])
    assert (h.ordered_updates, h.pool_grows) == (0, 1)
