"""The update tape and the query schedule of a traffic mix.

A traffic mix is a JSON file of parameters (``bench/traffic/<mix>.json``):

    loop             "closed": submit as fast as admission allows, so the
                     queue stays full (the only loop ``load.py`` drives)
    mix              relative weights of (edge adds, edge deletes,
                     feature updates)
    tenants, tenant_skew
                     tenants and their power-law traffic shares
                     (share of tenant i ~ (i+1)^-skew)
    vertex_skew      feature-update targets ~ rank^-skew within a tenant's
                     vertices (0: uniform, the paper's protocol)
    chunk            updates per submit call
    query_rate_per_s, query_vertices
                     Poisson snapshot queries of that many uniform vertices
    max_batch, capacity, overload
                     the server's micro-batch ceiling, queue bound and
                     overload policy
    readd_after      a tenant's own updates that must pass before an edge
                     it added may be deleted, or one it deleted re-added
    prefill_updates  updates drawn during set-up (more than warm-up and
                     window take), so that drawing them costs the measured
                     window no host time; later ones are drawn on demand,
                     by the submitter, which then holds the interpreter
                     long enough to slow the server by a fifth

The tape keeps the stated mix for as long as a run lasts: edges that the
stream deleted are added back later, so the adds never run out while
deletes run. Each edge and each vertex's features belong to one tenant, and
``readd_after >= max_batch`` keeps two updates of one edge out of one
micro-batch, so the final graph and features do not depend on how the
server interleaves tenants: the tape alone says what they are.
"""
from __future__ import annotations

from collections import deque

import numpy as np

from repro.core.graph import EdgeUpdate, FeatureUpdate

ADD, DELETE, FEATURE = 0, 1, 2
_FEATURE_POOL = 4096   # distinct feature vectors the tape draws from


def tenant_shares(n: int, skew: float) -> np.ndarray:
    w = np.arange(1, n + 1, dtype=np.float64) ** (-float(skew))
    return w / w.sum()


class Tape:
    """Deterministic stream of update chunks drawn from a seed.

    ``next_chunk()`` returns ``(tenant, updates)``; the same seed gives the
    same sequence of chunks. The tape logs every chunk compactly, and from
    that log says what graph and features the chunks taken so far lead to
    (``final_edges``, ``final_features``)."""

    def __init__(self, n: int, snapshot, holdout, x0: np.ndarray,
                 traffic: dict, ss: np.random.SeedSequence):
        self.n = n
        self.traffic = traffic
        self.chunk = int(traffic["chunk"])
        self.readd_after = int(traffic["readd_after"])
        if self.readd_after < int(traffic["max_batch"]):
            raise ValueError("readd_after must be >= max_batch")
        self.x0 = x0
        n_t = int(traffic["tenants"])
        self.names = [f"t{i}" for i in range(n_t)]
        self.shares = tenant_shares(n_t, traffic["tenant_skew"])
        mix = np.asarray(traffic["mix"], dtype=np.float64)
        if mix.min() < 0 or mix.sum() <= 0:
            raise ValueError(f"bad mix {traffic['mix']}")
        self.mix = mix / mix.sum()
        self.rng = np.random.default_rng(ss)
        rng = self.rng
        # ownership: each edge and each vertex's features belong to one
        # tenant, drawn by traffic share, so per-tenant FIFO order fixes
        # the order of every update to one edge or vertex
        s_src, s_dst = snapshot
        h_src, h_dst = holdout
        s_own = rng.choice(n_t, size=s_src.size, p=self.shares)
        h_own = rng.choice(n_t, size=h_src.size, p=self.shares)
        s_key, h_key = s_src * n + s_dst, h_src * n + h_dst
        self.present = [s_key[s_own == t].tolist() for t in range(n_t)]
        self.absent = [h_key[h_own == t].tolist() for t in range(n_t)]
        v_own = rng.choice(n_t, size=n, p=self.shares)
        self.vertices = [np.flatnonzero(v_own == t).tolist()
                         for t in range(n_t)]
        skew = float(traffic.get("vertex_skew", 0.0))
        self.v_cdf = [None if skew == 0 or not v else
                      _cdf(np.arange(1, len(v) + 1.0) ** -skew)
                      for v in self.vertices]
        self.pool = rng.normal(size=(_FEATURE_POOL, x0.shape[1])) \
            .astype(np.float32)
        self.pool_rows = list(self.pool)
        self.quarantine = [deque() for _ in range(n_t)]  # (due, key, added)
        self.count = [0] * n_t          # tenant updates drawn so far
        self.snapshot_keys = np.sort(s_key)
        self.log: list[tuple] = []      # (kinds, a, b) per drawn chunk
        self._ready: deque = deque()    # drawn ahead, not yet taken
        self.taken = 0                  # chunks handed out

    # -- drawing -------------------------------------------------------------
    def prefill(self, updates: int) -> None:
        """Draw chunks for ``updates`` updates ahead, during set-up, so that
        the window spends no time drawing them; later chunks are drawn as
        they are asked for."""
        while len(self._ready) * self.chunk < updates:
            self._ready.append(self._draw())

    def next_chunk(self):
        """The next chunk: ``(tenant name, updates)``."""
        self.taken += 1
        return self._ready.popleft() if self._ready else self._draw()

    def _draw(self):
        rng, n = self.rng, self.n
        t = int(rng.choice(len(self.names), p=self.shares))
        kinds = rng.choice(3, size=self.chunk, p=self.mix)
        picks = rng.random(self.chunk).tolist()
        rows = rng.integers(_FEATURE_POOL, size=self.chunk).tolist()
        q = self.quarantine[t]
        vs, cdf = self.vertices[t], self.v_cdf[t]
        ups: list = []
        a: list = []
        b: list = []
        for kind, r, row in zip(kinds.tolist(), picks, rows):
            self.count[t] += 1
            while q and q[0][0] <= self.count[t]:
                _, key, added = q.popleft()
                (self.present if added else self.absent)[t].append(key)
            if kind == FEATURE:
                v = vs[_pick(r, len(vs), cdf)]
                ups.append(FeatureUpdate(v, self.pool_rows[row]))
                a.append(v)
                b.append(row)
                continue
            pool = (self.absent if kind == ADD else self.present)[t]
            if not pool:
                raise RuntimeError(
                    f"tenant {self.names[t]} has no edge left to "
                    f"{'add' if kind == ADD else 'delete'}; the mix "
                    f"{self.traffic['mix']} outran the graph")
            j = int(r * len(pool))
            key = pool[j]
            pool[j] = pool[-1]
            pool.pop()
            q.append((self.count[t] + self.readd_after, key, kind == ADD))
            u, v = divmod(key, n)
            ups.append(EdgeUpdate(u, v, kind == ADD))
            a.append(u)
            b.append(v)
        self.log.append((kinds, np.array(a, np.int64), np.array(b, np.int64)))
        return self.names[t], ups

    # -- the state the taken chunks lead to ----------------------------------
    def logged(self):
        """(kinds, a, b) of the taken updates in draw order: an edge
        update's (src, dst), a feature update's (vertex, pool row)."""
        log = self.log[:self.taken]
        if not log:
            e = np.empty(0, np.int64)
            return e, e, e
        return tuple(np.concatenate(c) for c in zip(*log))

    def final_edges(self) -> tuple[np.ndarray, np.ndarray]:
        """(src, dst) of the graph after the taken chunks: each edge as its
        last update left it, or as the snapshot had it."""
        kinds, a, b = self.logged()
        edge = kinds != FEATURE
        keys = (a * self.n + b)[edge][::-1]
        last, first = np.unique(keys, return_index=True)
        added = kinds[edge][::-1][first] == ADD
        keys = np.union1d(np.setdiff1d(self.snapshot_keys, last[~added]),
                          last[added])
        return keys // self.n, keys % self.n

    def final_features(self) -> np.ndarray:
        """Features after the taken chunks (last writer wins)."""
        kinds, a, b = self.logged()
        feat = kinds == FEATURE
        v, first = np.unique(a[feat][::-1], return_index=True)
        x = np.array(self.x0, dtype=np.float32, copy=True)
        x[v] = self.pool[b[feat][::-1][first]]
        return x


def _cdf(w):
    c = np.cumsum(w)
    return c / c[-1]


def _pick(r: float, size: int, cdf) -> int:
    if cdf is None:
        return int(r * size)
    return min(int(np.searchsorted(cdf, r)), size - 1)


def poisson_times(rate: float, rng: np.random.Generator, block: int = 4096):
    """Endless Poisson arrival offsets (seconds from 0) at ``rate``/s."""
    t = 0.0
    while True:
        gaps = rng.exponential(1.0 / rate, size=block)
        for g in gaps.tolist():
            t += g
            yield t
