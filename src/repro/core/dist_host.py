"""Host driver for distributed RIPPLE: the paper's leader (§5.2).

Owns partitioning, relabeling, bootstrap scatter, per-batch update routing
(updates go to the owner of the hop-0 vertex; degree changes for cut edges
are the paper's "no-compute" topology sync, realized here as a global
in-degree refresh), buffer packing, and the adaptive capacity ladder.

State contract (what makes ``dist`` a first-class session backend): the
engine is constructed from the normalized ``(workload, params, graph,
state)`` signature — the host ``InferenceState`` is *scattered* onto the
mesh (re-partition + relabel, no recomputation), and ``gather_state``
writes the authoritative mesh state back into the same host arrays in
original vertex-id order, so hot-swapping host<->mesh is exact.

Warm path (the device engine's architecture, ported to the mesh):

 - **State lives on the mesh.**  H/S/C are placed once with their
   propagate shardings and, by default, *donated* through every dispatch;
   the propagate's gated commit returns bit-exact inputs on overflow, so
   the ladder retry simply re-dispatches the returned buffers.
 - **Resident partitioned CSR.**  The stacked ``[P, pool]`` adjacency
   mirror stays on the mesh; per-batch maintenance scatters only the
   touched rows through one packed donated ``shard_map`` (host numpy
   stays authoritative and a full re-upload happens only on ``rebuild``).
 - **Adaptive cap ladder.**  Buffer capacities come from per-channel
   high-water marks (rows/edges/halo/pull/pairs, reported by the
   propagate itself) bucketed to powers of two with headroom — the jit
   cache key stops tracking exact frontier sizes, so steady state runs
   ONE compiled executable; overflow retries jump straight to fitting
   rungs because the size report is valid even on failed attempts.
 - **Async overlap.**  With ``async_dispatch=True``, ``apply_batch``
   routes/packs batch t+1 on the host while the mesh still computes batch
   t; the previous batch is resolved (overflow check + stats) just before
   the next dispatch, and CSR refresh happens between resolve and
   dispatch so donated adjacency buffers are never scattered while a
   propagate that reads them is in flight.

Monotonic workloads (max/min) additionally carry contributor-ref arrays
``C`` on the mesh (relabeled ids; scattered on entry, mapped back to
original ids on gather) and maintain the in-adjacency mirror in every
mode, since shrunk (row, dim) cells re-aggregate via per-dim scalar
request/response pulls — rc mode keeps the row-sized pull-everything
baseline (see distributed.make_monotonic_propagate and
core/aggregators.py).
"""
from __future__ import annotations

import time

import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.utils import next_bucket
from .distributed import (DistBatch, DistCSR, make_monotonic_propagate,
                          make_rc_propagate, make_ripple_propagate,
                          tp_param_specs)
from .graph import (_GROW, _MIN_SLACK, DynamicGraph, EdgeUpdate,
                    UpdateBatch, flat_row_indices)
from .partition import Partitioning, ldg_partition
from .state import InferenceState
from .workloads import Workload

_HEADROOM = 1.25       # cap = next power of two above hw * headroom
_SETTLE_NOTES = 16     # after this many size reports, growth → overshoot


class PartitionedCSR:
    """Stacked ``[P, pool]`` CSR mirror of one adjacency half, maintained
    incrementally across streaming updates and kept *resident on the mesh*.

    Rows are the ``n_local`` vertices of each partition; each row owns a
    slack-padded slot range inside its partition's pool (sentinel col =
    ``n_pad``).  ``refresh_rows`` re-copies only the rows a batch touched
    from the backing ``_AdjHalf`` — on the host (vectorized ragged
    gather/scatter, O(sum of touched row degrees)) and on the mesh via one
    packed donated ``shard_map`` scatter, so the pool is uploaded in full
    exactly once per ``rebuild`` (``uploads`` counts them).  ``rebuild``
    re-lays-out everything with fresh slack and a power-of-two pool
    (stable jit keys) and runs only on row overflow.
    """

    def __init__(self, half, part: Partitioning, mesh=None,
                 data_axes: tuple = ("data",)):
        self.half = half            # the relabeled graph's _AdjHalf
        self.part = part
        self.mesh = mesh
        self.dspec = tuple(data_axes) if len(data_axes) > 1 else data_axes[0]
        self.rebuilds = 0           # counters for the bench / tests
        self.row_refreshes = 0
        self.uploads = 0            # full pool uploads (1 per rebuild)
        self._scatter_cache: dict = {}
        self.rebuild()

    # -- full (re)build: vectorized, no per-partition Python loop ----------
    def rebuild(self) -> None:
        P_, nl = self.part.n_parts, self.part.n_local
        deg = self.half.length.astype(np.int64)            # [n_pad]
        cap = np.maximum((deg * _GROW).astype(np.int64) + _MIN_SLACK, deg)
        cap2d = cap.reshape(P_, nl)
        start2d = np.zeros((P_, nl), dtype=np.int64)
        np.cumsum(cap2d[:, :-1], axis=1, out=start2d[:, 1:])
        pool = next_bucket(int((start2d[:, -1] + cap2d[:, -1]).max()) + 1)
        col = np.full((P_, pool), self.part.n_pad, dtype=np.int32)
        w = np.zeros((P_, pool), dtype=np.float32)
        # flat destination slots across all rows at once
        row_base = np.arange(P_, dtype=np.int64).repeat(nl) * pool \
            + start2d.ravel()
        src_idx = flat_row_indices(self.half.start, deg)
        dst_idx = flat_row_indices(row_base, deg)
        col.ravel()[dst_idx] = self.half.col[src_idx]
        w.ravel()[dst_idx] = self.half.w[src_idx]
        self.pool = pool
        self.col, self.w = col, w
        self.start = start2d.astype(np.int32)
        self.length = deg.reshape(P_, nl).astype(np.int32)
        self.cap = cap2d
        self.rebuilds += 1
        self._scatter_cache.clear()
        self._dev: DistCSR | None = None
        if self.mesh is not None:
            self._upload()

    def _upload(self) -> None:
        sh = NamedSharding(self.mesh, P(self.dspec, None))
        self._dev = DistCSR(col=jax.device_put(self.col, sh),
                            w=jax.device_put(self.w, sh),
                            start=jax.device_put(self.start, sh),
                            length=jax.device_put(self.length, sh))
        self.uploads += 1

    # -- incremental maintenance ------------------------------------------
    def refresh_rows(self, rows: np.ndarray) -> None:
        """Re-copy the given (relabeled global id) rows from the backing
        half — the per-batch path after topology updates mutate the graph.

        The mesh copy is updated via one packed donated scatter (never a
        full re-upload); the caller must not have a propagate in flight
        that reads the donated device buffers."""
        rows = np.asarray(rows, dtype=np.int64)
        if rows.size == 0:
            return
        nl = self.part.n_local
        p, r = rows // nl, rows % nl
        deg = self.half.length[rows]
        if np.any(deg > self.cap[p, r]):
            self.rebuild()          # some row outgrew its slack
            return
        row_base = p * self.pool + self.start[p, r]
        src_idx = flat_row_indices(self.half.start[rows], deg)
        dst_idx = flat_row_indices(row_base, deg)
        self.col.ravel()[dst_idx] = self.half.col[src_idx]
        self.w.ravel()[dst_idx] = self.half.w[src_idx]
        self.length[p, r] = deg
        self.row_refreshes += int(rows.size)
        if self.mesh is None:
            self._dev = None
            return
        # ---- mesh-side packed scatter (donated) --------------------------
        P_ = self.part.n_parts
        slot_part = np.repeat(p, deg).astype(np.int32)
        slot_idx = (dst_idx - slot_part.astype(np.int64)
                    * self.pool).astype(np.int32)
        cv, wv = self.half.col[src_idx], self.half.w[src_idx]
        ub = max(64, next_bucket(max(int(slot_part.size), 1)))
        rb = max(64, next_bucket(int(rows.size)))
        sp = np.full(ub, P_, np.int32)
        si = np.zeros(ub, np.int32)
        cvb = np.full(ub, self.part.n_pad, np.int32)
        wvb = np.zeros(ub, np.float32)
        sp[:slot_part.size] = slot_part
        si[:slot_idx.size] = slot_idx
        cvb[:cv.size] = cv
        wvb[:wv.size] = wv
        rp = np.full(rb, P_, np.int32)
        ri = np.zeros(rb, np.int32)
        rl = np.zeros(rb, np.int32)
        rp[:rows.size] = p
        ri[:rows.size] = r
        rl[:rows.size] = deg
        fn = self._scatter_fn(ub, rb)
        col_d, w_d, len_d = fn(self._dev.col, self._dev.w, self._dev.length,
                               sp, si, cvb, wvb, rp, ri, rl)
        self._dev = DistCSR(col=col_d, w=w_d, start=self._dev.start,
                            length=len_d)

    def _scatter_fn(self, ub: int, rb: int):
        key = (ub, rb, self.pool)
        fn = self._scatter_cache.get(key)
        if fn is not None:
            return fn
        pool, nl, dax = self.pool, self.part.n_local, self.dspec

        def local(col, w, length, sp, si, cv, wv, rp, ri, rl):
            col, w, length = col[0], w[0], length[0]
            me = jax.lax.axis_index(dax)
            tgt = jnp.where(sp == me, si, pool)
            col = col.at[tgt].set(cv, mode="drop")
            w = w.at[tgt].set(wv, mode="drop")
            rt = jnp.where(rp == me, ri, nl)
            length = length.at[rt].set(rl, mode="drop")
            return col[None], w[None], length[None]

        spec = P(self.dspec, None)
        sm = jax.shard_map(local, mesh=self.mesh,
                           in_specs=(spec, spec, spec) + (P(),) * 7,
                           out_specs=(spec, spec, spec),
                           check_vma=False)
        fn = jax.jit(sm, donate_argnums=(0, 1, 2))
        self._scatter_cache[key] = fn
        return fn

    def device(self) -> DistCSR:
        if self._dev is None:       # meshless legacy path
            self._dev = DistCSR(col=jnp.asarray(self.col),
                                w=jnp.asarray(self.w),
                                start=jnp.asarray(self.start),
                                length=jnp.asarray(self.length))
        return self._dev


class DistEngine:
    """Distributed incremental (or recompute-baseline) streaming engine."""

    def __init__(self, workload: Workload, params: list[dict],
                 graph: DynamicGraph, state: InferenceState, mesh, *,
                 mode: str = "ripple", data_axes: tuple = ("data",),
                 seed: int = 0, min_bucket: int = 32, donate: bool = True,
                 async_dispatch: bool = False, warm: bool = True):
        assert mode in ("ripple", "rc")
        self.workload = workload
        self.mesh = mesh
        self.mode = mode
        self.min_bucket = min_bucket
        self.data_axes = tuple(data_axes)
        self.donate = donate
        self._async = async_dispatch
        missing = [a for a in self.data_axes if a not in mesh.shape]
        if missing or "model" not in mesh.shape:
            raise ValueError(f"mesh axes {tuple(mesh.shape)} must include "
                             f"'model' and data axes {self.data_axes}")
        self.n_parts = int(np.prod([mesh.shape[a] for a in self.data_axes]))
        self.M = mesh.shape["model"]
        self._dspec = self.data_axes if len(self.data_axes) > 1 \
            else self.data_axes[0]
        self._sh_data = NamedSharding(mesh, P(self._dspec, None))
        self._sh_model = NamedSharding(mesh, P(self._dspec, None, "model"))

        # the session's graph stays authoritative in ORIGINAL ids; the
        # engine mirrors every effective update into its relabeled copy
        self.host_graph = graph
        src, dst, w = graph.coo()
        self.part = ldg_partition(graph.n, src, dst, self.n_parts, seed=seed)
        self.n_local = self.part.n_local
        n_pad = self.part.n_pad
        # relabeled graph over padded id space (pad vertices are isolated)
        self.g = DynamicGraph(n_pad, self.part.new_of_old[src],
                              self.part.new_of_old[dst], w)
        pspecs = tp_param_specs(workload)
        self.params = [
            {k: jax.device_put(np.asarray(v),
                               NamedSharding(mesh, pspecs[l][k]))
             for k, v in p.items()}
            for l, p in enumerate(params)]
        self.monotonic = not workload.agg.invertible
        # scatter the host state onto the mesh layout — entry migration is
        # a relabel, not a recomputation, so host->mesh swap is exact;
        # every array is placed with its propagate sharding once, then
        # donated through each dispatch (never re-uploaded)
        self.H = tuple(self._scatter(h) for h in state.H)
        self.S = (self._put2(np.zeros(
            (self.n_parts, self.n_local, 1), np.float32)),) \
            + tuple(self._scatter(s) for s in state.S[1:])
        # monotonic workloads: contributor refs ride along, relabeled into
        # the partition-contiguous id space (sentinel -1 preserved)
        self.C = (self._put2(np.zeros(
            (self.n_parts, self.n_local, 1), np.int32)),) \
            + tuple(self._scatter_ids(c) for c in state.C[1:]) \
            if self.monotonic else None
        self.out_csr = PartitionedCSR(self.g.out, self.part, mesh,
                                      self.data_axes)
        # the in-adjacency backs RC's pull-everything re-aggregation AND the
        # monotonic family's shrink re-aggregation requests
        self.in_csr = PartitionedCSR(self.g.inn, self.part, mesh,
                                     self.data_axes) \
            if (mode == "rc" or self.monotonic) else None
        self._d_max = max(int(h.shape[-1]) for h in self.H)

        # warm-path machinery
        self._fn_cache: dict = {}
        self._compiled: set = set()
        self.compiles = 0          # distinct (fn, shapes) executables built
        self.cap_transitions = 0   # dispatches whose caps differ from last
        self.retries = 0           # overflow re-dispatches
        self._last_capsx = None
        self._hw = None            # [L, 5] high-water marks
        self._notes = 0
        self._rung = 0
        self._bucket = min_bucket  # monotonic batch-buffer bucket
        self._pending = None
        self._last_affected = np.empty(0, dtype=np.int64)

        self.last_comm = None  # per-hop exchanged slot counts (paper fig12c)
        self.last_xpod = None  # hierarchical halo [cross_before, cross_after]
        self.last_host_seconds = 0.0   # routing + CSR maintenance per batch
        self.last_shrink_events = 0       # monotonic: SHRINK messages
        self.last_rows_reaggregated = 0   # monotonic: rows re-aggregated
        self.last_dims_reaggregated = 0   # monotonic: (row, dim) cells pulled
        self.last_recover_hits = 0        # monotonic: probe-recovered cells
        if warm:
            self._warm()

    @property
    def ladder_rungs(self) -> int:
        """Distinct cap configurations visited (transitions + the first)."""
        return self.cap_transitions + 1

    # -- layout transforms -------------------------------------------------
    def _put2(self, arr: np.ndarray) -> jax.Array:
        return jax.device_put(arr, self._sh_data)

    def _scatter(self, arr: np.ndarray) -> jax.Array:
        """[n, d] host array in original id order -> [P, n_local, d]."""
        pad = np.zeros((self.part.n_pad, arr.shape[1]), dtype=np.float32)
        pad[self.part.new_of_old] = arr
        return jax.device_put(pad.reshape(self.n_parts, self.n_local, -1),
                              self._sh_model)

    def _scatter_ids(self, arr: np.ndarray) -> jax.Array:
        """Contributor-ref scatter: [n, d] original-id refs -> [P, n_local,
        d] relabeled refs (-1 sentinel preserved, pad rows are -1)."""
        relab = np.where(arr >= 0,
                         self.part.new_of_old[np.maximum(arr, 0)],
                         -1).astype(np.int32)
        pad = np.full((self.part.n_pad, arr.shape[1]), -1, dtype=np.int32)
        pad[self.part.new_of_old] = relab
        return jax.device_put(pad.reshape(self.n_parts, self.n_local, -1),
                              self._sh_model)

    def _gather(self, arr: jax.Array) -> np.ndarray:
        """[P, n_local, d] mesh array -> [n, d] in original id order."""
        flat = np.asarray(arr).reshape(self.part.n_pad, -1)
        return flat[self.part.new_of_old]

    def gather_state(self, state: InferenceState) -> InferenceState:
        """Write the authoritative mesh state back into ``state`` in place
        (original vertex-id order) — the exit half of exact migration."""
        self._resolve()
        for l, h in enumerate(self.H):
            state.H[l][...] = self._gather(h)
        for l in range(1, len(self.S)):
            state.S[l][...] = self._gather(self.S[l])
        if self.monotonic and state.C is not None:
            for l in range(1, len(self.C)):
                relab = self._gather(self.C[l])
                state.C[l][...] = np.where(
                    relab >= 0, self.part.old_of_new[np.maximum(relab, 0)], -1)
        state.k[...] = self.host_graph.in_degree
        return state

    def gather_H(self) -> list[np.ndarray]:
        """Embeddings back in ORIGINAL vertex id order."""
        self._resolve()
        return [self._gather(h) for h in self.H]

    def query(self, vertices: np.ndarray) -> np.ndarray:
        """Final-layer rows for ``vertices`` without a full gather."""
        self._resolve()
        flat = np.asarray(self.H[-1]).reshape(self.part.n_pad, -1)
        return flat[self.part.new_of_old[np.asarray(vertices, np.int64)]]

    # -- routing (host side; does NOT touch device buffers) ----------------
    def _route(self, batch: UpdateBatch):
        """Apply topology to both host graph mirrors and pack padded
        per-partition numpy buffers.  Device-side CSR refresh is deferred
        to the caller (it must not race an in-flight donated propagate).

        Returns ``(np_batch, out_rows, in_rows)`` where the row arrays are
        the relabeled global ids whose CSR rows the batch touched."""
        P_, nl, n_pad = self.n_parts, self.n_local, self.part.n_pad
        relabel = self.part.new_of_old
        adds, dels = self.host_graph.apply_topology(batch.edges)
        r_adds = [(int(relabel[e.src]), int(relabel[e.dst]), e.weight)
                  for e in adds]
        r_dels = [(int(relabel[e.src]), int(relabel[e.dst]), e.weight)
                  for e in dels]
        # the relabeled graph takes the same net updates, deletes first:
        # a batch may delete an edge and add it back
        self.g.apply_topology([EdgeUpdate(s, d, False) for s, d, _ in r_dels]
                              + [EdgeUpdate(s, d, True, wt)
                                 for s, d, wt in r_adds])
        touched = r_adds + r_dels
        out_rows = np.unique([s for s, _, _ in touched]) if touched \
            else np.empty(0, np.int64)
        in_rows = np.unique([d for _, d, _ in touched]) if touched \
            else np.empty(0, np.int64)

        feats: dict[int, list] = {p: [] for p in range(P_)}
        for f in batch.features:
            g_id = int(relabel[f.vertex])
            feats[g_id // nl].append((g_id % nl, f.value))
        radds: dict[int, list] = {p: [] for p in range(P_)}
        for s, d, wt in r_adds:
            radds[s // nl].append((s % nl, d, wt))
        rdels: dict[int, list] = {p: [] for p in range(P_)}
        for s, d, wt in r_dels:
            rdels[s // nl].append((s % nl, d, wt))

        # one monotonically-growing bucket for every batch channel — cap
        # drift never mints a new jit shape once the stream settles
        need = max(max(len(v) for v in feats.values()),
                   max(len(v) for v in radds.values()),
                   max(len(v) for v in rdels.values()), 1)
        b = max(self.min_bucket, next_bucket(need))
        if b > self._bucket:
            self._bucket = b
        capf = cape = self._bucket

        d0 = int(self.H[0].shape[-1])

        def pack_feats():
            idx = np.full((P_, capf), nl, dtype=np.int32)
            val = np.zeros((P_, capf, d0), dtype=np.float32)
            for p, lst in feats.items():
                # last-writer-wins
                seen = {}
                for lid, v in lst:
                    seen[lid] = v
                for i, (lid, v) in enumerate(seen.items()):
                    idx[p, i] = lid
                    val[p, i] = v
            return idx, val

        def pack_edges(d):
            s = np.full((P_, cape), nl, dtype=np.int32)
            t = np.full((P_, cape), n_pad, dtype=np.int32)
            ww = np.zeros((P_, cape), dtype=np.float32)
            for p, lst in d.items():
                for i, (ls, gd, wt) in enumerate(lst):
                    s[p, i], t[p, i], ww[p, i] = ls, gd, wt
            return s, t, ww

        fi, fv = pack_feats()
        a_s, a_d, a_w = pack_edges(radds)
        d_s, d_d, d_w = pack_edges(rdels)
        return (fi, fv, a_s, a_d, a_w, d_s, d_d, d_w), out_rows, in_rows

    def _upload_batch(self, np_b):
        """Place the packed batch + the current in-degree on the mesh."""
        fi, fv, a_s, a_d, a_w, d_s, d_d, d_w = np_b
        put = jax.device_put
        db = DistBatch(
            feat_idx=put(fi, self._sh_data), feat_val=put(fv, self._sh_model),
            add_src=put(a_s, self._sh_data), add_dst=put(a_d, self._sh_data),
            add_w=put(a_w, self._sh_data), del_src=put(d_s, self._sh_data),
            del_dst=put(d_d, self._sh_data), del_w=put(d_w, self._sh_data))
        k = put(self.g.in_degree.reshape(self.n_parts, self.n_local),
                self._sh_data)
        return db, k

    # -- adaptive cap ladder ----------------------------------------------
    def _caps(self, rung: int):
        """Capacity configuration for the given ladder rung: per-layer
        (rows, edges) plus per-layer halo and pull/pair channels.

        High-water driven once the first size report lands; a geometric
        fallback tied to the batch bucket covers the cold start.  Rung r
        scales everything by 4**r (the overflow-escalation safety valve —
        normally retries jump straight to fitting rungs because the size
        report is exact).  Capacities quantize to {2^k, 3*2^(k-1)} rather
        than bare powers of two: padded bucket work is the warm path's
        dominant cost, and the extra rung between doublings shaves up to
        25% of it at the price of a few more possible compiled shapes
        (steady state still settles on exactly one)."""
        L = self.workload.spec.n_layers
        scale = 4 ** rung
        nl_b = next_bucket(self.n_local)
        e_max = max(next_bucket(max(self.g.num_edges, 1)) * 2,
                    self.min_bucket)
        dl = max(1, self._d_max // max(self.M, 1))
        pull_max = e_max * next_bucket(dl)
        pd_max = max(2 * e_max, next_bucket(nl_b * dl))

        def nb(v):
            v = max(int(v), 1)
            b = next_bucket(v)
            t = (b // 4) * 3     # the 3*2^(k-1) point below b
            return max(self.min_bucket, t if t >= v else b)

        if self._hw is None:
            r = nb(self._bucket * 2) * scale
            caps, rr, ee = [], r, 4 * r
            for _ in range(L):
                caps.append((int(min(rr, nl_b)), int(min(ee, e_max))))
                rr, ee = rr * 4, ee * 4
            halo = (int(min(4 * r, 2 * e_max)),) * L
            pull = int(min(8 * r, pull_max))
            pd = int(min(8 * r, pd_max))
            return tuple(caps), halo, pull, pd
        hw = self._hw
        caps, halo = [], []
        for l in range(L):
            caps.append((int(min(nb(hw[l, 0] * _HEADROOM) * scale, nl_b)),
                         int(min(nb(hw[l, 1] * _HEADROOM) * scale, e_max))))
            halo.append(int(min(nb(hw[l, 2] * _HEADROOM) * scale,
                                2 * e_max)))
        pull = int(min(nb(hw[:, 3].max() * _HEADROOM) * scale, pull_max))
        pd = int(min(nb(hw[:, 4].max() * _HEADROOM) * scale, pd_max))
        return tuple(caps), tuple(halo), pull, pd

    def _note_sizes(self, sizes) -> None:
        s = np.asarray(sizes).astype(np.int64)
        if self._hw is None:
            self._hw = s
            self._notes = 1
            return
        grew = s > self._hw
        if self._notes >= _SETTLE_NOTES and grew.any():
            # late growth means the stream drifted past the settled caps —
            # overshoot so the ladder converges in one recompile, not many
            self._hw = np.maximum(self._hw, s * 2)
        else:
            self._hw = np.maximum(self._hw, s)
        self._notes += 1

    # -- dispatch machinery ------------------------------------------------
    def _run(self, db: DistBatch, k, capsx):
        """One propagate attempt at the given capacity configuration."""
        caps, halo, pull, pd = capsx
        kind = "mono" if self.monotonic else self.mode
        key = (kind, caps, halo, pull, pd, self.donate)
        fn = self._fn_cache.get(key)
        if fn is None:
            if self.monotonic:
                fn = make_monotonic_propagate(
                    self.mesh, self.workload, self.n_local, caps, halo, pull,
                    pd, data_axes=self.data_axes, rc=self.mode == "rc",
                    donate=self.donate)
            elif self.mode == "ripple":
                fn = make_ripple_propagate(
                    self.mesh, self.workload, self.n_local, caps, halo,
                    data_axes=self.data_axes, donate=self.donate)
            else:
                fn = make_rc_propagate(
                    self.mesh, self.workload, self.n_local, caps, halo, pull,
                    data_axes=self.data_axes, donate=self.donate)
            self._fn_cache[key] = fn
        ckey = key + (self._bucket, self.out_csr.pool,
                      self.in_csr.pool if self.in_csr is not None else 0)
        if ckey not in self._compiled:
            self._compiled.add(ckey)
            self.compiles = len(self._compiled)
        if self._last_capsx is not None and capsx != self._last_capsx:
            self.cap_transitions += 1
        self._last_capsx = capsx

        out_csr = self.out_csr.device()
        in_csr = self.in_csr.device() if self.in_csr is not None else None
        if self.monotonic:
            H, S, C, final, ovf, comm, sstats, sizes = fn(
                self.params, self.H, self.S, self.C, k, out_csr, in_csr, db)
            return (H, S, C), final, ovf, comm, sizes, sstats, None
        if self.mode == "ripple":
            H, S, final, ovf, comm, sizes, xpod = fn(
                self.params, self.H, self.S, k, out_csr, db)
            return (H, S, None), final, ovf, comm, sizes, None, xpod
        H, S, final, ovf, comm, sizes = fn(
            self.params, self.H, self.S, k, out_csr, in_csr, db)
        return (H, S, None), final, ovf, comm, sizes, None, None

    def _commit_state(self, st) -> None:
        self.H, self.S = st[0], st[1]
        if st[2] is not None:
            self.C = st[2]

    def _dispatch(self, db: DistBatch, k) -> None:
        """Launch one batch without waiting for it.  State is committed
        optimistically — the propagate's gated commit guarantees the
        returned buffers bit-exactly equal the inputs on overflow, so an
        eventual retry in ``_resolve`` starts from the correct state."""
        assert self._pending is None, "dispatch with a batch still pending"
        capsx = self._caps(self._rung)
        st, final, ovf, comm, sizes, sstats, xpod = self._run(db, k, capsx)
        self._commit_state(st)
        self._pending = (ovf, final, comm, sizes, sstats, xpod, db, k, capsx)

    def _resolve(self) -> np.ndarray:
        """Block on the pending batch: check its overflow verdict, walk the
        cap ladder until the retry fits, capture stats, and return the
        affected vertex ids (ORIGINAL order)."""
        if self._pending is None:
            return self._last_affected
        ovf, final, comm, sizes, sstats, xpod, db, k, capsx = self._pending
        while float(ovf) != 0.0:
            self.retries += 1
            # the size report is exact even on overflow: aim the retry
            self._note_sizes(sizes)
            new = self._caps(0)
            if new == capsx:
                self._rung += 1
                new = self._caps(self._rung)
                if new == capsx:
                    self._pending = None
                    raise RuntimeError(
                        "distributed bucket ladder saturated while still "
                        "overflowing — graph inconsistency?")
            else:
                self._rung = 0
            capsx = new
            st, final, ovf, comm, sizes, sstats, xpod = self._run(db, k,
                                                                  capsx)
            self._commit_state(st)
        self._note_sizes(sizes)
        self._rung = 0
        self._pending = None
        self.last_comm = np.asarray(comm)
        if sstats is not None:
            s = np.asarray(sstats)
            self.last_shrink_events = int(s[0])
            self.last_rows_reaggregated = int(s[1])
            self.last_dims_reaggregated = int(s[2])
            self.last_recover_hits = int(s[3])
        if xpod is not None:
            self.last_xpod = np.asarray(xpod)
        f = np.asarray(final).reshape(-1)
        offs = np.repeat(np.arange(self.n_parts) * self.n_local,
                         np.asarray(final).shape[-1])
        f_global = np.where(f < self.n_local, f + offs, -1)
        f_global = f_global[f_global >= 0]
        orig = self.part.old_of_new[f_global]
        self._last_affected = np.unique(orig[orig >= 0])
        return self._last_affected

    def flush(self) -> np.ndarray:
        """Resolve any in-flight batch (async mode); idempotent."""
        return self._resolve()

    def _warm(self) -> None:
        """Precompile the rung-0 executable with a sentinel no-op batch so
        the first real dispatch doesn't pay the shard_map compile."""
        P_, nl, n_pad = self.n_parts, self.n_local, self.part.n_pad
        d0 = int(self.H[0].shape[-1])
        b = self._bucket
        fi = np.full((P_, b), nl, np.int32)
        fv = np.zeros((P_, b, d0), np.float32)
        es = np.full((P_, b), nl, np.int32)
        ed = np.full((P_, b), n_pad, np.int32)
        ew = np.zeros((P_, b), np.float32)
        db, k = self._upload_batch((fi, fv, es, ed, ew, es, ed, ew))
        self._dispatch(db, k)
        self._resolve()
        # the sentinel's zero sizes must not seed the high-water marks
        self._hw = None
        self._notes = 0
        self._rung = 0
        self._last_affected = np.empty(0, dtype=np.int64)

    # -- main entry --------------------------------------------------------
    def apply_batch(self, batch: UpdateBatch) -> np.ndarray:
        """Apply one batch; returns affected vertex ids in ORIGINAL order.

        Synchronous mode blocks on this batch's mesh state.  With
        ``async_dispatch=True`` the call returns after launching this
        batch, reporting the PREVIOUS batch's affected set — host routing
        and packing of batch t+1 overlap the mesh compute of batch t, and
        the pipeline order (route -> resolve prev -> CSR refresh ->
        dispatch) keeps the donated adjacency scatter off the in-flight
        propagate's buffers."""
        t0 = time.perf_counter()
        np_b, out_rows, in_rows = self._route(batch)
        t_route = time.perf_counter() - t0
        prev = self._resolve()
        t1 = time.perf_counter()
        self.out_csr.refresh_rows(out_rows)
        if self.in_csr is not None:
            self.in_csr.refresh_rows(in_rows)
        db, k = self._upload_batch(np_b)
        self.last_host_seconds = t_route + (time.perf_counter() - t1)
        self._dispatch(db, k)
        if self._async:
            return prev
        return self._resolve()
