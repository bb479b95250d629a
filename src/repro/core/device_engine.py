"""TPU-native fully-jitted RIPPLE propagation (single replica).

The host engine (engine.py) drives NumPy; this module is the hardware
adaptation (DESIGN.md §2): the entire L-hop propagation of one update batch
is ONE jitted function with *static bucket capacities*, so XLA compiles a
fixed dataflow while the work stays proportional to the frontier size
(the paper's k'-incrementality), not to |V| or |E|:

 - the frontier is a padded index vector (sentinel = n) + aligned deltas;
 - frontier out-edges are expanded with a vectorized ragged gather
   (cumsum + searchsorted) into an edge bucket of static size E_cap;
 - mailboxes are *compacted*: messages are sorted by destination and
   segment-summed into R_cap rows — no dense [n, d] buffer is ever built,
   which keeps per-hop HBM traffic O(frontier), not O(n);
 - self-dependent workloads (SAGE/GIN) inject zero-valued messages from the
   frontier to itself so "recipients" uniformly equals "affected".

Device residency (the per-batch cost contract): the adjacency lives in a
persistent :class:`DeviceCSRMirror` (slack-pool CSR maintained by
touched-row scatters, full re-upload only on slack overflow), the
``DeviceState`` buffers are *donated* through the jitted propagation so XLA
updates H/S/C in place instead of copying every layer, and the in-degree
vector ``k`` is maintained on device from each batch's add/delete counts —
so per-batch host→device traffic and HBM writes are O(frontier), never
O(|E|) or O(|V|·d·L).

To keep the commits-nothing-on-overflow contract *with* donation, the
propagation is two-phase: phase 1 computes every hop's compact row patches
(reads only — later hops read earlier hops' values through a patch-gather,
never through a scatter), accumulating the exact overflow flag; phase 2
scatters all patches with indices gated on the flag (an overflowing attempt
drops every write, so the returned — possibly aliased — buffers hold the
pre-batch values bit-exactly and the ladder can retry).

Monotonic workloads (max/min) run through ``propagate_monotonic`` instead:
candidate extrema compact into per-row segment-max mailboxes, SHRINK cells
(tracked contributor lost, classified per ``(row, dim)``) first face the
re-cover probe — a candidate that ties-or-beats the lost extremum
re-witnesses the dim with no pull at all — and the survivors gather
single columns of the mirrored in-CSR's neighborhoods as pair-flattened
element reads; the next frontier keeps only rows whose embedding actually
changed (filtered propagation) — see core/aggregators.py for the algebra.
With ``pallas=True`` the hop apply runs through the fused Pallas kernels
(kernels/delta_apply, kernels/extremum_apply, kernels/mlp_apply for GIN's
two-matmul MLP) — interpret mode off-TPU, real kernels on TPU — with the
jnp path kept as the oracle.
"""
from __future__ import annotations

from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import interpret_mode
from repro.utils.trace import span

from .aggregators import (MAX, certified_error_bound, deferral_budgets,
                          jnp_segment_extremum)
from .graph import (_GROW, _MIN_SLACK, DynamicGraph, edge_columns,
                    flat_row_indices)
from .workloads import Workload, matmul_f32


class DeviceCSR(NamedTuple):
    """One adjacency half mirrored on device (slacked-CSR pool layout)."""

    col: jax.Array    # [pool] int32, -1 in slack slots
    w: jax.Array      # [pool] f32
    start: jax.Array  # [n] int32
    length: jax.Array  # [n] int32

    @classmethod
    def from_half(cls, half) -> "DeviceCSR":
        return cls(col=jnp.asarray(half.col, dtype=jnp.int32),
                   w=jnp.asarray(half.w),
                   start=jnp.asarray(half.start, dtype=jnp.int32),
                   length=jnp.asarray(half.length, dtype=jnp.int32))

    @classmethod
    def from_graph(cls, g: DynamicGraph) -> "DeviceCSR":
        return cls.from_half(g.out)


@partial(jax.jit, donate_argnames=("col", "w", "length"),
         static_argnames=("kb",))
def _mirror_scatter(col, w, length, ints, slot_w, *, kb: int):
    """Touched-row refresh on device: scatter the rows' fresh contents into
    the persistent pool (out-of-range pad indices drop).  ``ints`` packs
    [slot_idx | slot_col | row_idx | row_len] into one upload (``kb`` slot
    entries, the rest split evenly between row ids and lengths)."""
    slot_idx, slot_col = ints[:kb], ints[kb:2 * kb]
    row_idx, row_len = jnp.split(ints[2 * kb:], 2)
    col = col.at[slot_idx].set(slot_col, mode="drop")
    w = w.at[slot_idx].set(slot_w, mode="drop")
    length = length.at[row_idx].set(row_len, mode="drop")
    return col, w, length


class ShapeMisses(dict):
    """First sightings of a static jit key, counted by call site.

    Each is a trace plus a compile (or compile-cache load) at that site:
    ``propagate`` keys on the caps, the batch width and the mirror pools,
    ``mirror_scatter`` on ``(pool, kb, rb)`` and ``commit_gather`` on the
    padded index size. The check is one set lookup per call."""

    def __init__(self):
        super().__init__(propagate=0, mirror_scatter=0, commit_gather=0)
        self._seen: set = set()

    def note(self, site: str, key) -> None:
        if (site, key) not in self._seen:
            self._seen.add((site, key))
            self[site] += 1


class DeviceCSRMirror:
    """Persistent device-resident slack-pool CSR of one adjacency half.

    The device_engine sibling of dist's ``PartitionedCSR``: rows own
    slack-padded slot ranges in a flat pool (power-of-two total size for
    stable jit keys).  ``refresh_rows`` re-copies only the rows a batch
    touched — a vectorized ragged gather on the host half followed by one
    donated device scatter, O(sum of touched row degrees) host→device
    traffic.  A full pool upload happens exactly once at construction and
    again only when a row outgrows its slack (``rebuilds``); the counters
    let tests assert the no-O(E)-per-batch contract. ``shapes`` receives
    each refresh's scatter key (shared by the mirrors of one engine, as
    the jit cache is). Its work is timed under the spans
    ``<span_prefix>refresh`` and ``<span_prefix>rebuild``.
    """

    def __init__(self, half, *, min_pool: int = 1024,
                 shapes: ShapeMisses | None = None,
                 span_prefix: str = "ripple.mirror."):
        from repro.utils import next_bucket
        self._next_bucket = next_bucket
        self.half = half            # backing host _AdjHalf (authoritative)
        self.min_pool = min_pool
        self.shapes = ShapeMisses() if shapes is None else shapes
        self._span_refresh = span_prefix + "refresh"
        self._span_rebuild = span_prefix + "rebuild"
        self.uploads = 0            # full-pool uploads (init + rebuilds)
        self.rebuilds = -1          # slack-overflow re-layouts
        self.row_refreshes = 0      # rows refreshed incrementally
        self._rebuild()

    def _rebuild(self) -> None:
        with span(self._span_rebuild):
            n = self.half.n
            deg = self.half.length.astype(np.int64)
            cap = np.maximum((deg * _GROW).astype(np.int64) + _MIN_SLACK,
                             deg)
            start = np.zeros(n, dtype=np.int64)
            if n:
                np.cumsum(cap[:-1], out=start[1:])
            pool = self._next_bucket(int(start[-1] + cap[-1]) if n else 1,
                                     minimum=self.min_pool)
            col = np.full(pool, -1, dtype=np.int32)
            w = np.zeros(pool, dtype=np.float32)
            if deg.sum():
                src_idx = flat_row_indices(self.half.start, deg)
                dst_idx = flat_row_indices(start, deg)
                col[dst_idx] = self.half.col[src_idx]
                w[dst_idx] = self.half.w[src_idx]
            self._start_h, self._cap_h = start, cap
            self.pool = pool
            self.col = jnp.asarray(col)
            self.w = jnp.asarray(w)
            self.start = jnp.asarray(start, dtype=jnp.int32)
            self.length = jnp.asarray(deg, dtype=jnp.int32)
        self.uploads += 1
        self.rebuilds += 1

    def refresh_rows(self, rows: np.ndarray) -> None:
        """Re-copy the given rows from the backing host half (the per-batch
        maintenance path after topology updates mutate the graph)."""
        from repro.utils import pad_to
        rows = np.asarray(rows, dtype=np.int64)
        if rows.size == 0:
            return
        with span(self._span_refresh):
            deg = self.half.length[rows]
            if np.any(deg > self._cap_h[rows]):
                self._rebuild()         # some row outgrew its slack
                return
            src_idx = flat_row_indices(self.half.start[rows], deg)
            dst_idx = flat_row_indices(self._start_h[rows], deg)
            kb = self._next_bucket(max(int(dst_idx.size), 1), minimum=64)
            rb = self._next_bucket(int(rows.size), minimum=64)
            n = self.half.n
            ints = np.concatenate([
                pad_to(dst_idx, kb, fill=self.pool),
                pad_to(self.half.col[src_idx], kb),
                pad_to(rows, rb, fill=n),
                pad_to(deg, rb)]).astype(np.int32)
            self.shapes.note("mirror_scatter", (self.pool, kb, rb))
            self.col, self.w, self.length = _mirror_scatter(
                self.col, self.w, self.length, jnp.asarray(ints),
                jnp.asarray(pad_to(self.half.w[src_idx], kb)), kb=kb)
        self.row_refreshes += int(rows.size)

    def device(self) -> DeviceCSR:
        return DeviceCSR(col=self.col, w=self.w, start=self.start,
                         length=self.length)


class DeviceState(NamedTuple):
    H: tuple[jax.Array, ...]  # [n, d_l] per layer 0..L
    S: tuple[jax.Array, ...]  # [n, d_{l-1}] per layer 1..L ([0] placeholder)
    k: jax.Array              # [n] in-degree (maintained on device)
    C: tuple[jax.Array, ...] = ()  # monotonic contributor refs (int32,
    #                                index-aligned with S; () if invertible)
    A: tuple = ()             # bounded cached partial state: per layer a
    #                           tuple of arrays in agg.aux_names order
    #                           (A[0] = () placeholder; () otherwise)


class BatchDev(NamedTuple):
    """A routed update batch in padded device form (sentinel index = n).

    The index/weight vectors travel packed ([5, cap] / [2, cap]) so a batch
    costs three host->device transfers instead of eight — per-transfer
    dispatch overhead dominates these tiny uploads; the named accessors are
    device-side slices that XLA fuses away.
    """

    ints: jax.Array       # [5, cap] int32: feat/add_src/add_dst/del_src/del_dst
    ws: jax.Array         # [2, cap] f32: add_w, del_w
    feat_val: jax.Array   # [cap, d0]

    @property
    def feat_idx(self) -> jax.Array:
        return self.ints[0]

    @property
    def add_src(self) -> jax.Array:
        return self.ints[1]

    @property
    def add_dst(self) -> jax.Array:
        return self.ints[2]

    @property
    def del_src(self) -> jax.Array:
        return self.ints[3]

    @property
    def del_dst(self) -> jax.Array:
        return self.ints[4]

    @property
    def add_w(self) -> jax.Array:
        return self.ws[0]

    @property
    def del_w(self) -> jax.Array:
        return self.ws[1]


# ---------------------------------------------------------------------------
# Deferred-commit plumbing: later hops read earlier hops' (rec_idx, h_new)
# patches instead of scattered arrays, so all writes can be gated at the end
# ---------------------------------------------------------------------------
def _patch_pos(n: int, p_idx: jax.Array) -> jax.Array:
    """Vertex id -> patch slot map (-1 where unpatched; sentinel ids drop)."""
    pos = jnp.full((n,), -1, dtype=jnp.int32)
    return pos.at[p_idx].set(jnp.arange(p_idx.shape[0], dtype=jnp.int32),
                             mode="drop")


def _patched(n: int, base: jax.Array, pos: jax.Array, p_val: jax.Array,
             idx: jax.Array) -> jax.Array:
    """Rows of ``base`` at ``idx`` as if the patch had been scattered."""
    idx_c = jnp.minimum(idx, n - 1)
    slot = pos[idx_c]
    return jnp.where((slot >= 0)[:, None], p_val[jnp.maximum(slot, 0)],
                     base[idx_c])


def _hop_messages(n: int, h_pre: jax.Array, csr: DeviceCSR,
                  frontier: jax.Array, delta: jax.Array,
                  batch: BatchDev, *, weighted: bool, self_dep: bool,
                  e_cap: int):
    """Build the (dst, value) message stream for hop l -> l+1.

    ``h_pre`` is the PRE-batch layer-l embedding array (pristine in the
    deferred-commit scheme), which is exactly the ``h_old`` the add/delete
    retraction messages need.  Returns (all_dst [E_tot], all_val [E_tot, d],
    n_edges_needed) where E_tot = e_cap + A + D (+ F for self-dep).
    """
    f_cap = frontier.shape[0]
    degs = jnp.where(frontier < n, csr.length[jnp.minimum(frontier, n - 1)], 0)
    csum = jnp.cumsum(degs)
    total = csum[-1] if f_cap else jnp.int32(0)

    # ragged expansion of frontier out-edges into the static edge bucket
    e = jnp.arange(e_cap, dtype=jnp.int32)
    fid = jnp.searchsorted(csum, e, side="right").astype(jnp.int32)
    fid_c = jnp.minimum(fid, f_cap - 1)
    row_begin = csum[fid_c] - degs[fid_c]
    off = e - row_begin
    vsrc = frontier[fid_c]
    flat = csr.start[jnp.minimum(vsrc, n - 1)] + off
    evalid = e < total
    flat = jnp.where(evalid, flat, 0)
    edst = jnp.where(evalid, csr.col[flat], n)
    ew = csr.w[flat] if weighted else jnp.ones(e_cap, dtype=h_pre.dtype)
    evals = delta[fid_c] * (ew * evalid)[:, None]

    def h_old(src: jax.Array) -> jax.Array:
        return h_pre[jnp.minimum(src, n - 1)]

    a_valid = (batch.add_src < n)[:, None]
    aw = batch.add_w if weighted else jnp.ones_like(batch.add_w)
    a_val = h_old(batch.add_src) * aw[:, None] * a_valid
    d_valid = (batch.del_src < n)[:, None]
    dw = batch.del_w if weighted else jnp.ones_like(batch.del_w)
    d_val = -h_old(batch.del_src) * dw[:, None] * d_valid

    dsts = [edst, batch.add_dst, batch.del_dst]
    vals = [evals, a_val, d_val]
    if self_dep:
        dsts.append(frontier)
        vals.append(jnp.zeros_like(delta))
    return jnp.concatenate(dsts), jnp.concatenate(vals), total


def _compact_mailbox(n: int, all_dst: jax.Array, all_val: jax.Array,
                     r_cap: int):
    """Sort-by-destination compaction: unique recipients + summed mailboxes.

    Returns (rec_idx [r_cap] sentinel-padded, mailbox [r_cap, d], n_recipients).
    Kept for the distributed halo path; the single-machine hops use the
    sort-free :func:`_unique_recipients` (XLA's CPU sort is the single most
    expensive op in the old formulation).
    """
    order = jnp.argsort(all_dst)  # sentinels (n) sort to the end
    sd = all_dst[order]
    sv = all_val[order]
    first = jnp.concatenate([jnp.ones((1,), bool), sd[1:] != sd[:-1]])
    is_real = sd < n
    newseg = first & is_real
    seg_id = jnp.cumsum(newseg) - 1
    seg_id = jnp.where(is_real, seg_id, r_cap).astype(jnp.int32)
    mailbox = jax.ops.segment_sum(sv, seg_id, num_segments=r_cap + 1)[:r_cap]
    n_rec = newseg.sum()
    rec_idx = jnp.full((r_cap,), n, dtype=jnp.int32)
    rec_idx = rec_idx.at[jnp.where(newseg, seg_id, r_cap)].set(sd, mode="drop")
    return rec_idx, mailbox, n_rec


def _unique_recipients(n: int, all_dst: jax.Array, r_cap: int):
    """Recipient compaction: unique message destinations in ascending vertex
    order plus the vertex -> mailbox-slot map.

    Two regimes, chosen by static shape: when the message bucket is at
    least half of |V|, a [n+1] presence mask + fixed-size ``nonzero`` is
    cheapest (O(n), no sort); when the bucket is small relative to the
    graph, an index sort keeps the cost O(E log E) — independent of |V|,
    which is what keeps per-batch work graph-size-insensitive on large
    graphs.  Both produce identical (ascending) recipient order.

    Returns (rec_idx [r_cap] ascending + sentinel-n padded, pos [n+1] vertex
    -> mailbox slot map (r_cap for non-recipients), n_recipients).
    """
    if all_dst.shape[0] >= n // 2:
        mask = jnp.zeros((n + 1,), bool).at[jnp.minimum(all_dst, n)].set(True)
        n_rec = mask[:n].sum()
        rec_idx = jnp.nonzero(mask[:n], size=r_cap, fill_value=n)[0] \
            .astype(jnp.int32)
    else:
        sd = jnp.sort(all_dst)  # sentinels (n) sort to the end
        newseg = jnp.concatenate([jnp.ones((1,), bool), sd[1:] != sd[:-1]]) \
            & (sd < n)
        n_rec = newseg.sum()
        seg_id = jnp.where(newseg, jnp.cumsum(newseg) - 1, r_cap)
        rec_idx = jnp.full((r_cap,), n, dtype=jnp.int32) \
            .at[seg_id].set(sd.astype(jnp.int32), mode="drop")
    pos = jnp.full((n + 1,), r_cap, dtype=jnp.int32)
    pos = pos.at[rec_idx].set(jnp.arange(r_cap, dtype=jnp.int32), mode="drop")
    return rec_idx, pos, n_rec


def _k_rows(n: int, state: DeviceState, batch: BatchDev, rec_idx: jax.Array,
            pos_r: jax.Array, r_cap: int) -> jax.Array:
    """Post-batch in-degree at the affected rows, from the batch's add/del
    counts — O(bucket) segment sums instead of materializing a full [n]
    updated-degree vector (the full vector is only written once, in the
    gated phase-2 commit)."""
    def cnt(dst):
        slot = pos_r[jnp.minimum(dst, n)]
        return jax.ops.segment_sum((dst < n).astype(jnp.float32), slot,
                                   num_segments=r_cap + 1)[:r_cap]
    return state.k[jnp.minimum(rec_idx, n - 1)] \
        + cnt(batch.add_dst) - cnt(batch.del_dst)


def _apply_hop(workload: Workload, params_l: dict, layer: int, n: int,
               state: DeviceState, k_rows: jax.Array, patch,
               rec_idx: jax.Array, mailbox: jax.Array, *, pallas: bool,
               interpret: bool):
    """Compute hop layer+1's row patch (no writes); returns
    (S_rows, h_new, next delta)."""
    aff_c = jnp.minimum(rec_idx, n - 1)
    valid = (rec_idx < n)[:, None]
    S_base = state.S[layer + 1][aff_c]
    pos = _patch_pos(n, patch[0])
    h_prev = _patched(n, state.H[layer], pos, patch[1], rec_idx)
    last = layer == workload.spec.n_layers - 1
    if pallas and workload.family in ("gc", "sage"):
        from repro.kernels.delta_apply import delta_apply
        mean = getattr(workload.agg, "by_degree", False)
        if workload.family == "gc":
            S_rows, h_new = delta_apply(S_base, mailbox, k_rows,
                                        params_l["w"], params_l["b"],
                                        mean=mean, relu=not last,
                                        interpret=interpret)
        else:  # SAGE: fused neighbor term; self term stays a jnp matmul
            S_rows, h_new = delta_apply(S_base, mailbox, k_rows,
                                        params_l["w_nbr"], params_l["b"],
                                        mean=mean, relu=False,
                                        interpret=interpret)
            h_new = h_new + matmul_f32(h_prev, params_l["w_self"])
            if not last:
                h_new = jnp.maximum(h_new, 0.0)
    elif pallas and workload.family == "gin":
        # fused two-matmul MLP apply (kernels/mlp_apply): fold + z-term +
        # both GIN matmuls in one HBM pass; jnp path stays the oracle
        from repro.kernels.mlp_apply import mlp_apply
        mean = getattr(workload.agg, "by_degree", False)
        S_rows, h_new = mlp_apply(S_base, mailbox, h_prev, k_rows,
                                  params_l["eps"], params_l["w1"],
                                  params_l["b1"], params_l["w2"],
                                  params_l["b2"], mean=mean, relu=not last,
                                  interpret=interpret)
    else:  # jnp oracle path
        S_rows = S_base + mailbox
        x = workload.normalize(S_rows, k_rows)
        h_new = workload.update_fn(layer)(params_l, h_prev, x)
    delta = (h_new - state.H[layer + 1][aff_c]) * valid
    return S_rows, h_new, delta


def _propagate_impl(workload: Workload, n: int,
                    caps: tuple[tuple[int, int], ...],
                    params: list[dict], state: DeviceState, csr: DeviceCSR,
                    batch: BatchDev, *, pallas: bool = False,
                    interpret: bool):
    """One full L-hop incremental propagation of a routed batch.

    caps[l] = (frontier_cap entering hop l+1 computation, edge_cap at hop l).
    Returns (new_state, final_affected idx, overflow flag, sizes [L, 3]) —
    ``sizes[l] = (recipients, edges, 0)`` actually needed at hop l, which
    the engine's adaptive cap schedule feeds on.  Phase 1 below only reads;
    phase 2 commits with overflow-gated scatters, so a failed attempt
    returns the input values bit-exactly even when ``state`` was donated.
    """
    L = workload.spec.n_layers
    spec = workload.spec

    # ---- phase 1: per-hop row patches, reads only ------------------------
    fv = batch.feat_idx
    old0 = state.H[0][jnp.minimum(fv, n - 1)]
    delta = (batch.feat_val - old0) * (fv < n)[:, None]
    frontier = fv
    patch = (fv, batch.feat_val)
    overflow = jnp.zeros((), dtype=bool)
    hops = []
    sizes = []
    for l in range(L):
        r_cap, e_cap = caps[l]
        all_dst, all_val, needed = _hop_messages(
            n, state.H[l], csr, frontier, delta, batch,
            weighted=spec.weighted, self_dep=spec.self_dependent, e_cap=e_cap)
        overflow |= needed > e_cap
        rec_idx, pos_r, n_rec = _unique_recipients(n, all_dst, r_cap)
        overflow |= n_rec > r_cap
        sizes.append(jnp.stack([n_rec.astype(jnp.int32),
                                needed.astype(jnp.int32),
                                jnp.int32(0)]))
        seg = pos_r[jnp.minimum(all_dst, n)]
        mailbox = jax.ops.segment_sum(all_val, seg,
                                      num_segments=r_cap + 1)[:r_cap]
        k_rows = _k_rows(n, state, batch, rec_idx, pos_r, r_cap)
        S_rows, h_new, delta = _apply_hop(
            workload, params[l], l, n, state, k_rows, patch, rec_idx, mailbox,
            pallas=pallas, interpret=interpret)
        hops.append((rec_idx, S_rows, h_new))
        patch = (rec_idx, h_new)
        frontier = rec_idx

    # ---- phase 2: overflow-gated commit ----------------------------------
    ok = ~overflow
    gate = lambda idx: jnp.where(ok, idx, n)  # noqa: E731
    H = list(state.H)
    S = list(state.S)
    H[0] = H[0].at[gate(fv)].set(batch.feat_val, mode="drop")
    for l, (rec, S_rows, h_new) in enumerate(hops):
        S[l + 1] = S[l + 1].at[gate(rec)].set(S_rows, mode="drop")
        H[l + 1] = H[l + 1].at[gate(rec)].set(h_new, mode="drop")
    k = state.k.at[gate(batch.add_dst)].add(1.0, mode="drop") \
               .at[gate(batch.del_dst)].add(-1.0, mode="drop")
    new_state = DeviceState(H=tuple(H), S=tuple(S), k=k, C=state.C)
    return new_state, jnp.where(ok, frontier, n), overflow, jnp.stack(sizes)


_PROP_STATIC = ("workload", "n", "caps", "pallas", "interpret")
propagate = jax.jit(_propagate_impl, static_argnames=_PROP_STATIC)
propagate_donated = jax.jit(_propagate_impl, static_argnames=_PROP_STATIC,
                            donate_argnames=("state",))


# ---------------------------------------------------------------------------
# Monotonic (max/min) propagation: GROW via candidate segment-extremum,
# SHRINK via per-row in-neighborhood pulls, filtered frontier (see
# core/aggregators.py for the algebra; host mirror in engine.py).
# ---------------------------------------------------------------------------
def _ragged_gather(n: int, csr: DeviceCSR, rows: jax.Array, degs: jax.Array,
                   cap: int):
    """Expand the CSR rows' adjacency lists into one static bucket.

    ``rows [R]`` are sentinel-clamped vertex ids with per-row counts
    ``degs [R]`` (0 for rows to skip).  Returns (cols [cap] sentinel-n
    padded, fid [cap] source row slot, valid [cap], total_needed).
    """
    r_cap = rows.shape[0]
    csum = jnp.cumsum(degs)
    total = csum[-1] if r_cap else jnp.int32(0)
    e = jnp.arange(cap, dtype=jnp.int32)
    fid = jnp.minimum(jnp.searchsorted(csum, e, side="right").astype(jnp.int32),
                      r_cap - 1)
    off = e - (csum[fid] - degs[fid])
    valid = e < total
    flat = jnp.where(valid,
                     csr.start[jnp.minimum(rows[fid], n - 1)] + off, 0)
    cols = jnp.where(valid, csr.col[flat], n)
    return cols, fid, valid, total


def _masked_pairs(mask: jax.Array, cap: int, fill_row: int):
    """Row-major (row, col) indices of the True cells of ``mask``, padded
    with ``(fill_row, 0)`` to the static ``cap``.

    Semantically ``jnp.nonzero(mask, size=cap, fill_value=(fill_row, 0))``,
    but lowered as one cumsum + one drop-scatter — XLA CPU's nonzero
    lowering is ~7x slower at these shapes and was the single hottest op
    in the per-dim monotonic hop.  Cells beyond ``cap`` are dropped
    (callers detect that via ``mask.sum() > cap`` overflow checks).
    """
    R, D = mask.shape
    flat = mask.reshape(-1)
    dest = jnp.where(flat, jnp.cumsum(flat) - 1, cap)
    lin = jnp.full((cap,), R * D, dtype=jnp.int32).at[dest].set(
        jnp.arange(R * D, dtype=jnp.int32), mode="drop")
    hit = lin < R * D
    return (jnp.where(hit, lin // D, fill_row).astype(jnp.int32),
            jnp.where(hit, lin % D, 0).astype(jnp.int32))


def _expand_frontier_edges(n: int, csr: DeviceCSR, frontier: jax.Array,
                           e_cap: int):
    """Ragged gather of frontier out-edges into a static bucket.

    Returns (edst [e_cap], esrc [e_cap], n_edges_needed); sentinel n pads.
    """
    degs = jnp.where(frontier < n, csr.length[jnp.minimum(frontier, n - 1)], 0)
    edst, fid, evalid, total = _ragged_gather(n, csr, frontier, degs, e_cap)
    esrc = jnp.where(evalid, frontier[fid], n)
    return edst, esrc, total


def _monotonic_hop(workload: Workload, params_l: dict, layer: int, n: int,
                   state: DeviceState, out_csr: DeviceCSR, in_csr: DeviceCSR,
                   batch: BatchDev, frontier: jax.Array, patch,
                   *, r_cap: int, e_cap: int, p_cap: int, pd_cap: int,
                   pallas: bool, interpret: bool):
    """One GROW/SHRINK hop layer -> layer+1 (reads only); returns the hop
    patch (rec_idx, S_new, C_new, h_new), the filtered next frontier, the
    overflow flag, and the (shrink_events, rows_reaggregated,
    dims_reaggregated, recover_hits) counters.

    SHRINK runs at per-(row, dim) granularity: classification produces a
    ``[r_cap, d]`` mask (one cell per shrunk dim, deduped across the
    batch's messages by the segment-max scatter), the re-cover probe drops
    every cell the batch's own candidate extremum already re-witnesses,
    and the survivors re-derive from the in-CSR.  The fetch has two
    lowerings chosen by static backend: on accelerators the cells are
    flattened into (row, dim) *pairs* (static cap ``pd_cap``) whose
    in-neighborhoods are gathered as single-column element reads —
    ``p_cap`` then bounds pulled elements, not pulled-rows-times-d; under
    XLA CPU (interpret mode) needy rows are re-derived with vector row
    gathers instead (``p_cap`` bounds their total in-degree), because the
    CPU per-lane scatter overhead dwarfs the traffic saved.

    All extremum arithmetic runs in max-space (``sign * value``) so one code
    path serves both max and min; the post-update layer-l values are read
    through the previous hop's patch (deferred-commit scheme).
    """
    agg = workload.agg
    sign = agg.sign
    H_pre, S_next, C_next = state.H[layer], state.S[layer + 1], \
        state.C[layer + 1]
    pos_p = _patch_pos(n, patch[0])

    edst, esrc, needed = _expand_frontier_edges(n, out_csr, frontier, e_cap)
    overflow = needed > e_cap

    # unified message stream: frontier edges + adds are candidates AND
    # probes; deletes are probes only (their value must never grow S)
    msg_dst = jnp.concatenate([edst, batch.add_dst, batch.del_dst])
    msg_src = jnp.concatenate([esrc, batch.add_src, batch.del_src])
    n_cand = edst.shape[0] + batch.add_dst.shape[0]
    is_del = jnp.arange(msg_dst.shape[0]) >= n_cand
    valid = (msg_dst < n) & (msg_src < n)

    # affected rows = unique message dsts (+ frontier for self-dependence)
    all_dst = msg_dst
    if workload.spec.self_dependent:
        all_dst = jnp.concatenate([all_dst, frontier])
    rec_idx, pos, n_rec = _unique_recipients(n, all_dst, r_cap)
    overflow |= n_rec > r_cap
    aff_c = jnp.minimum(rec_idx, n - 1)
    real_row = rec_idx < n
    slot = jnp.where(valid, pos[jnp.minimum(msg_dst, n)], r_cap)

    vals = _patched(n, H_pre, pos_p, patch[1], msg_src)  # post-update values
    vals_ms = sign * vals

    # ---- per-(message, dim) SHRINK classification, deduped per row -------
    S_dst_ms = sign * S_next[jnp.minimum(msg_dst, n - 1)]
    C_dst = C_next[jnp.minimum(msg_dst, n - 1)]
    covered = C_dst == msg_src[:, None].astype(C_dst.dtype)
    gone = is_del[:, None] | (S_dst_ms > vals_ms)
    dim_shrink = covered & gone & valid[:, None]
    n_shrink = jnp.any(dim_shrink, axis=1).sum().astype(jnp.int32)
    row_dim = jax.ops.segment_max(dim_shrink.astype(jnp.float32), slot,
                                  num_segments=r_cap + 1)[:r_cap] > 0

    # ---- GROW candidate extremum + witnesses (also feeds the probe) ------
    small_ids = n < (1 << 24)  # f32 witness ids are exact below 2^24
    is_cand = valid & ~is_del
    cslot = jnp.where(is_cand, slot, r_cap)
    cand_S, cand_C = jnp_segment_extremum(agg, vals, cslot, r_cap, msg_src,
                                          small_ids=small_ids)

    S_pre_rows = S_next[aff_c]
    C_pre_rows = C_next[aff_c]

    # ---- re-cover probe: candidate ties-or-beats the lost extremum -------
    recovered = row_dim & (sign * cand_S >= sign * S_pre_rows)
    need = row_dim & ~recovered & real_row[:, None]
    n_recover = recovered.sum().astype(jnp.int32)
    n_pairs = need.sum()
    n_reagg = jnp.any(need, axis=1).sum().astype(jnp.int32)

    # ---- surviving (row, dim) cells: re-derive from the in-CSR -----------
    # Two lowerings of the same per-dim algebra, chosen by static backend
    # (the `_unique_recipients` precedent): on accelerators, pair-flatten
    # the cells and gather single columns as element reads — pulled volume
    # is exactly Σ shrunk-dims × degree; on XLA CPU (interpret mode),
    # per-element scatter/gather lowering costs ~1us/lane, so rows that
    # still need any dim are re-derived with one vector-friendly row
    # gather instead (the probe still prunes whole rows, the counters
    # still report cells — the algebra is identical, only the fetch
    # granularity differs).
    if interpret:  # CPU: row-granular vector gathers over needy rows
        row_need = jnp.any(need, axis=1)
        degs = jnp.where(row_need, in_csr.length[aff_c], 0)
        psrc, fid, pvalid, pull_total = _ragged_gather(n, in_csr, aff_c,
                                                       degs, p_cap)
        overflow |= pull_total > p_cap
        pvals = _patched(n, H_pre, pos_p, patch[1], psrc)
        pseg = jnp.where(pvalid, fid, r_cap)
        S_sh, C_sh = jnp_segment_extremum(agg, pvals, pseg, r_cap, psrc,
                                          small_ids=small_ids)
        base_S = jnp.where(row_need[:, None], S_sh, S_pre_rows)
        base_C = jnp.where(row_need[:, None], C_sh, C_pre_rows)
        MK = jnp.broadcast_to(row_need[:, None],
                              S_pre_rows.shape).astype(jnp.float32)
        RG = jnp.where(row_need[:, None], S_sh, 0.0)
    else:  # accelerator: pair-flattened single-column element gathers
        overflow |= n_pairs > pd_cap
        pr, pdim = _masked_pairs(need, pd_cap, r_cap)
        rows_pair = aff_c[jnp.minimum(pr, r_cap - 1)]
        degs = jnp.where(pr < r_cap, in_csr.length[rows_pair], 0)
        psrc, fid, pvalid, pull_total = _ragged_gather(n, in_csr, rows_pair,
                                                       degs, p_cap)
        overflow |= pull_total > p_cap
        pdim_e = pdim[fid]
        psrc_c = jnp.minimum(psrc, n - 1)
        pslot = pos_p[psrc_c]
        pvals = jnp.where(pslot >= 0,
                          patch[1][jnp.maximum(pslot, 0), pdim_e],
                          H_pre[psrc_c, pdim_e])
        pseg = jnp.where(pvalid, fid, pd_cap)
        S_pair, C_pair = jnp_segment_extremum(agg, pvals, pseg, pd_cap, psrc,
                                              small_ids=small_ids)
        base_S = S_pre_rows.at[pr, pdim].set(S_pair, mode="drop")
        base_C = C_pre_rows.at[pr, pdim].set(C_pair, mode="drop")
        MK = jnp.zeros_like(S_pre_rows).at[pr, pdim].set(1.0, mode="drop")
        RG = jnp.zeros_like(S_pre_rows).at[pr, pdim].set(S_pair, mode="drop")

    # ---- GROW: fold the candidate extremum in (elementwise) --------------
    cand_wins = (sign * cand_S >= sign * base_S) & (cand_C >= 0)
    S_new = jnp.where(cand_wins, cand_S, base_S)
    C_new = jnp.where(cand_wins, cand_C, base_C)

    # ---- apply + filtered propagation ------------------------------------
    h_prev = _patched(n, H_pre, pos_p, patch[1], rec_idx)
    last = layer == workload.spec.n_layers - 1
    if pallas and workload.family in ("gc", "sage"):
        from repro.kernels.extremum_apply import extremum_apply
        # the masked kernel fuses the per-dim select (pre-batch rows vs
        # re-aggregated cells), the candidate fold, the finite-mask and
        # the matmul into one HBM pass; RG/MK carry the regime's re-derived
        # cells (pair scatters on accelerators, row masks on CPU)
        maximize = sign > 0
        if workload.family == "gc":
            S_new, h_new = extremum_apply(S_pre_rows, cand_S,
                                          params_l["w"], params_l["b"],
                                          reagg=RG, mask=MK,
                                          maximize=maximize, relu=not last,
                                          interpret=interpret)
        else:  # SAGE: fused neighbor term; self term stays a jnp matmul
            S_new, h_new = extremum_apply(S_pre_rows, cand_S,
                                          params_l["w_nbr"], params_l["b"],
                                          reagg=RG, mask=MK,
                                          maximize=maximize, relu=False,
                                          interpret=interpret)
            h_new = h_new + matmul_f32(h_prev, params_l["w_self"])
            if not last:
                h_new = jnp.maximum(h_new, 0.0)
    else:
        # monotonic normalize is the finite-mask — k is unused by the
        # algebra, so the pre-batch rows suffice for the call contract
        x = workload.normalize(S_new, state.k[aff_c])
        h_new = workload.update_fn(layer)(params_l, h_prev, x)
    changed = jnp.any(h_new != state.H[layer + 1][aff_c], axis=1) & real_row
    frontier_next = jnp.where(changed, rec_idx, n)
    sizes = jnp.stack([n_rec.astype(jnp.int32), needed.astype(jnp.int32),
                       pull_total.astype(jnp.int32),
                       n_pairs.astype(jnp.int32)])
    return (rec_idx, S_new, C_new, h_new), frontier_next, overflow, sizes, \
        jnp.stack([n_shrink, n_reagg, n_pairs.astype(jnp.int32), n_recover])


def _propagate_monotonic_impl(workload: Workload, n: int,
                              caps: tuple[tuple[int, int, int, int], ...],
                              params: list[dict], state: DeviceState,
                              out_csr: DeviceCSR, in_csr: DeviceCSR,
                              batch: BatchDev, *, pallas: bool = False,
                              interpret: bool):
    """L-hop monotonic (max/min) propagation of a routed batch.

    caps[l] = (row_cap, edge_cap, pull_cap, pair_cap) at hop l; pull_cap
    bounds the total pulled *elements* (per-dim single-column gathers) and
    pair_cap the number of (row, dim) cells re-aggregated that hop.
    Returns (new_state, final frontier idx, overflow flag, sizes [L, 4]
    needed per hop, [shrink_events, rows_reaggregated, dims_reaggregated,
    recover_hits]) — phase-1/phase-2 deferred commit like ``propagate``,
    so an overflowing attempt commits nothing even under buffer donation.
    """
    L = workload.spec.n_layers

    fv = batch.feat_idx
    old = state.H[0][jnp.minimum(fv, n - 1)]
    changed0 = jnp.any(batch.feat_val != old, axis=1) & (fv < n)
    frontier = jnp.where(changed0, fv, n)  # hop-0 filtering: no-op writes stop
    patch = (fv, batch.feat_val)
    overflow = jnp.zeros((), dtype=bool)
    stats = jnp.zeros((4,), dtype=jnp.int32)
    hops = []
    sizes = []
    for l in range(L):
        r_cap, e_cap, p_cap, pd_cap = caps[l]
        hop_patch, frontier, ovf, hop_sizes, hop_stats = _monotonic_hop(
            workload, params[l], l, n, state, out_csr, in_csr, batch,
            frontier, patch, r_cap=r_cap, e_cap=e_cap, p_cap=p_cap,
            pd_cap=pd_cap, pallas=pallas, interpret=interpret)
        overflow |= ovf
        stats = stats + hop_stats
        hops.append(hop_patch)
        sizes.append(hop_sizes)
        patch = (hop_patch[0], hop_patch[3])

    # ---- overflow-gated commit -------------------------------------------
    ok = ~overflow
    gate = lambda idx: jnp.where(ok, idx, n)  # noqa: E731
    H = list(state.H)
    S = list(state.S)
    C = list(state.C)
    H[0] = H[0].at[gate(fv)].set(batch.feat_val, mode="drop")
    for l, (rec, S_new, C_new, h_new) in enumerate(hops):
        S[l + 1] = S[l + 1].at[gate(rec)].set(S_new, mode="drop")
        C[l + 1] = C[l + 1].at[gate(rec)].set(C_new, mode="drop")
        H[l + 1] = H[l + 1].at[gate(rec)].set(h_new, mode="drop")
    k = state.k.at[gate(batch.add_dst)].add(1.0, mode="drop") \
               .at[gate(batch.del_dst)].add(-1.0, mode="drop")
    new_state = DeviceState(H=tuple(H), S=tuple(S), k=k, C=tuple(C))
    return new_state, jnp.where(ok, frontier, n), overflow, \
        jnp.stack(sizes), stats


propagate_monotonic = jax.jit(_propagate_monotonic_impl,
                              static_argnames=_PROP_STATIC)
propagate_monotonic_donated = jax.jit(_propagate_monotonic_impl,
                                      static_argnames=_PROP_STATIC,
                                      donate_argnames=("state",))


# ---------------------------------------------------------------------------
# Bounded-recompute (attention / top-k / PNA) propagation: every affected
# row re-aggregates over its mirrored in-neighborhood each hop (the device
# trades the host's PATCH classification for one uniform gather — a fixed
# dataflow XLA can compile), the cached aux state rides DeviceState through
# donation + the gated commit, and the frontier stays *filtered* (only
# changed rows propagate), which is what keeps the device path
# frontier-proportional rather than RC-shaped.  With ``tolerance > 0`` the
# per-layer deferral budgets arrive as a dynamic ``taus`` vector (no
# recompile across tolerance values): interior-hop writes within budget are
# dropped (the stale store is exactly what downstream reads see), and the
# per-layer max deferred magnitude / max committed |h| travel back to the
# host, which owns the certified eps/M/kmax accounting.
# ---------------------------------------------------------------------------
def _bounded_hop(workload: Workload, params_l: dict, layer: int, n: int,
                 state: DeviceState, out_csr: DeviceCSR, in_csr: DeviceCSR,
                 batch: BatchDev, frontier: jax.Array, patch, tau, *,
                 r_cap: int, e_cap: int, p_cap: int, h_cap: int,
                 pallas: bool, interpret: bool):
    """One bounded hop layer -> layer+1 (reads only); returns the hop patch
    (rec_idx, x_rows, aux tuple, h_out), the filtered next frontier, the
    overflow flag, sizes, int counters and (max deferred b, max |h|)."""
    agg = workload.agg
    H_pre = state.H[layer]
    pos_p = _patch_pos(n, patch[0])

    edst, esrc, needed = _expand_frontier_edges(n, out_csr, frontier, e_cap)
    overflow = needed > e_cap

    all_dst = jnp.concatenate([edst, batch.add_dst, batch.del_dst])
    if workload.spec.self_dependent:
        all_dst = jnp.concatenate([all_dst, frontier])
    rec_idx, pos_r, n_rec = _unique_recipients(n, all_dst, r_cap)
    overflow |= n_rec > r_cap
    aff_c = jnp.minimum(rec_idx, n - 1)
    real_row = rec_idx < n
    k_rows = _k_rows(n, state, batch, rec_idx, pos_r, r_cap)

    # refresh-all pull: the affected rows' post-batch in-neighborhoods,
    # post-update layer-l values read through the previous hop's patch
    degs = jnp.where(real_row, in_csr.length[aff_c], 0)
    psrc, fid, pvalid, pull_total = _ragged_gather(n, in_csr, aff_c, degs,
                                                   p_cap)
    overflow |= pull_total > p_cap
    hmax = jnp.max(degs)
    pvals = _patched(n, H_pre, pos_p, patch[1], psrc)
    pseg = jnp.where(pvalid, fid, r_cap)

    if pallas and agg.name == "pna":
        # PNA moment gather through the EmbeddingBag Pallas kernel: the
        # ragged neighborhoods become one [r_cap, h_cap] index rectangle
        # (sentinel lanes point at a zero row appended to the table) and
        # s1 = bag-sum of neighbor embeddings is exactly the kernel's
        # contract; s2 / max+witness stay segment ops on the same pull
        from repro.kernels.embedding_bag import embedding_bag_pallas
        overflow |= hmax > h_cap
        d = H_pre.shape[1]
        table = jnp.concatenate([H_pre, jnp.zeros((1, d), H_pre.dtype)])
        p_idx = jnp.where(patch[0] < n, patch[0], n + 1)  # keep row n zero
        table = table.at[p_idx].set(patch[1], mode="drop")
        csum = jnp.cumsum(degs)
        off = jnp.arange(p_cap, dtype=jnp.int32) - (csum[fid] - degs[fid])
        idx = jnp.full((r_cap, h_cap), n, dtype=jnp.int32)
        idx = idx.at[jnp.where(pvalid, fid, r_cap),
                     jnp.where(pvalid, off, 0)].set(
            jnp.minimum(psrc, n).astype(jnp.int32), mode="drop")
        s1 = embedding_bag_pallas(idx, table, interpret=interpret)
        vc = jnp.where(pvalid[:, None], pvals, 0.0)
        s2 = jax.ops.segment_sum(vc * vc, pseg,
                                 num_segments=r_cap + 1)[:r_cap]
        mx, mref = jnp_segment_extremum(
            MAX, jnp.where(pvalid[:, None], pvals, -jnp.inf), pseg, r_cap,
            psrc)
        x_rows = agg._tower(s1, s2, mx, k_rows, xp=jnp)
        aux_t = (s1, s2, mx, mref)
    else:
        x_rows, aux_t = agg.jnp_reaggregate(pvals, psrc, pseg, r_cap, k_rows)

    # ---- apply + certified deferral + filtered propagation ---------------
    h_prev = _patched(n, H_pre, pos_p, patch[1], rec_idx)
    x = workload.normalize(x_rows, k_rows)
    h_new = workload.update_fn(layer)(params_l, h_prev, x)
    stored = state.H[layer + 1][aff_c]
    changed = jnp.any(h_new != stored, axis=1) & real_row
    b = jnp.max(jnp.abs(h_new - stored), axis=1)
    defer = changed & (b <= tau)  # tau = 0 at the last hop: never defers
    write = changed & ~defer
    viol = changed & ~defer & (tau > 0)
    h_out = jnp.where(write[:, None], h_new, stored)
    frontier_next = jnp.where(write, rec_idx, n)
    i_stats = jnp.stack([real_row.sum().astype(jnp.int32),
                         defer.sum().astype(jnp.int32),
                         viol.sum().astype(jnp.int32)])
    f_stats = jnp.stack([jnp.max(jnp.where(defer, b, 0.0)),
                         jnp.max(jnp.where(write,
                                           jnp.max(jnp.abs(h_new), axis=1),
                                           0.0))])
    sizes = jnp.stack([n_rec.astype(jnp.int32), needed.astype(jnp.int32),
                       pull_total.astype(jnp.int32), hmax.astype(jnp.int32)])
    return (rec_idx, x_rows, aux_t, h_out), frontier_next, overflow, sizes, \
        i_stats, f_stats


def _propagate_bounded_impl(workload: Workload, n: int,
                            caps: tuple[tuple[int, int, int, int], ...],
                            params: list[dict], state: DeviceState,
                            out_csr: DeviceCSR, in_csr: DeviceCSR,
                            batch: BatchDev, taus: jax.Array, *,
                            pallas: bool = False, interpret: bool):
    """L-hop bounded (attention/top-k/PNA) propagation of a routed batch.

    caps[l] = (row_cap, edge_cap, pull_cap, indeg_cap); pull_cap bounds the
    affected rows' total in-degree, indeg_cap the max per-row in-degree
    (the EmbeddingBag rectangle width — only enforced on the Pallas PNA
    path).  Returns (new_state, final frontier, overflow, sizes [L, 4],
    ([rows_reaggregated, deferred_rows, bound_violations],
    per-layer [L+1, 2] (max deferred b, max committed |h|))) — same
    deferred phase-1/phase-2 gated commit as the other families, so an
    overflowing attempt commits nothing even under buffer donation.
    """
    L = workload.spec.n_layers
    fv = batch.feat_idx
    old = state.H[0][jnp.minimum(fv, n - 1)]
    changed0 = jnp.any(batch.feat_val != old, axis=1) & (fv < n)
    frontier = jnp.where(changed0, fv, n)
    patch = (fv, batch.feat_val)
    overflow = jnp.zeros((), dtype=bool)
    i_stats = jnp.zeros((3,), dtype=jnp.int32)
    f_rows = [jnp.stack([jnp.float32(0.0),
                         jnp.max(jnp.abs(batch.feat_val)
                                 * (fv < n)[:, None].astype(jnp.float32))])]
    hops = []
    sizes = []
    for l in range(L):
        r_cap, e_cap, p_cap, h_cap = caps[l]
        hop_patch, frontier, ovf, hop_sizes, hop_i, hop_f = _bounded_hop(
            workload, params[l], l, n, state, out_csr, in_csr, batch,
            frontier, patch, taus[l + 1], r_cap=r_cap, e_cap=e_cap,
            p_cap=p_cap, h_cap=h_cap, pallas=pallas, interpret=interpret)
        overflow |= ovf
        i_stats = i_stats + hop_i
        hops.append(hop_patch)
        sizes.append(hop_sizes)
        f_rows.append(hop_f)
        patch = (hop_patch[0], hop_patch[3])

    # ---- overflow-gated commit -------------------------------------------
    ok = ~overflow
    gate = lambda idx: jnp.where(ok, idx, n)  # noqa: E731
    H = list(state.H)
    S = list(state.S)
    A = list(state.A)
    H[0] = H[0].at[gate(fv)].set(batch.feat_val, mode="drop")
    for l, (rec, x_rows, aux_t, h_out) in enumerate(hops):
        S[l + 1] = S[l + 1].at[gate(rec)].set(x_rows, mode="drop")
        H[l + 1] = H[l + 1].at[gate(rec)].set(h_out, mode="drop")
        A[l + 1] = tuple(a.at[gate(rec)].set(v, mode="drop")
                         for a, v in zip(A[l + 1], aux_t))
    k = state.k.at[gate(batch.add_dst)].add(1.0, mode="drop") \
               .at[gate(batch.del_dst)].add(-1.0, mode="drop")
    new_state = DeviceState(H=tuple(H), S=tuple(S), k=k, C=state.C,
                            A=tuple(A))
    okf = ok.astype(jnp.float32)
    return new_state, jnp.where(ok, frontier, n), overflow, \
        jnp.stack(sizes), (i_stats * ok.astype(jnp.int32),
                           jnp.stack(f_rows) * okf)


propagate_bounded = jax.jit(_propagate_bounded_impl,
                            static_argnames=_PROP_STATIC)
propagate_bounded_donated = jax.jit(_propagate_bounded_impl,
                                    static_argnames=_PROP_STATIC,
                                    donate_argnames=("state",))


def _overflowed(flag: jax.Array) -> bool:
    """The propagate's overflow flag, which blocks until the device has
    run the step."""
    with span("ripple.engine.device_wait"):
        return bool(flag)


# the cap channels of each algebra family's propagate, in ``caps`` order
_CHANNELS = {"invertible": ("rows", "edges"),
             "monotonic": ("rows", "edges", "pulls", "pairs"),
             "bounded": ("rows", "edges", "pulls", "indeg")}


class DeviceEngine:
    """Host driver around the jitted propagation with a warm bucket ladder.

    Mirrors RippleEngine semantics; used by tests for cross-engine
    equivalence and by the dry-run/roofline path for the paper's own
    workloads.  Per-batch cost is frontier-proportional: the adjacency
    lives in persistent :class:`DeviceCSRMirror` pools, the state buffers
    are donated through the jit (``donate=True``), ``k`` is maintained on
    device, and the cap schedule is a sticky ladder (the rung that last
    fit is retried first, and rung 0 is precompiled at construction).

    With ``async_dispatch=True`` the overflow flag of batch t is checked
    lazily: ``apply_batch(t)`` routes t on the host while the device still
    crunches batch t-1, resolves t-1 (retrying it on the next rung if it
    overflowed — the gated commit guarantees the pre-batch values
    survived), then dispatches t and returns the *previous* batch's
    affected ids; ``flush()`` drains the pipeline.
    """

    def __init__(self, workload: Workload, params: list[dict],
                 graph: DynamicGraph, state_np, *, min_bucket: int = 64,
                 donate: bool = True, use_pallas: bool = False,
                 async_dispatch: bool = False, debug_checks: bool = False,
                 warm: bool = True, tolerance: float = 0.0):
        from repro.utils import next_bucket
        self._next_bucket = next_bucket
        self.workload = workload
        self.params = [{k: jnp.asarray(v) for k, v in p.items()} for p in params]
        self._params_np = [{k: np.asarray(v) for k, v in p.items()}
                           for p in params]
        self.graph = graph
        self.n = graph.n
        self.monotonic = workload.agg.algebra == "monotonic"
        self.bounded = workload.agg.algebra == "bounded"
        self.tolerance = float(tolerance)
        if self.tolerance > 0 and not self.bounded:
            raise ValueError(
                f"tolerance > 0 requires a bounded-recompute workload; "
                f"{workload.spec.name!r} uses the "
                f"{workload.agg.algebra} family")
        aux_names = workload.agg.aux_names if self.bounded else ()
        self.state = DeviceState(
            H=tuple(jnp.asarray(h) for h in state_np.H),
            S=tuple(jnp.asarray(s) for s in state_np.S),
            k=jnp.asarray(graph.in_degree),
            C=tuple(jnp.asarray(c, dtype=jnp.int32) for c in state_np.C)
            if state_np.C is not None else (),
            A=tuple(tuple(jnp.asarray(a[nm]) for nm in aux_names)
                    if a else () for a in state_np.A)
            if getattr(state_np, "A", None) is not None else ())
        if self.bounded:
            # host-owned certified-bound accounting (see engine.py): eps is
            # authoritative state (rides checkpoints via InferenceState),
            # M/kmax are re-derived bounds grown per batch
            eps = getattr(state_np, "eps", None)
            self._eps = np.array(eps, dtype=np.float64) if eps is not None \
                else np.zeros(workload.spec.n_layers + 1, dtype=np.float64)
            self._M = np.array([float(np.abs(h).max()) if h.size else 0.0
                                for h in state_np.H], dtype=np.float64)
            self._kmax = float(graph.in_degree.max()) if graph.n else 0.0
        self.min_bucket = min_bucket
        self.donate = donate
        self.use_pallas = use_pallas
        self.async_dispatch = async_dispatch
        self.debug_checks = debug_checks
        self.interpret = interpret_mode()
        # host-memory backend: device arrays are host arrays, so the
        # serving commit log indexes them instead of gathering on device
        self._host_backend = jax.default_backend() == "cpu"
        self.shape_misses = ShapeMisses()
        self.out_mirror = DeviceCSRMirror(graph.out,
                                          shapes=self.shape_misses)
        self.in_mirror = DeviceCSRMirror(graph.inn, shapes=self.shape_misses,
                                         span_prefix="ripple.mirror.in_") \
            if (self.monotonic or self.bounded) else None
        self._bucket = min_bucket
        self._rung = 0          # transient retry boost (0 once sizes known)
        self._hw = None         # per-hop high-water marks: [L, 3] (r, e, 0)
        #                         invertible, [L, 4] (r, e, p, pd) monotonic
        self._notes = 0         # high-water adoptions (settle-phase counter)
        self.retries = 0        # overflow retries across the stream
        # the same retries by the cap channel that overflowed (a retry
        # whose attempt overflowed two channels counts under both)
        self.retries_by_channel = dict.fromkeys(
            _CHANNELS[workload.agg.algebra], 0)
        self._pending = None    # (ovf, final, sizes, stats, batch, caps, k)
        self._last_affected = np.empty(0, dtype=np.int64)
        self._commit_log = None   # serving: [(commit_idx, affected, rows)]
        self._commits = 0         # batches committed since log enabled
        self.last_shrink_events = 0
        self.last_rows_reaggregated = 0
        self.last_dims_reaggregated = 0
        self.last_recover_hits = 0
        # the last_* counts summed over every batch resolved
        self.shrink_events = 0
        self.rows_reaggregated = 0
        self.dims_reaggregated = 0
        self.recover_hits = 0
        self.last_patch_events = 0      # bounded: device is refresh-all (0)
        self.last_deferred_rows = 0
        self.last_bound_violations = 0
        if warm:
            self._warm()

    def error_bound(self) -> np.ndarray:
        """Certified per-vertex inf-norm bound on published H[L] vs the
        full oracle (zeros unless deferrals have happened)."""
        if not self.bounded:
            return np.zeros(self.n, dtype=np.float32)
        E = certified_error_bound(self.workload, self._params_np, self._eps,
                                  self._M, self._kmax)
        return np.full(self.n, E[-1], dtype=np.float32)

    def _taus(self) -> jax.Array:
        """Per-layer deferral budgets for the next dispatch (zeros at
        tolerance=0: the jitted comparison never defers)."""
        L = self.workload.spec.n_layers
        if self.bounded and self.tolerance > 0:
            t = deferral_budgets(self.workload, self._params_np, self._eps,
                                 self._M, self._kmax, self.tolerance)
        else:
            t = np.zeros(L + 1, dtype=np.float64)
        return jnp.asarray(t.astype(np.float32))

    # -- cap schedule ------------------------------------------------------
    _HEADROOM = 1.25  # slack over the high-water mark before bucketing

    def _caps(self, rung: int) -> tuple:
        """The static bucket capacities at retry rung ``rung``.

        Once a batch has run, the schedule is *adaptive*: each hop's caps
        are the power-of-two bucket over that hop's high-water needed sizes
        (reported back by the jitted propagate), so buckets track the
        stream's actual frontier growth instead of a blind geometric ladder
        — the caps a batch pays for are within 2.5x of what it uses.  The
        first batch (and rung escalations when a retry's sizes were
        truncated) falls back to the geometric schedule.
        """
        nb = self._next_bucket
        e_max = nb(max(self.graph.num_edges, 1)) * 2
        n_b = nb(self.n)
        L = self.workload.spec.n_layers
        # per-dim shrink channels: pairs are bounded by every dim of every
        # row re-aggregating, pulled ELEMENTS by every edge read once per
        # dim — both ceilings must exceed e_max or a batch whose pull
        # volume tops the edge count can never fit and the ladder spins
        max_d = nb(max(self.workload.spec.dims))
        pd_max = n_b * max_d
        p_max = e_max * max_d
        scale = 4 ** rung
        caps = []
        if self._hw is not None:
            for l in range(L):
                chans = [max(int(v * self._HEADROOM), 1) * scale
                         for v in self._hw[l]]
                cap_l = (min(nb(chans[0], minimum=self.min_bucket), n_b),
                         min(nb(chans[1], minimum=self.min_bucket), e_max))
                if self.monotonic:
                    cap_l += (min(nb(chans[2], minimum=self.min_bucket),
                                  p_max),
                              min(nb(chans[3], minimum=self.min_bucket),
                                  pd_max))
                elif self.bounded:
                    # pull channel: affected rows' total in-degree (<= |E|);
                    # indeg channel: max per-row in-degree (<= n)
                    cap_l += (min(nb(chans[2], minimum=self.min_bucket),
                                  e_max),
                              min(nb(chans[3], minimum=self.min_bucket),
                                  n_b))
                caps.append(cap_l)
            return tuple(caps)
        r = min(nb(self._bucket * scale, minimum=self._bucket), n_b)
        e = min(nb(4 * r), e_max)
        rr, ee = r, e
        for _ in range(L):
            if self.monotonic:
                caps.append((rr, ee, min(ee, p_max), min(ee, pd_max)))
            elif self.bounded:
                caps.append((rr, ee, min(ee, e_max), min(ee, n_b)))
            else:
                caps.append((rr, ee))
            rr = min(nb(rr * 4), n_b)
            ee = min(nb(ee * 4), e_max)
        return tuple(caps)

    def _bucketed(self, hw: np.ndarray) -> np.ndarray:
        """Elementwise power-of-two bucket of headroomed high-water marks
        (the quantity whose changes force a recompile)."""
        v = np.maximum((hw * self._HEADROOM).astype(np.int64),
                       self.min_bucket)
        return 1 << np.ceil(np.log2(v)).astype(np.int64)

    _SETTLE_NOTES = 16  # high-water adoptions before drift-overshoot kicks in

    def _note_sizes(self, sizes) -> None:
        """Fold one attempt's per-hop needed sizes into the high-water
        marks (an overflowed attempt's sizes aim the retry directly at
        fitting caps — no blind escalation).  While the schedule settles,
        marks adopt the observed sizes plainly (batch-to-batch noise must
        not inflate the buckets); once settled, a channel that outgrows
        its bucket gets one extra 2x of headroom so a drifting stream pays
        at most one recompile per doubling instead of one per crossing.

        A settled monotonic mark moves only when a batch needed more than
        its channel's cap (the batch overflowed), and then to that need: a
        record that still fits keeps the caps. Its four channels' sizes are
        heavy-tailed, so under the crossing rule a record past 1/_HEADROOM
        of some cap comes late and at a random batch, with a ~10 s compile
        on a TPU and a 4x larger cap from then on; an overflow's need
        already buckets at least one doubling up."""
        s = np.asarray(sizes, dtype=np.int64)
        self._notes += 1
        if self._hw is None:
            self._hw = s
            return
        grown = np.maximum(self._hw, s)
        if self._notes > self._SETTLE_NOTES:
            held = self._bucketed(self._hw)
            if self.monotonic:
                grown = np.where(s > held, s, self._hw)
            else:
                crossed = self._bucketed(grown) > held
                grown = np.where(crossed, grown * 2, grown)
        self._hw = grown

    def _sentinel_batch(self) -> BatchDev:
        n, cap = self.n, self._bucket
        d0 = int(self.state.H[0].shape[1])
        return BatchDev(ints=jnp.full((5, cap), n, dtype=jnp.int32),
                        ws=jnp.zeros((2, cap), dtype=jnp.float32),
                        feat_val=jnp.zeros((cap, d0), dtype=jnp.float32))

    def _warm(self) -> None:
        """Precompile the rung-0 cap schedule by propagating a sentinel
        (all-padding) batch — a bit-exact no-op on the state.  The sentinel
        must not seed the adaptive high-water marks (its needs are zero),
        so they are reset afterwards and the first real batch starts from
        the geometric schedule this warm-up compiled."""
        self._dispatch(self._sentinel_batch())
        self._resolve()
        self._hw = None
        self._notes = 0
        self._rung = 0

    # -- routing -----------------------------------------------------------
    def _route(self, batch):
        """Apply the batch's topology to the host graph and build the padded
        device batch + the mirror rows it touched.  Does NOT refresh the
        mirrors (that happens after the previous batch resolves, so a retry
        of batch t-1 still sees t-1's adjacency)."""
        from repro.utils import pad_to
        with span("ripple.engine.route"):
            n = self.n
            d0 = int(self.state.H[0].shape[1])
            (a_src, a_dst, a_w), (d_src, d_dst, d_w) = \
                self.graph.apply_edges(*edge_columns(batch.edges))
            if self.bounded and n:
                self._kmax = max(self._kmax,
                                 float(self.graph.in_degree.max()))
            fa = np.array([f.vertex for f in batch.features], dtype=np.int32)
            fx = (np.stack([f.value for f in batch.features])
                  .astype(np.float32)
                  if batch.features else np.zeros((0, d0), np.float32))
            # last-writer-wins for duplicate feature updates
            if fa.size:
                uniq, last = np.unique(fa[::-1], return_index=True)
                fa, fx = uniq.astype(np.int32), fx[::-1][last]
            need = max(fa.size, a_src.size, d_src.size, 1)
            if need > self._bucket:
                self._bucket = self._next_bucket(need,
                                                 minimum=self.min_bucket)
            cap = self._bucket
            ints = np.full((5, cap), n, dtype=np.int32)
            ws = np.zeros((2, cap), dtype=np.float32)
            ints[0, :fa.size] = fa
            for row, vals in enumerate((a_src, a_dst, d_src, d_dst), 1):
                ints[row, :vals.size] = vals
            ws[0, :a_w.size] = a_w
            ws[1, :d_w.size] = d_w
            dev_batch = BatchDev(ints=jnp.asarray(ints), ws=jnp.asarray(ws),
                                 feat_val=jnp.asarray(pad_to(fx, cap)))
            out_rows = np.unique(np.concatenate((a_src, d_src)))
            in_rows = np.unique(np.concatenate((a_dst, d_dst))) \
                if self.in_mirror is not None else np.empty(0, np.int64)
        return dev_batch, out_rows, in_rows

    # -- dispatch / resolve ------------------------------------------------
    def _call(self, dev_batch: BatchDev, caps: tuple):
        """The family's jitted propagate with its positional arguments."""
        head = (self.workload, self.n, caps, self.params, self.state,
                self.out_mirror.device())
        if self.bounded:
            fn = propagate_bounded_donated if self.donate \
                else propagate_bounded
            return fn, head + (self.in_mirror.device(), dev_batch,
                               self._taus())
        if self.monotonic:
            fn = propagate_monotonic_donated if self.donate \
                else propagate_monotonic
            return fn, head + (self.in_mirror.device(), dev_batch)
        return (propagate_donated if self.donate else propagate), \
            head + (dev_batch,)

    def _run(self, dev_batch: BatchDev, caps: tuple):
        self.shape_misses.note("propagate", (
            caps, dev_batch.ints.shape[1], self.out_mirror.pool,
            self.in_mirror.pool if self.in_mirror is not None else 0))
        fn, args = self._call(dev_batch, caps)
        out = fn(*args, pallas=self.use_pallas, interpret=self.interpret)
        return out if len(out) == 5 else (*out, None)

    def compiled_propagate_text(self) -> str:
        """Optimized HLO of the propagate at the current rung-0 caps, so a
        caller can check which kernels the backend compiler placed in it
        (a Pallas kernel compiled for a TPU is a ``tpu_custom_call``)."""
        fn, args = self._call(self._sentinel_batch(), self._caps(0))
        return fn.lower(*args, pallas=self.use_pallas,
                        interpret=self.interpret).compile().as_text()

    def _dispatch(self, dev_batch: BatchDev) -> None:
        assert self._pending is None
        with span("ripple.engine.dispatch"):
            caps = self._caps(self._rung)
            new_state, final, overflow, sizes, stats = self._run(dev_batch,
                                                                 caps)
        # optimistic commit: on overflow the gated writes all dropped, so
        # these buffers hold the pre-batch values and the retry is safe
        self.state = new_state
        k_check = self.graph.in_degree.copy() if self.debug_checks else None
        self._pending = (overflow, final, sizes, stats, dev_batch, caps,
                         k_check)

    def _resolve(self) -> np.ndarray:
        """Lazily check the in-flight batch's overflow flag, retrying it
        with fitting caps if needed; returns its affected vertex ids."""
        if self._pending is None:
            return self._last_affected
        overflow, final, sizes, stats, dev_batch, caps, k_check = \
            self._pending
        while _overflowed(overflow):
            self.retries += 1
            sizes = np.asarray(sizes)
            over = np.any(sizes[:, :len(caps[0])] > np.asarray(caps), axis=0)
            for name, hit in zip(self.retries_by_channel, over):
                self.retries_by_channel[name] += int(hit)
            # the failed attempt reported what it actually needed; aim the
            # retry straight at fitting caps (truncated attempts may still
            # under-report downstream hops — the rung fallback guarantees
            # progress, and each retry fixes at least the first short cap)
            self._note_sizes(sizes)
            new_caps = self._caps(0)
            if new_caps == caps:
                self._rung += 1
                new_caps = self._caps(self._rung)
                if new_caps == caps:
                    # leave the engine diagnosable: the batch is lost but
                    # the state still holds the pre-batch values
                    self._pending = None
                    raise RuntimeError("bucket ladder saturated while still "
                                       "overflowing — graph inconsistency?")
            else:
                self._rung = 0
            with span("ripple.engine.retry"):
                new_state, final, overflow, sizes, stats = self._run(
                    dev_batch, new_caps)
            caps = new_caps
            self.state = new_state
        self._note_sizes(sizes)
        self._rung = 0
        with span("ripple.engine.device_wait"):
            f = np.asarray(final)
            stats = None if stats is None else jax.device_get(stats)
        self._last_affected = f[f < self.n].astype(np.int64)
        if stats is not None:
            if self.bounded:
                i_s = np.asarray(stats[0])
                f_s = np.asarray(stats[1])
                self.last_rows_reaggregated = int(i_s[0])
                self.last_deferred_rows = int(i_s[1])
                self.last_bound_violations = int(i_s[2])
                self.last_patch_events = 0
                self._eps = np.maximum(self._eps,
                                       f_s[:, 0].astype(np.float64))
                self._M = np.maximum(self._M, f_s[:, 1].astype(np.float64))
            else:
                s = np.asarray(stats)
                self.last_shrink_events = int(s[0])
                self.last_rows_reaggregated = int(s[1])
                self.last_dims_reaggregated = int(s[2])
                self.last_recover_hits = int(s[3])
                self.shrink_events += self.last_shrink_events
                self.dims_reaggregated += self.last_dims_reaggregated
                self.recover_hits += self.last_recover_hits
            self.rows_reaggregated += self.last_rows_reaggregated
        if k_check is not None:
            np.testing.assert_allclose(np.asarray(self.state.k), k_check,
                                       err_msg="device k drifted from host "
                                               "in-degree")
        if self._commit_log is not None:
            # committed-snapshot handle for the serving layer: the batch is
            # now irrevocably committed (overflow flag forced above, gated
            # writes landed), so gather exactly its final-layer rows to the
            # host before the *next* dispatch can donate these buffers away.
            # The gather index is padded to a power-of-two bucket so the jit
            # compiles O(log n) programs, not one per distinct frontier size
            with span("ripple.engine.commit_gather"):
                self._commits += 1
                aff = self._last_affected
                if not aff.size:
                    rows = np.zeros((0, int(self.state.H[-1].shape[1])),
                                    np.float32)
                elif self._host_backend:
                    # host backend: np.asarray is ~zero-copy, a device gather
                    # dispatch costs ~100x more than indexing on the host
                    rows = np.asarray(self.state.H[-1])[aff]
                else:
                    # accelerator: gather only the frontier rows, padding the
                    # index to a power-of-two bucket so the jit compiles
                    # O(log n) programs, not one per distinct frontier size
                    cap = self._next_bucket(aff.size)
                    self.shape_misses.note("commit_gather", cap)
                    idx = np.full(cap, aff[0], dtype=np.int64)
                    idx[:aff.size] = aff
                    rows = np.asarray(self.state.H[-1][jnp.asarray(idx)])
                    rows = rows[:aff.size]
                self._commit_log.append((self._commits, aff.copy(), rows))
        self._pending = None
        return self._last_affected

    # -- main entry --------------------------------------------------------
    def apply_batch(self, batch) -> np.ndarray:
        """Apply one routed batch; returns final-hop affected vertex ids.

        Synchronous by default.  With ``async_dispatch`` the host routing
        of this batch overlaps the device compute of the previous one and
        the return value is the *previous* batch's affected ids (one batch
        of pipeline latency; ``flush()``/``sync`` drain exactly).
        """
        dev_batch, out_rows, in_rows = self._route(batch)
        prev_affected = self._resolve()
        self.out_mirror.refresh_rows(out_rows)
        if self.in_mirror is not None:
            self.in_mirror.refresh_rows(in_rows)
        self._dispatch(dev_batch)
        if self.async_dispatch:
            return prev_affected
        return self._resolve()

    def flush(self) -> np.ndarray:
        """Drain the pipeline (resolve any in-flight batch)."""
        return self._resolve()

    # -- committed-snapshot handle (serving layer) -------------------------
    def enable_commit_log(self) -> None:
        """Start recording, per committed batch, the (affected ids, final-
        layer rows) patch — captured at resolve time, i.e. the instant the
        gated commit is known to have landed, so the serving layer can
        publish snapshots that trail the async pipeline without ever
        observing a half-committed batch."""
        self._resolve()          # batches already in flight predate the log
        self._commit_log = []

    def drain_commits(self) -> list:
        """Return + clear the commits recorded since the last drain, in
        commit order: ``[(commit_idx, affected_ids, H_final_rows)]``.  Does
        NOT force the in-flight batch — an async engine's latest batch
        appears only after its resolve (or ``flush``)."""
        if self._commit_log is None:
            raise RuntimeError("enable_commit_log() first")
        out, self._commit_log = self._commit_log, []
        return out

    # -- test helpers -----------------------------------------------------
    def host_H(self) -> list[np.ndarray]:
        self._resolve()
        return [np.array(h) for h in self.state.H]
