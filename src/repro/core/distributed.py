"""Distributed RIPPLE (paper §5) on a (data, model) device mesh.

Mapping of the paper's MPI/BSP design onto JAX (DESIGN.md §2, §5):

 - Vertices are partitioned over the ``data`` mesh axis (paper: METIS over
   workers; here: LDG partitioner + partition-contiguous relabeling so
   owner(gid) = gid // n_local).
 - The feature dimension is sharded over the ``model`` axis: the UPDATE
   matmul runs row-parallel with a ``psum_scatter`` epilogue (tensor
   parallelism — the TPU-native replacement for the paper's single-threaded
   NumPy update).
 - Each BSP superstep (one hop): local frontier edge expansion -> pack
   per-destination-partition message buffers -> ``all_to_all`` halo exchange
   (paper: MPI mailbox stubs on remote workers) -> sort-compact mailboxes ->
   local apply.  Messages carry *deltas* only — this is the paper's ~70x
   communication reduction vs. the pull-based recompute baseline, which we
   also implement (``make_rc_propagate``) with its request/response
   embedding pulls.
 - All buffers have static capacities; overflow is detected exactly and the
   host retries on the next bucket (never silent truncation).

Warm-path contracts (the device engine's playbook, ported to the mesh):

 - **One collective per hop.**  Destination ids ride the halo exchange as
   an extra float32 channel (exact below 2^24), so the id+value pair costs
   a single fused ``all_to_all`` instead of two; the pull request path is
   fused the same way and its response is a single value-only collective.
 - **Gated commit.**  Every propagate runs to completion uncondition­ally,
   then commits its state outputs through one overflow gate reduced over
   *all* mesh axes (data AND model — per-dim pull overflow can differ
   between model shards, and a disagreeing gate would tear rows apart).
   On overflow the returned H/S/C bit-exactly equal the inputs, which is
   what makes ``donate_argnums`` retries safe: the host re-dispatches with
   the returned buffers and larger caps, never re-uploading state.
 - **Size feedback.**  Each hop reports its true needed sizes
   ``[rows, edges, halo, pull, pairs]`` (valid even when the attempt
   overflowed), so the host's cap ladder aims the retry directly at
   fitting power-of-two buckets and the steady state stops recompiling.
 - **Hierarchical multipod halo.**  With ``data_axes=("pod", "data")`` the
   invertible halo runs in two stages: an intra-pod shuffle to the
   destination's data slot, a combine of co-destined deltas, then the
   cross-pod exchange — so duplicate deltas are merged *before* they cross
   the expensive inter-pod links (``xpod`` reports slots before/after).

The routed-batch convention follows §5.2: an update is assigned to the
owner of its hop-0 (source) vertex; the in-degree vector (the "no-compute"
topology sync for cut edges) is refreshed globally by the host router.
"""
from __future__ import annotations

from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp

import numpy as np
from jax.sharding import PartitionSpec as P

from .aggregators import jnp_segment_extremum
from .device_engine import _compact_mailbox, _masked_pairs
from .graph import DynamicGraph
from .partition import Partitioning, ldg_partition
from .workloads import Workload, matmul_f32

_F32_EXACT = 1 << 24   # ids ride collectives as float32 below this


# ---------------------------------------------------------------------------
# Tensor-parallel UPDATE functions (row-parallel matmul + psum_scatter)
# ---------------------------------------------------------------------------
def tp_update(workload: Workload, params_l: dict, layer: int,
              h_prev: jax.Array, x: jax.Array, axis: str = "model") -> jax.Array:
    """UPDATE with d_in sharded over `axis`; returns d_out/M shard."""
    last = layer == workload.spec.n_layers - 1
    fam = workload.family

    def rp_matmul(a, w):  # row-parallel: a [R, d_in/M] @ w [d_in/M, d_out]
        return jax.lax.psum_scatter(matmul_f32(a, w), axis,
                                    scatter_dimension=1, tiled=True)

    if fam == "gc":
        out = rp_matmul(x, params_l["w"]) + params_l["b"]
    elif fam == "sage":
        out = rp_matmul(h_prev, params_l["w_self"]) \
            + rp_matmul(x, params_l["w_nbr"]) + params_l["b"]
    elif fam == "gin":
        z = (1.0 + params_l["eps"]) * h_prev + x
        h1 = jax.nn.relu(rp_matmul(z, params_l["w1"]) + params_l["b1"])
        out = rp_matmul(h1, params_l["w2"]) + params_l["b2"]
    else:
        raise ValueError(fam)
    return out if last else jax.nn.relu(out)


def tp_param_specs(workload: Workload) -> list[dict]:
    """shard_map in_specs for params: weights row-sharded, biases col-sharded."""
    specs = []
    for _ in range(workload.spec.n_layers):
        fam = workload.family
        if fam == "gc":
            specs.append({"w": P("model", None), "b": P("model")})
        elif fam == "sage":
            specs.append({"w_self": P("model", None), "w_nbr": P("model", None),
                          "b": P("model")})
        else:  # gin
            specs.append({"eps": P(), "w1": P("model", None), "b1": P("model"),
                          "w2": P("model", None), "b2": P("model")})
    return specs


# ---------------------------------------------------------------------------
# In-jit primitives
# ---------------------------------------------------------------------------
def _pack_by_partition(n_parts: int, n_local: int, cap: int,
                       dst_global: jax.Array, vals: jax.Array):
    """Route a (global-dst, value) stream into [P, cap] per-owner buffers.

    Returns (ids [P,cap] local-sentinel-padded, vals [P,cap,d], counts [P],
    overflow).  Sentinel dst (>= P*n_local) is dropped.
    """
    n_pad = n_parts * n_local
    part = jnp.where(dst_global < n_pad, dst_global // n_local, n_parts)
    return _pack_buckets(n_parts, cap, part, dst_global % n_local, n_local,
                         vals)


def _pack_buckets(n_buckets: int, cap: int, bucket: jax.Array,
                  key: jax.Array, key_sentinel: int, vals: jax.Array):
    """Route a (bucket, key, value) stream into ``[n_buckets, cap]`` buffers
    (``bucket == n_buckets`` drops the entry; key slots pad with
    ``key_sentinel``).

    The per-bucket slot of each entry is its running occurrence count,
    computed from a one-hot cumulative sum — no argsort, and crucially no
    permutation of the d-wide value payload (values scatter straight from
    their source position).  Entries keep stream order within a bucket,
    matching what a stable sort-by-bucket would produce.  The one-hot
    matrix is [N, n_buckets+1] ints, fine for mesh-sized bucket counts; a
    sort fallback covers the (unused today) many-bucket regime.
    """
    if n_buckets > 64:
        return _pack_buckets_sorted(n_buckets, cap, bucket, key,
                                    key_sentinel, vals)
    oh = (bucket[:, None]
          == jnp.arange(n_buckets + 1, dtype=bucket.dtype)[None, :])
    run = jnp.cumsum(oh.astype(jnp.int32), axis=0)
    pos = jnp.take_along_axis(run, bucket[:, None].astype(jnp.int32),
                              axis=1)[:, 0] - 1
    counts = run[-1, :n_buckets]
    overflow = jnp.any(counts > cap)
    keys = jnp.full((n_buckets, cap), key_sentinel, dtype=jnp.int32)
    keys = keys.at[bucket, pos].set(key.astype(jnp.int32), mode="drop")
    buf = jnp.zeros((n_buckets, cap) + vals.shape[1:], dtype=vals.dtype)
    buf = buf.at[bucket, pos].set(vals, mode="drop")
    return keys, buf, counts, overflow


def _pack_buckets_sorted(n_buckets: int, cap: int, bucket: jax.Array,
                         key: jax.Array, key_sentinel: int, vals: jax.Array):
    """Sort-based :func:`_pack_buckets` for bucket counts where the one-hot
    running-count matrix would dominate."""
    order = jnp.argsort(bucket)
    sb = bucket[order]
    sk = key[order]
    sv = vals[order]
    first_pos = jnp.searchsorted(sb, sb, side="left")
    pos = jnp.arange(sb.shape[0], dtype=jnp.int32) - first_pos.astype(jnp.int32)
    counts = jax.ops.segment_sum(jnp.ones_like(sb), sb,
                                 num_segments=n_buckets + 1)[:n_buckets]
    overflow = jnp.any(counts > cap)
    keys = jnp.full((n_buckets, cap), key_sentinel, dtype=jnp.int32)
    keys = keys.at[sb, pos].set(sk.astype(jnp.int32), mode="drop")
    buf = jnp.zeros((n_buckets, cap) + vals.shape[1:], dtype=vals.dtype)
    buf = buf.at[sb, pos].set(sv, mode="drop")
    return keys, buf, counts, overflow


def _compact(n: int, all_dst: jax.Array, all_val: jax.Array, r_cap: int):
    """Recipient compaction sized to the regime: the distributed mailbox is
    usually much larger than the per-shard row space (n_parts * halo_cap
    slots landing on n_local rows), where a presence mask + scatter-add is
    far cheaper than the sort in :func:`_compact_mailbox`; small mailboxes
    keep the sort (O(N log N), independent of n)."""
    if all_dst.shape[0] < n // 2:
        return _compact_mailbox(n, all_dst, all_val, r_cap)
    cl = jnp.minimum(all_dst, n)
    acc = jnp.zeros((n + 1,) + all_val.shape[1:], all_val.dtype).at[cl].add(
        all_val)
    mask = jnp.zeros((n + 1,), bool).at[cl].set(True)
    n_rec = mask[:n].sum()
    rec_idx = jnp.nonzero(mask[:n], size=r_cap, fill_value=n)[0].astype(
        jnp.int32)
    valid = (rec_idx < n).reshape((-1,) + (1,) * (all_val.ndim - 1))
    mailbox = jnp.where(valid, acc[jnp.minimum(rec_idx, n - 1)], 0)
    return rec_idx, mailbox, n_rec


def _per_hop(cap, n_hops: int) -> tuple:
    """Normalize a capacity knob (one int, or one per hop) to a tuple."""
    if isinstance(cap, (tuple, list)):
        if len(cap) != n_hops:
            raise ValueError(f"expected {n_hops} per-hop caps, got {cap}")
        return tuple(int(c) for c in cap)
    return (int(cap),) * n_hops


def _exchange(ids: jax.Array, vals: jax.Array, axis="data"):
    """BSP halo exchange: block p of my buffers goes to device p."""
    rid = jax.lax.all_to_all(ids, axis, split_axis=0, concat_axis=0, tiled=True)
    rval = jax.lax.all_to_all(vals, axis, split_axis=0, concat_axis=0, tiled=True)
    return rid, rval


def _exchange_fused(ids: jax.Array, vals: jax.Array, axis, fuse: bool):
    """Halo exchange as ONE fused collective: the id channel rides the value
    buffer as float32 (exact below 2^24 — ``fuse`` is the static guard).
    Falls back to the two-collective :func:`_exchange` above the id bound."""
    if not fuse:
        return _exchange(ids, vals, axis)
    packed = jnp.concatenate([ids[..., None].astype(vals.dtype), vals], axis=2)
    r = jax.lax.all_to_all(packed, axis, split_axis=0, concat_axis=0,
                           tiled=True)
    return r[..., 0].astype(jnp.int32), r[..., 1:]


def _pull_in_neighbors(n_parts: int, n_local: int, n_pad: int, dax, me,
                       h_l: jax.Array, in_csr: "DistCSR", aff_c: jax.Array,
                       degs: jax.Array, pull_cap: int, r_cap: int):
    """Ragged in-CSR expansion of the given rows + request/response pull of
    the (possibly remote) source embeddings — the shared machinery behind
    RC's pull-everything re-aggregation and the monotonic family's
    SHRINK-only re-aggregation requests.

    ``aff_c [r_cap]`` are clamped local row ids, ``degs [r_cap]`` their
    pull counts (0 skips a row).  Two collectives total: the request ships
    (id, slot) fused, the response ships values only (block layout is
    preserved by the tiled all_to_all round trip, so reply row p aligns
    position-wise with the requests packed for owner p).  Returns (got
    [pull_cap, d] pulled values aligned with the expansion, src_g
    [pull_cap] global source ids, fid [pull_cap] row slot per pulled edge,
    evalid [pull_cap], ew [pull_cap] edge weights, comm_req
    globally-summed remote request slots, needed true lane/bucket size,
    overflow).
    """
    csum = jnp.cumsum(degs)
    total = csum[-1]
    e = jnp.arange(pull_cap, dtype=jnp.int32)
    fid = jnp.minimum(jnp.searchsorted(csum, e, side="right").astype(jnp.int32),
                      r_cap - 1)
    off = e - (csum[fid] - degs[fid])
    evalid = e < total
    flat = jnp.where(evalid, in_csr.start[aff_c[fid]] + off, 0)
    src_g = jnp.where(evalid, in_csr.col[flat], n_pad)
    ew = in_csr.w[flat]

    # request/response: route src ids to owners, owners reply values
    req_ids, req_slot, counts, ovf = _pack_by_partition(
        n_parts, n_local, pull_cap, src_g,
        jnp.arange(pull_cap, dtype=jnp.float32)[:, None])
    comm_req = jax.lax.psum(counts.sum() - counts[me], dax)
    r_req, _ = _exchange_fused(req_ids, req_slot, dax, n_local < _F32_EXACT)
    vals_resp = h_l[jnp.minimum(r_req, n_local - 1)] \
        * (r_req < n_local)[..., None]
    # respond: send values straight back (reverse exchange, values only)
    back_vals = jax.lax.all_to_all(vals_resp, dax, split_axis=0,
                                   concat_axis=0, tiled=True)
    # place returned values into their pull slots (my original buffers)
    slot = req_slot[..., 0].astype(jnp.int32).reshape(-1)
    filled = (req_ids < n_local).reshape(-1)
    got = jnp.zeros((pull_cap,) + h_l.shape[1:], h_l.dtype)
    got = got.at[jnp.where(filled, slot, pull_cap)].set(
        back_vals.reshape((-1,) + back_vals.shape[2:]), mode="drop")
    needed = jnp.maximum(total, counts.max()).astype(jnp.int32)
    overflow = (total > pull_cap) | ovf
    return got, src_g, fid, evalid, ew, comm_req, needed, overflow


def _pull_in_neighbor_dims(n_parts: int, n_local: int, n_pad: int, dax, me,
                           h_l: jax.Array, in_csr: "DistCSR",
                           rows_c: jax.Array, dims: jax.Array,
                           degs: jax.Array, pull_cap: int, pd_cap: int):
    """Per-(row, dim) SHRINK re-aggregation pull — the dim-masked sibling of
    :func:`_pull_in_neighbors`.

    ``rows_c [pd_cap]`` are clamped local row ids of the (row, dim) pairs
    being re-derived, ``dims [pd_cap]`` their local feature dims, ``degs
    [pd_cap]`` the per-pair pull counts (0 skips a pair).  Each pulled lane
    requests ONE scalar ``H[src, dim]`` from the source's owner — the fused
    request slot carries (id, lane, dim), the response a single float32
    instead of a d_loc-wide row, which is where the shrink-pull comm drops
    from row-sized to dim-masked payloads.  Returns (got [pull_cap] scalar
    values, src_g [pull_cap] global source ids, fid [pull_cap] pair slot
    per lane, evalid [pull_cap], comm_req globally-summed remote request
    slots, needed true lane/bucket size, overflow).
    """
    csum = jnp.cumsum(degs)
    total = csum[-1]
    e = jnp.arange(pull_cap, dtype=jnp.int32)
    fid = jnp.minimum(jnp.searchsorted(csum, e, side="right").astype(jnp.int32),
                      pd_cap - 1)
    off = e - (csum[fid] - degs[fid])
    evalid = e < total
    flat = jnp.where(evalid, in_csr.start[rows_c[fid]] + off, 0)
    src_g = jnp.where(evalid, in_csr.col[flat], n_pad)
    dim_e = dims[fid]

    # request: route (lane, dim) to the owner of src_g; owners reply the
    # single requested scalar
    payload = jnp.stack([jnp.arange(pull_cap, dtype=jnp.float32),
                         dim_e.astype(jnp.float32)], axis=1)
    req_ids, req_pay, counts, ovf = _pack_by_partition(
        n_parts, n_local, pull_cap, src_g, payload)
    comm_req = jax.lax.psum(counts.sum() - counts[me], dax)
    r_req, r_pay = _exchange_fused(req_ids, req_pay, dax,
                                   n_local < _F32_EXACT)
    rdim = jnp.clip(r_pay[..., 1].astype(jnp.int32), 0, h_l.shape[1] - 1)
    scal = h_l[jnp.minimum(r_req, n_local - 1), rdim] * (r_req < n_local)
    back = jax.lax.all_to_all(scal[..., None], dax, split_axis=0,
                              concat_axis=0, tiled=True)
    slot = req_pay[..., 0].astype(jnp.int32).reshape(-1)
    filled = (req_ids < n_local).reshape(-1)
    got = jnp.zeros((pull_cap,), h_l.dtype)
    got = got.at[jnp.where(filled, slot, pull_cap)].set(
        back.reshape(-1), mode="drop")
    needed = jnp.maximum(total, counts.max()).astype(jnp.int32)
    overflow = (total > pull_cap) | ovf
    return got, src_g, fid, evalid, comm_req, needed, overflow


def _local_frontier_messages(n_local: int, n_pad: int, h_l: jax.Array,
                             col, w, start, length,
                             frontier: jax.Array, delta: jax.Array,
                             add_src, add_dst, add_w, del_src, del_dst, del_w,
                             *, weighted: bool, self_dep: bool, e_cap: int,
                             my_part: jax.Array):
    """Local-shard message stream (dsts in GLOBAL relabeled id space)."""
    f_cap = frontier.shape[0]
    degs = jnp.where(frontier < n_local,
                     length[jnp.minimum(frontier, n_local - 1)], 0)
    csum = jnp.cumsum(degs)
    total = csum[-1]
    e = jnp.arange(e_cap, dtype=jnp.int32)
    fid = jnp.minimum(jnp.searchsorted(csum, e, side="right").astype(jnp.int32),
                      f_cap - 1)
    row_begin = csum[fid] - degs[fid]
    off = e - row_begin
    vsrc = frontier[fid]
    flat = start[jnp.minimum(vsrc, n_local - 1)] + off
    evalid = e < total
    flat = jnp.where(evalid, flat, 0)
    edst = jnp.where(evalid, col[flat], n_pad)
    ew = w[flat] if weighted else jnp.ones(e_cap, dtype=h_l.dtype)
    evals = delta[fid] * (ew * evalid)[:, None]

    pos = jnp.full((n_local,), -1, dtype=jnp.int32)
    pos = pos.at[frontier].set(jnp.arange(f_cap, dtype=jnp.int32), mode="drop")

    def h_old(src):
        src_c = jnp.minimum(src, n_local - 1)
        h = h_l[src_c]
        slot = pos[src_c]
        return h - jnp.where((slot >= 0)[:, None], delta[jnp.maximum(slot, 0)], 0.0)

    aw = add_w if weighted else jnp.ones_like(add_w)
    dw = del_w if weighted else jnp.ones_like(del_w)
    a_val = h_old(add_src) * aw[:, None] * (add_src < n_local)[:, None]
    d_val = -h_old(del_src) * dw[:, None] * (del_src < n_local)[:, None]

    dsts = [edst, add_dst, del_dst]
    vals = [evals, a_val, d_val]
    if self_dep:
        self_g = jnp.where(frontier < n_local,
                           my_part * n_local + frontier, n_pad)
        dsts.append(self_g)
        vals.append(jnp.zeros_like(delta))
    return jnp.concatenate(dsts), jnp.concatenate(vals), total


# ---------------------------------------------------------------------------
# Distributed RIPPLE propagate (factory returns a jitted fn bound to a mesh)
# ---------------------------------------------------------------------------
class DistBatch(NamedTuple):
    feat_idx: jax.Array  # [P, Fc] local ids (sentinel n_local)
    feat_val: jax.Array  # [P, Fc, d0]
    add_src: jax.Array   # [P, Ac] local ids
    add_dst: jax.Array   # [P, Ac] GLOBAL relabeled ids (sentinel n_pad)
    add_w: jax.Array
    del_src: jax.Array
    del_dst: jax.Array
    del_w: jax.Array


class DistCSR(NamedTuple):
    col: jax.Array     # [P, pool] global relabeled dst ids
    w: jax.Array       # [P, pool]
    start: jax.Array   # [P, n_local]
    length: jax.Array  # [P, n_local]


def _gated_commit(ok, new, old):
    """Commit ``new`` when the globally-agreed gate holds, else bit-exactly
    return ``old`` — the overflow-retry contract under buffer donation."""
    return jax.tree.map(lambda a, b: jnp.where(ok, a, b), new, old)


def make_ripple_propagate(mesh, workload: Workload, n_local: int,
                          caps: tuple, halo_cap,
                          data_axes: tuple = ("data",), *,
                          donate: bool = False):
    """Build the jitted distributed propagate for a fixed geometry.

    ``data_axes`` lets the vertex-partition dimension span multiple mesh
    axes — e.g. ("pod", "data") partitions over 32 ways on the multi-pod
    mesh.  With exactly two data axes (and ids exact in float32) the halo
    runs hierarchically: intra-pod shuffle -> combine co-destined deltas ->
    cross-pod exchange, so duplicate deltas never cross the DCI.

    ``halo_cap`` may be one capacity or a per-hop tuple — early hops carry
    far fewer deltas than late ones, and the receive-side mailbox work
    scales with n_parts * halo_cap, so per-hop sizing matters.

    With ``donate=True`` the H/S state buffers are donated through the jit;
    the gated commit keeps overflow retries bit-exact (outputs == inputs).

    Returns ``(H, S, final, ovf, comm [L], sizes [L, 5], xpod [2])``.
    """
    import math
    n_parts = math.prod(mesh.shape[a] for a in data_axes)
    dax = data_axes if len(data_axes) > 1 else data_axes[0]
    allax = tuple(data_axes) + ("model",)
    n_pad = n_parts * n_local
    fuse = n_local < _F32_EXACT
    hier = len(data_axes) == 2 and n_pad < _F32_EXACT
    if hier:
        pod_ax, leaf_ax = data_axes
        Np, Nd = mesh.shape[pod_ax], mesh.shape[leaf_ax]
    spec = workload.spec
    L = spec.n_layers
    halo_caps = _per_hop(halo_cap, L)
    zero = jnp.zeros((), jnp.int32)

    def halo(dst_g, vals, me, hc):
        """One halo step at capacity ``hc``: returns (mdst local ids, mval,
        remote-slot count, xpod [before, after], needed bucket size,
        overflow)."""
        if not hier:
            ids, buf, counts, ovf = _pack_by_partition(
                n_parts, n_local, hc, dst_g, vals)
            rid, rval = _exchange_fused(ids, buf, dax, fuse)
            remote = counts.sum() - counts[me]
            return (rid.reshape(-1), rval.reshape((-1,) + rval.shape[2:]),
                    remote, jnp.zeros((2,), jnp.int32),
                    counts.max().astype(jnp.int32), ovf)
        me_p = jax.lax.axis_index(pod_ax)
        me_d = jax.lax.axis_index(leaf_ax)
        valid = dst_g < n_pad
        part = jnp.where(valid, dst_g // n_local, n_parts)
        cross_before = (valid & (part // Nd != me_p)).sum().astype(jnp.int32)
        # stage 1: intra-pod shuffle to the destination's data slot
        b1 = jnp.where(valid, part % Nd, Nd)
        k1, v1, c1, ovf = _pack_buckets(Nd, hc, b1, dst_g, n_pad, vals)
        r1, rv1 = _exchange_fused(k1, v1, leaf_ax, True)
        # combine co-destined deltas before they cross pods
        g1, m1, n1 = _compact(
            n_pad, r1.reshape(-1), rv1.reshape((-1,) + rv1.shape[2:]),
            hc)
        ovf |= n1 > hc
        # stage 2: cross-pod exchange to the destination's pod
        b2 = jnp.where(g1 < n_pad, g1 // (n_local * Nd), Np)
        k2, v2, c2, ovf2 = _pack_buckets(Np, hc, b2, g1 % n_local,
                                         n_local, m1)
        ovf |= ovf2
        r2, rv2 = _exchange_fused(k2, v2, pod_ax, True)
        intra = c1.sum() - c1[me_d]
        cross_after = (c2.sum() - c2[me_p]).astype(jnp.int32)
        needed = jnp.maximum(jnp.maximum(c1.max(), n1),
                             c2.max()).astype(jnp.int32)
        return (r2.reshape(-1), rv2.reshape((-1,) + rv2.shape[2:]),
                intra + cross_after,
                jnp.stack([cross_before, cross_after]), needed, ovf)

    def local_fn(params, H, S, k, csr: DistCSR, batch: DistBatch):
        # strip the leading data-axis block dim (=1 per shard)
        sq = lambda t: jax.tree.map(lambda a: a[0], t)
        H, S, k, csr, batch = sq(H), sq(S), sq(k), sq(csr), sq(batch)
        me = jax.lax.axis_index(dax)
        H_in, S_in = H, S

        # hop 0: feature updates (values arrive model-sharded)
        fv = batch.feat_idx
        old = H[0][jnp.minimum(fv, n_local - 1)]
        delta = (batch.feat_val - old) * (fv < n_local)[:, None]
        H = (H[0].at[fv].set(batch.feat_val, mode="drop"),) + H[1:]
        frontier = fv
        overflow = jnp.zeros((), bool)
        comm, sizes = [], []
        xpod = jnp.zeros((2,), jnp.int32)

        for l in range(L):
            r_cap, e_cap = caps[l]
            dst_g, vals, needed = _local_frontier_messages(
                n_local, n_pad, H[l], csr.col, csr.w,
                csr.start, csr.length, frontier, delta,
                batch.add_src, batch.add_dst, batch.add_w,
                batch.del_src, batch.del_dst, batch.del_w,
                weighted=spec.weighted, self_dep=spec.self_dependent,
                e_cap=e_cap, my_part=me)
            overflow |= needed > e_cap
            mdst, mval, remote, xp, h_need, ovf = halo(dst_g, vals, me,
                                                       halo_caps[l])
            overflow |= ovf
            xpod = xpod + xp
            # comm accounting: slots destined to OTHER partitions
            comm.append(jax.lax.psum(remote, dax))
            rec_idx, mailbox, n_rec = _compact(
                n_local, mdst, mval, r_cap)
            overflow |= n_rec > r_cap
            sizes.append(jnp.stack([n_rec.astype(jnp.int32),
                                    needed.astype(jnp.int32),
                                    h_need, zero, zero]))

            aff_c = jnp.minimum(rec_idx, n_local - 1)
            valid = (rec_idx < n_local)[:, None]
            S_rows = S[l + 1][aff_c] + mailbox
            S_next = S[l + 1].at[rec_idx].set(S_rows, mode="drop")
            if spec.aggregator == "mean":
                x = S_rows / jnp.maximum(k[aff_c], 1.0)[:, None]
            else:
                x = S_rows
            h_new = tp_update(workload, params[l], l, H[l][aff_c], x)
            delta = (h_new - H[l + 1][aff_c]) * valid
            H = H[: l + 1] + (H[l + 1].at[rec_idx].set(h_new, mode="drop"),) \
                + H[l + 2:]
            S = S[: l + 1] + (S_next,) + S[l + 2:]
            frontier = rec_idx

        # gated commit: one overflow verdict over EVERY mesh axis; on
        # overflow the outputs bit-exactly equal the inputs (donation-safe)
        ovf_g = jax.lax.psum(overflow.astype(jnp.float32), allax)
        ok = ovf_g == 0
        H = _gated_commit(ok, H, H_in)
        S = _gated_commit(ok, S, S_in)
        final = jnp.where(ok, frontier, n_local)
        sz = jax.lax.pmax(jnp.stack(sizes), allax)
        add_back = lambda t: jax.tree.map(lambda a: a[None], t)
        return (add_back(H), add_back(S), add_back(final),
                ovf_g, jnp.stack(comm), sz, jax.lax.psum(xpod, dax))

    state_spec_h = tuple(P(dax, None, "model") for _ in range(L + 1))
    state_spec_s = (P(dax, None),) + tuple(P(dax, None, "model")
                                           for _ in range(L))
    batch_spec = DistBatch(
        feat_idx=P(dax, None), feat_val=P(dax, None, "model"),
        add_src=P(dax, None), add_dst=P(dax, None), add_w=P(dax, None),
        del_src=P(dax, None), del_dst=P(dax, None), del_w=P(dax, None))
    csr_spec = DistCSR(col=P(dax, None), w=P(dax, None),
                       start=P(dax, None), length=P(dax, None))
    fn = jax.shard_map(
        local_fn, mesh=mesh,
        in_specs=(tp_param_specs(workload), state_spec_h, state_spec_s,
                  P(dax, None), csr_spec, batch_spec),
        out_specs=(state_spec_h, state_spec_s, P(dax, None), P(), P(), P(),
                   P()),
        check_vma=False)
    return jax.jit(fn, donate_argnums=(1, 2)) if donate else jax.jit(fn)


# ---------------------------------------------------------------------------
# Distributed monotonic (max/min) propagation: candidate-extremum mailboxes
# + shrink re-aggregation pulls (see core/aggregators.py for the algebra)
# ---------------------------------------------------------------------------
def make_monotonic_propagate(mesh, workload: Workload, n_local: int,
                             caps: tuple, halo_cap, pull_cap: int,
                             pd_cap: int = 0,
                             data_axes: tuple = ("data",), *,
                             rc: bool = False, donate: bool = False):
    """Distributed GROW/SHRINK propagation for max/min workloads.

    Mailboxes ship *candidate extrema* (value + global source id + delete
    flag) to the owner of each destination; the owner classifies every
    message against its tracked (S, C) rows at per-(row, dim) granularity.
    Shrunk cells first run the re-cover probe (a candidate that
    ties-or-beats the lost extremum re-witnesses the dim pull-free), then
    the survivors re-aggregate via per-dim request/response pulls: each
    pulled lane fetches ONE scalar ``H[src, dim]`` instead of a d_loc-wide
    row (``pd_cap`` bounds the (row, dim) pairs per hop, ``pull_cap`` the
    pulled elements).  Because the feature dims are sharded over the model
    axis, each model shard re-derives exactly its own shrunk dims — no
    cross-model reduction is needed for the shrink masks, only for the
    row-level propagation decisions *and the overflow gate* (a per-dim
    pull can overflow on one model shard only; the gated commit must
    agree).  This is the communication contrast ``dist_bench`` measures
    against ``rc=True`` (the unfiltered baseline: every affected row
    re-aggregates a full row via the row-sized pull path and the frontier
    never filters, i.e. distributed RC for the monotonic family).

    Contributor ids ride the halo exchange as float32 payload channels, so
    the relabeled id space must stay below 2^24 (exact float32 integers).

    Returns ``(H, S, C, final, ovf, comm [3L], sstats [4], sizes [L, 5])``.
    """
    import math
    n_parts = math.prod(mesh.shape[a] for a in data_axes)
    dax = data_axes if len(data_axes) > 1 else data_axes[0]
    allax = tuple(data_axes) + ("model",)
    n_pad = n_parts * n_local
    if n_pad >= _F32_EXACT:
        raise ValueError(
            f"monotonic propagate: padded id space {n_pad} exceeds 2^24 — "
            "contributor ids ride the halo as float32 and would lose "
            "exactness; shard the graph over more partitions")
    spec = workload.spec
    agg = workload.agg
    sign = agg.sign
    L = spec.n_layers
    halo_caps = _per_hop(halo_cap, L)

    def local_fn(params, H, S, C, k, out_csr: DistCSR, in_csr: DistCSR,
                 batch: DistBatch):
        sq = lambda t: jax.tree.map(lambda a: a[0], t)
        H, S, C, k, out_csr, in_csr, batch = (
            sq(H), sq(S), sq(C), sq(k), sq(out_csr), sq(in_csr), sq(batch))
        me = jax.lax.axis_index(dax)
        H_in, S_in, C_in = H, S, C

        # hop 0: feature updates; no-op writes are filtered out immediately
        fv = batch.feat_idx
        old = H[0][jnp.minimum(fv, n_local - 1)]
        # value-dependent decisions must agree across MODEL shards (each
        # holds d/M dims): reduce "changed in any dim" over the model axis
        changed0 = jax.lax.psum(
            (jnp.any(batch.feat_val != old, axis=1) & (fv < n_local)
             ).astype(jnp.float32), "model") > 0
        H = (H[0].at[fv].set(batch.feat_val, mode="drop"),) + H[1:]
        frontier = fv if rc else jnp.where(changed0, fv, n_local)
        overflow = jnp.zeros((), bool)
        comm, sizes = [], []
        n_shrink = jnp.zeros((), jnp.float32)   # SHRINK-classified messages
        n_reagg = jnp.zeros((), jnp.float32)    # rows re-aggregated
        n_dims = jnp.zeros((), jnp.float32)     # (row, dim) cells gathered
        n_recover = jnp.zeros((), jnp.float32)  # probe-recovered cells

        for l in range(L):
            r_cap, e_cap = caps[l]
            d_loc = H[l].shape[1]

            # ---- local frontier out-edge expansion (global dst ids) ------
            f_cap = frontier.shape[0]
            degs = jnp.where(frontier < n_local,
                             out_csr.length[jnp.minimum(frontier, n_local - 1)], 0)
            csum = jnp.cumsum(degs)
            total = csum[-1]
            overflow |= total > e_cap
            e = jnp.arange(e_cap, dtype=jnp.int32)
            fid = jnp.minimum(
                jnp.searchsorted(csum, e, side="right").astype(jnp.int32),
                f_cap - 1)
            off = e - (csum[fid] - degs[fid])
            vsrc = frontier[fid]
            evalid = e < total
            flat = jnp.where(evalid,
                             out_csr.start[jnp.minimum(vsrc, n_local - 1)] + off,
                             0)
            edst_g = jnp.where(evalid, out_csr.col[flat], n_pad)
            esrc_l = jnp.where(evalid, vsrc, n_local)

            # ---- unified message stream (frontier+adds: cand&probe;
            #      dels: probe-only) with payload [val, src_g, is_del] ------
            dst_g = jnp.concatenate([edst_g, batch.add_dst, batch.del_dst])
            src_l = jnp.concatenate([esrc_l, batch.add_src, batch.del_src])
            n_cand = e_cap + batch.add_src.shape[0]
            is_del = (jnp.arange(dst_g.shape[0]) >= n_cand).astype(jnp.float32)
            mvalid = (src_l < n_local) & (dst_g < n_pad)
            src_g = jnp.where(mvalid, me * n_local + src_l, n_pad)
            vals = H[l][jnp.minimum(src_l, n_local - 1)]
            payload = jnp.concatenate(
                [vals, src_g[:, None].astype(jnp.float32), is_del[:, None]],
                axis=1)
            dst_g = jnp.where(mvalid, dst_g, n_pad)

            ids, buf, counts, ovf = _pack_by_partition(
                n_parts, n_local, halo_caps[l], dst_g, payload)
            overflow |= ovf
            halo_remote = counts.sum() - counts[me]
            h_need = counts.max().astype(jnp.int32)
            rid, rpay = _exchange_fused(ids, buf, dax, True)
            mdst = rid.reshape(-1)
            rpay = rpay.reshape(-1, d_loc + 2)
            rval_ms = sign * rpay[:, :d_loc]
            rsrc_g = rpay[:, d_loc].astype(jnp.int32)
            rdel = rpay[:, d_loc + 1] > 0.5
            rvalid = mdst < n_local

            # ---- affected rows (+ frontier for self-dependence) ----------
            all_dst = jnp.concatenate([mdst, frontier]) \
                if spec.self_dependent else mdst
            rec_idx, _, n_rec = _compact(
                n_local, all_dst, jnp.zeros((all_dst.shape[0], 1), H[l].dtype),
                r_cap)
            overflow |= n_rec > r_cap
            aff_c = jnp.minimum(rec_idx, n_local - 1)
            pos = jnp.full((n_local + 1,), r_cap, dtype=jnp.int32)
            pos = pos.at[rec_idx].set(jnp.arange(r_cap, dtype=jnp.int32),
                                      mode="drop")
            slot = jnp.where(rvalid, pos[jnp.minimum(mdst, n_local)], r_cap)

            # ---- per-(message, local dim) SHRINK classification ----------
            S_pre_rows = S[l + 1][aff_c]
            C_pre_rows = C[l + 1][aff_c]
            S_dst_ms = sign * S[l + 1][jnp.minimum(mdst, n_local - 1)]
            C_dst = C[l + 1][jnp.minimum(mdst, n_local - 1)]
            covered = C_dst == rsrc_g[:, None]
            gone = rdel[:, None] | (S_dst_ms > rval_ms)
            dim_shrink = covered & gone & rvalid[:, None]
            # message-level stat: ANY of the full d dims (spread over the
            # model shards) lost its covering contribution
            shrink_full = jax.lax.psum(
                jnp.any(dim_shrink, axis=1).astype(jnp.float32), "model") > 0
            n_shrink = n_shrink + shrink_full.sum()

            # ---- GROW candidate extremum + witnesses (feeds the probe) ---
            is_cand = rvalid & ~rdel
            cslot = jnp.where(is_cand, slot, r_cap)
            cand_S, cand_C = jnp_segment_extremum(
                agg, rpay[:, :d_loc], cslot, r_cap, rsrc_g, small_ids=True)

            real_row = rec_idx < n_local
            if rc:
                # unfiltered baseline: every affected row re-aggregates its
                # FULL row through the row-sized pull path
                row_shrink = real_row
                pdegs = jnp.where(row_shrink, in_csr.length[aff_c], 0)
                got, psrc_g, pfid, pvalid, _ew, comm_req, p_need, p_ovf = \
                    _pull_in_neighbors(n_parts, n_local, n_pad, dax, me,
                                       H[l], in_csr, aff_c, pdegs,
                                       pull_cap, r_cap)
                overflow |= p_ovf
                pd_need = jnp.zeros((), jnp.int32)
                pseg = jnp.where(pvalid, pfid, r_cap)
                S_sh, C_sh = jnp_segment_extremum(agg, got, pseg, r_cap,
                                                  psrc_g, small_ids=True)
                base_S = jnp.where(row_shrink[:, None], S_sh, S_pre_rows)
                base_C = jnp.where(row_shrink[:, None], C_sh, C_pre_rows)
                n_rows_re = row_shrink.sum().astype(jnp.float32)
                n_reagg = n_reagg + n_rows_re
                n_dims = n_dims + jax.lax.psum(n_rows_re * d_loc, "model")
                # row-sized responses: one d_loc-wide value row per request
                pull_req, pull_resp = (jax.lax.psum(comm_req, "model"),
                                       jax.lax.psum(comm_req * d_loc,
                                                    "model"))
            else:
                # each model shard owns its d_loc dims outright: the shrink
                # mask, probe, and pulls are all shard-local — only the
                # row-level frontier decision below crosses the model axis
                row_dim = jax.ops.segment_max(
                    dim_shrink.astype(jnp.int32), slot,
                    num_segments=r_cap + 1)[:r_cap] > 0
                recovered = row_dim & (sign * cand_S >= sign * S_pre_rows)
                need = row_dim & ~recovered & real_row[:, None]
                n_recover = n_recover + jax.lax.psum(
                    recovered.sum().astype(jnp.float32), "model")
                n_pairs = need.sum()
                overflow |= n_pairs > pd_cap
                pd_need = n_pairs.astype(jnp.int32)
                n_dims = n_dims + jax.lax.psum(
                    n_pairs.astype(jnp.float32), "model")
                n_reagg = n_reagg + (jax.lax.psum(
                    jnp.any(need, axis=1).astype(jnp.float32), "model")
                    > 0).sum()

                pr, pdim = _masked_pairs(need, pd_cap, r_cap)
                rows_pair = aff_c[jnp.minimum(pr, r_cap - 1)]
                pdegs = jnp.where(pr < r_cap, in_csr.length[rows_pair], 0)
                got, psrc_g, pfid, pvalid, comm_req, p_need, p_ovf = \
                    _pull_in_neighbor_dims(n_parts, n_local, n_pad, dax, me,
                                           H[l], in_csr, rows_pair, pdim,
                                           pdegs, pull_cap, pd_cap)
                overflow |= p_ovf
                pseg = jnp.where(pvalid, pfid, pd_cap)
                S_pair, C_pair = jnp_segment_extremum(
                    agg, got, pseg, pd_cap, psrc_g, small_ids=True)
                base_S = S_pre_rows.at[pr, pdim].set(S_pair, mode="drop")
                base_C = C_pre_rows.at[pr, pdim].set(C_pair, mode="drop")
                # dim-masked responses: one scalar per request
                pull_req, pull_resp = (jax.lax.psum(comm_req, "model"),
                                       jax.lax.psum(comm_req, "model"))

            # comm accounting, three slots per hop: candidate-halo traffic
            # (paid by both modes), re-aggregation pull requests, and pull
            # response payload in scalar units — the row-sized vs
            # dim-masked contrast dist_bench measures
            comm.append(jax.lax.psum(halo_remote, dax))
            comm.append(pull_req)
            comm.append(pull_resp)
            sizes.append(jnp.stack([n_rec.astype(jnp.int32),
                                    total.astype(jnp.int32),
                                    h_need, p_need, pd_need]))

            # ---- GROW: fold the candidate extremum in (elementwise) ------
            cand_wins = (sign * cand_S >= sign * base_S) & (cand_C >= 0)
            S_new = jnp.where(cand_wins, cand_S, base_S)
            C_new = jnp.where(cand_wins, cand_C, base_C)

            # ---- apply + (filtered) propagation --------------------------
            x = agg.normalize(S_new, k[aff_c], xp=jnp)
            h_new = tp_update(workload, params[l], l, H[l][aff_c], x)
            changed = jax.lax.psum(
                (jnp.any(h_new != H[l + 1][aff_c], axis=1)
                 & (rec_idx < n_local)).astype(jnp.float32), "model") > 0
            S = S[: l + 1] + (S[l + 1].at[rec_idx].set(S_new, mode="drop"),) \
                + S[l + 2:]
            C = C[: l + 1] + (C[l + 1].at[rec_idx].set(C_new, mode="drop"),) \
                + C[l + 2:]
            H = H[: l + 1] + (H[l + 1].at[rec_idx].set(h_new, mode="drop"),) \
                + H[l + 2:]
            frontier = rec_idx if rc else jnp.where(changed, rec_idx, n_local)

        # gated commit: the verdict reduces over data AND model axes (a
        # per-dim pull can overflow on a single model shard; all shards
        # must agree or rows would tear across the model dimension)
        ovf_g = jax.lax.psum(overflow.astype(jnp.float32), allax)
        ok = ovf_g == 0
        H = _gated_commit(ok, H, H_in)
        S = _gated_commit(ok, S, S_in)
        C = _gated_commit(ok, C, C_in)
        final = jnp.where(ok, frontier, n_local)
        sz = jax.lax.pmax(jnp.stack(sizes), allax)
        shrink_stats = jax.lax.psum(
            jnp.stack([n_shrink, n_reagg, n_dims, n_recover]), dax)
        add_back = lambda t: jax.tree.map(lambda a: a[None], t)
        return (add_back(H), add_back(S), add_back(C), add_back(final),
                ovf_g, jnp.stack(comm), shrink_stats, sz)

    state_spec_h = tuple(P(dax, None, "model") for _ in range(L + 1))
    state_spec_s = (P(dax, None),) + tuple(P(dax, None, "model")
                                           for _ in range(L))
    batch_spec = DistBatch(
        feat_idx=P(dax, None), feat_val=P(dax, None, "model"),
        add_src=P(dax, None), add_dst=P(dax, None), add_w=P(dax, None),
        del_src=P(dax, None), del_dst=P(dax, None), del_w=P(dax, None))
    csr_spec = DistCSR(col=P(dax, None), w=P(dax, None),
                       start=P(dax, None), length=P(dax, None))
    fn = jax.shard_map(
        local_fn, mesh=mesh,
        in_specs=(tp_param_specs(workload), state_spec_h, state_spec_s,
                  state_spec_s, P(dax, None), csr_spec, csr_spec, batch_spec),
        out_specs=(state_spec_h, state_spec_s, state_spec_s, P(dax, None),
                   P(), P(), P(), P()),
        check_vma=False)
    return jax.jit(fn, donate_argnums=(1, 2, 3)) if donate else jax.jit(fn)


# ---------------------------------------------------------------------------
# Distributed layer-wise recompute baseline ("RC", pull-based — paper fig 12)
# ---------------------------------------------------------------------------
def make_rc_propagate(mesh, workload: Workload, n_local: int,
                      caps: tuple, halo_cap, pull_cap: int,
                      data_axes: tuple = ("data",), *,
                      donate: bool = False):
    """Distributed RC: frontier ids are exchanged, then every affected vertex
    PULLS all its in-neighbor embeddings (request/response all_to_all pair) —
    the communication-heavy pattern the paper measures ~70x worse.

    Returns ``(H, S, final, ovf, comm [L], sizes [L, 5])``.
    """
    import math
    n_parts = math.prod(mesh.shape[a] for a in data_axes)
    dax = data_axes if len(data_axes) > 1 else data_axes[0]
    allax = tuple(data_axes) + ("model",)
    n_pad = n_parts * n_local
    fuse = n_local < _F32_EXACT
    spec = workload.spec
    L = spec.n_layers
    halo_caps = _per_hop(halo_cap, L)
    zero = jnp.zeros((), jnp.int32)

    def local_fn(params, H, S, k, out_csr: DistCSR, in_csr: DistCSR,
                 batch: DistBatch):
        sq = lambda t: jax.tree.map(lambda a: a[0], t)
        H, S, k, out_csr, in_csr, batch = (sq(H), sq(S), sq(k), sq(out_csr),
                                           sq(in_csr), sq(batch))
        me = jax.lax.axis_index(dax)
        H_in, S_in = H, S

        fv = batch.feat_idx
        H = (H[0].at[fv].set(batch.feat_val, mode="drop"),) + H[1:]
        frontier = fv
        overflow = jnp.zeros((), bool)
        comm, sizes = [], []

        for l in range(L):
            r_cap, e_cap = caps[l]
            # --- frontier id expansion (no values) ------------------------
            dst_g, vals, needed = _local_frontier_messages(
                n_local, n_pad, jnp.zeros((n_local, 1), H[l].dtype),
                out_csr.col, out_csr.w, out_csr.start,
                out_csr.length, frontier,
                jnp.zeros((frontier.shape[0], 1), H[l].dtype),
                batch.add_src, batch.add_dst,
                jnp.zeros_like(batch.add_w), batch.del_src, batch.del_dst,
                jnp.zeros_like(batch.del_w),
                weighted=False, self_dep=spec.self_dependent,
                e_cap=e_cap, my_part=me)
            overflow |= needed > e_cap
            ids, buf, counts, ovf = _pack_by_partition(
                n_parts, n_local, halo_caps[l], dst_g, vals)
            overflow |= ovf
            comm_ids = jax.lax.psum(counts.sum() - counts[me], dax)
            rid, _ = _exchange_fused(ids, buf, dax, fuse)
            rec_idx, _, n_rec = _compact(
                n_local, rid.reshape(-1),
                jnp.zeros((rid.size, 1), H[l].dtype), r_cap)
            overflow |= n_rec > r_cap

            # --- pull ALL in-neighbors of affected vertices ----------------
            aff_c = jnp.minimum(rec_idx, n_local - 1)
            degs = jnp.where(rec_idx < n_local, in_csr.length[aff_c], 0)
            got, src_g, fid, evalid, ew, comm_req, p_need, p_ovf = \
                _pull_in_neighbors(n_parts, n_local, n_pad, dax, me, H[l],
                                   in_csr, aff_c, degs, pull_cap, r_cap)
            overflow |= p_ovf
            if not spec.weighted:
                ew = jnp.ones(pull_cap, H[l].dtype)
            comm_resp = comm_req  # one value per requested id comes back
            comm.append(comm_ids + comm_req + comm_resp)
            sizes.append(jnp.stack([n_rec.astype(jnp.int32),
                                    needed.astype(jnp.int32),
                                    counts.max().astype(jnp.int32),
                                    p_need, zero]))

            # segment-sum pulled values into S rows of affected vertices
            seg = jnp.where(evalid, fid, r_cap)
            S_rows = jax.ops.segment_sum(got * ew[:, None], seg,
                                         num_segments=r_cap + 1)[:r_cap]
            valid = (rec_idx < n_local)[:, None]
            S_next = S[l + 1].at[rec_idx].set(S_rows, mode="drop")
            if spec.aggregator == "mean":
                x = S_rows / jnp.maximum(k[aff_c], 1.0)[:, None]
            else:
                x = S_rows
            h_new = tp_update(workload, params[l], l, H[l][aff_c], x)
            H = H[: l + 1] + (H[l + 1].at[rec_idx].set(h_new, mode="drop"),) \
                + H[l + 2:]
            S = S[: l + 1] + (S_next,) + S[l + 2:]
            frontier = rec_idx

        ovf_g = jax.lax.psum(overflow.astype(jnp.float32), allax)
        ok = ovf_g == 0
        H = _gated_commit(ok, H, H_in)
        S = _gated_commit(ok, S, S_in)
        final = jnp.where(ok, frontier, n_local)
        sz = jax.lax.pmax(jnp.stack(sizes), allax)
        add_back = lambda t: jax.tree.map(lambda a: a[None], t)
        return (add_back(H), add_back(S), add_back(final), ovf_g,
                jnp.stack(comm), sz)

    L_ = L
    state_spec_h = tuple(P(dax, None, "model") for _ in range(L_ + 1))
    state_spec_s = (P(dax, None),) + tuple(P(dax, None, "model")
                                           for _ in range(L_))
    batch_spec = DistBatch(
        feat_idx=P(dax, None), feat_val=P(dax, None, "model"),
        add_src=P(dax, None), add_dst=P(dax, None), add_w=P(dax, None),
        del_src=P(dax, None), del_dst=P(dax, None), del_w=P(dax, None))
    csr_spec = DistCSR(col=P(dax, None), w=P(dax, None),
                       start=P(dax, None), length=P(dax, None))
    fn = jax.shard_map(
        local_fn, mesh=mesh,
        in_specs=(tp_param_specs(workload), state_spec_h, state_spec_s,
                  P(dax, None), csr_spec, csr_spec, batch_spec),
        out_specs=(state_spec_h, state_spec_s, P(dax, None), P(), P(), P()),
        check_vma=False)
    return jax.jit(fn, donate_argnums=(1, 2)) if donate else jax.jit(fn)
