"""Owner-partitioned message passing (the RIPPLE §5 pattern as a *static*
GNN training primitive) — the §Perf hillclimb for collective-bound cells.

Baseline full-graph cells let GSPMD all-gather the whole [n, d] feature
matrix on every layer (edges are sharded, vertices replicated/gathered).
Here instead vertices are OWNER-partitioned over all mesh axes and each
edge's message is computed on its SOURCE owner ("strictly look-forward",
exactly the paper's push model), then routed to the destination owner with
ONE capacity-bounded all_to_all per layer — wire bytes drop from
O(n·d·layers) to O(edges_cut/P · d).

Everything is differentiable (all_to_all/scatter have transposes), so the
same primitive serves training.
"""
from __future__ import annotations

from typing import NamedTuple

import numpy as np
import jax
import jax.numpy as jnp

from jax.sharding import PartitionSpec as P

from repro.models.gnn.common import (cosine_cutoff, gaussian_rbf, init_mlp,
                                     mlp)
from repro.models.gnn.schnet import shifted_softplus


class PartEdges(NamedTuple):
    """Edges grouped by SOURCE owner; per-partition padded arrays."""

    src_local: jax.Array   # [Pp, e_cap] local src id (sentinel n_local)
    dst_global: jax.Array  # [Pp, e_cap] partition-contiguous global dst
    dist: jax.Array        # [Pp, e_cap] edge length (molecular archs)
    mask: jax.Array        # [Pp, e_cap]


def _pack_route(n_parts, n_local, cap, dst_global, vals):
    """Route (global dst, value) -> [Pp, cap] per-owner buffers (in-shard)."""
    n_pad = n_parts * n_local
    part = jnp.where(dst_global < n_pad, dst_global // n_local, n_parts)
    order = jnp.argsort(part)
    sp = part[order]
    sl = (dst_global % n_local)[order]
    sv = vals[order]
    first = jnp.searchsorted(sp, sp, side="left")
    pos = jnp.arange(sp.shape[0], dtype=jnp.int32) - first.astype(jnp.int32)
    ids = jnp.full((n_parts, cap), n_local, dtype=jnp.int32)
    ids = ids.at[sp, pos].set(sl.astype(jnp.int32), mode="drop")
    buf = jnp.zeros((n_parts, cap) + vals.shape[1:], vals.dtype)
    buf = buf.at[sp, pos].set(sv, mode="drop")
    counts = jax.ops.segment_sum(jnp.ones_like(sp), sp,
                                 num_segments=n_parts + 1)[:n_parts]
    return ids, buf, jnp.any(counts > cap)


def _aggregate(n_parts, n_local, halo_cap, dax, msgs, dst_global):
    """Push messages to dst owners; returns local aggregate [n_local, d]."""
    ids, buf, ovf = _pack_route(n_parts, n_local, halo_cap, dst_global, msgs)
    rid = jax.lax.all_to_all(ids, dax, 0, 0, tiled=True)
    rval = jax.lax.all_to_all(buf, dax, 0, 0, tiled=True)
    flat_id = rid.reshape(-1)
    flat_v = rval.reshape((-1,) + rval.shape[2:])
    agg = jax.ops.segment_sum(flat_v, flat_id, num_segments=n_local + 1)
    return agg[:n_local], ovf


def make_partitioned_schnet(mesh, *, n_local: int, e_cap: int, halo_cap: int,
                            d_in: int, d_hidden: int = 64,
                            n_interactions: int = 3, n_rbf: int = 300,
                            cutoff: float = 10.0, d_out: int = 47):
    """Returns (train_step fn, in_specs builder) for the partitioned cell."""
    data_axes = tuple(mesh.axis_names)
    import math
    n_parts = math.prod(mesh.shape[a] for a in data_axes)
    dax = data_axes if len(data_axes) > 1 else data_axes[0]

    def forward_local(params, feat, edges: PartEdges):
        h = mlp(params["embed"], feat)
        rbf = gaussian_rbf(edges.dist, n_rbf, cutoff)
        fcut = (cosine_cutoff(edges.dist, cutoff) * edges.mask)[:, None]
        ovf = jnp.zeros((), bool)
        for blk in params["blocks"]:
            W = mlp(blk["filter"], rbf, act=shifted_softplus) * fcut
            x = mlp(blk["in_proj"], h)
            src_c = jnp.minimum(edges.src_local, n_local - 1)
            msgs = x[src_c] * W
            agg, o = _aggregate(n_parts, n_local, halo_cap, dax, msgs,
                                edges.dst_global)
            ovf |= o
            h = h + mlp(blk["out_proj"], agg, act=shifted_softplus)
        return mlp(params["out"], h, act=shifted_softplus), ovf

    def local_loss(params, feat, edges, labels):
        # shard-local CE over owned vertices, psum'd to the global mean
        out, ovf = forward_local(params, feat[0], jax.tree.map(lambda a: a[0],
                                                               edges))
        logits = out.astype(jnp.float32)
        lse = jax.scipy.special.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(logits, labels[0][:, None], axis=-1)[:, 0]
        loss = jnp.sum(lse - gold)
        total = jax.lax.psum(loss, dax) / (n_parts * n_local)
        return total[None]

    edge_spec = PartEdges(src_local=P(data_axes, None),
                          dst_global=P(data_axes, None),
                          dist=P(data_axes, None), mask=P(data_axes, None))
    loss_sharded = jax.shard_map(
        local_loss, mesh=mesh,
        in_specs=(P(),  # params replicated (pytree-prefix spec)
                  P(data_axes, None, None), edge_spec, P(data_axes, None)),
        out_specs=P(None), check_vma=False)

    from repro.train.optim import adamw_update

    def train_step(params, opt_state, feat, edges, labels):
        def lf(p):
            return loss_sharded(p, feat, edges, labels)[0]

        loss, grads = jax.value_and_grad(lf)(params)
        params, opt_state = adamw_update(grads, opt_state, params, lr=1e-3)
        return params, opt_state, loss

    return train_step, edge_spec


# ---------------------------------------------------------------------------
# v2: host-PRE-ROUTED edges — iteration 2 of the §Perf hillclimb.  v1 packed
# messages by destination with an in-jit argsort+scatter, which cut
# collectives 7.5x but cost ~12x HBM traffic (REFUTED as a net win; see
# EXPERIMENTS.md §Perf).  Here the edge list arrives already grouped by
# (src owner, dst owner) — routing becomes a plain reshape + all_to_all.
# ---------------------------------------------------------------------------
class RoutedEdges(NamedTuple):
    """Edges grouped [src_part, dst_part, cap2] on the host."""

    src_local: jax.Array   # [Pp, Pp, cap2] (sentinel n_local)
    dst_local: jax.Array   # [Pp, Pp, cap2] local id at the DESTINATION owner
    dist: jax.Array        # [Pp, Pp, cap2]
    mask: jax.Array        # [Pp, Pp, cap2]


def make_partitioned_schnet_v2(mesh, *, n_local: int, cap2: int, d_in: int,
                               d_hidden: int = 64, n_interactions: int = 3,
                               n_rbf: int = 300, cutoff: float = 10.0,
                               d_out: int = 47):
    """Pre-routed push: per dst-partition message blocks are computed in
    place (no sort, no scatter) and exchanged with one all_to_all/layer."""
    data_axes = tuple(mesh.axis_names)
    import math
    n_parts = math.prod(mesh.shape[a] for a in data_axes)
    dax = data_axes if len(data_axes) > 1 else data_axes[0]

    def forward_local(params, feat, edges: RoutedEdges):
        h = mlp(params["embed"], feat)
        rbf = gaussian_rbf(edges.dist.reshape(-1), n_rbf, cutoff)
        fcut = (cosine_cutoff(edges.dist.reshape(-1), cutoff)
                * edges.mask.reshape(-1))[:, None]
        src_c = jnp.minimum(edges.src_local.reshape(-1), n_local - 1)
        for blk in params["blocks"]:
            W = mlp(blk["filter"], rbf, act=shifted_softplus) * fcut
            x = mlp(blk["in_proj"], h)
            msgs = (x[src_c] * W).reshape(n_parts, cap2, -1)
            r_msgs = jax.lax.all_to_all(msgs, dax, 0, 0, tiled=True)
            r_dst = jax.lax.all_to_all(edges.dst_local, dax, 0, 0, tiled=True)
            agg = jax.ops.segment_sum(
                r_msgs.reshape(n_parts * cap2, -1),
                r_dst.reshape(-1), num_segments=n_local + 1)[:n_local]
            h = h + mlp(blk["out_proj"], agg, act=shifted_softplus)
        return mlp(params["out"], h, act=shifted_softplus)

    def local_loss(params, feat, edges, labels):
        out = forward_local(params, feat[0],
                            jax.tree.map(lambda a: a[0], edges))
        logits = out.astype(jnp.float32)
        lse = jax.scipy.special.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(logits, labels[0][:, None], axis=-1)[:, 0]
        total = jax.lax.psum(jnp.sum(lse - gold), dax) / (n_parts * n_local)
        return total[None]

    edge_spec = RoutedEdges(src_local=P(data_axes, None, None),
                            dst_local=P(data_axes, None, None),
                            dist=P(data_axes, None, None),
                            mask=P(data_axes, None, None))
    loss_sharded = jax.shard_map(
        local_loss, mesh=mesh,
        in_specs=(P(), P(data_axes, None, None), edge_spec,
                  P(data_axes, None)),
        out_specs=P(None), check_vma=False)

    from repro.train.optim import adamw_update

    def train_step(params, opt_state, feat, edges, labels):
        def lf(p):
            return loss_sharded(p, feat, edges, labels)[0]

        loss, grads = jax.value_and_grad(lf)(params)
        params, opt_state = adamw_update(grads, opt_state, params, lr=1e-3)
        return params, opt_state, loss

    return train_step, edge_spec


def route_graph_for_push_v2(n, src, dst, dist, n_parts):
    """Host prep for v2: group edges by (src owner, dst owner) pairs."""
    n_local = -(-n // n_parts)
    so, do = src // n_local, dst // n_local
    cap2 = max(int(np.bincount(so * n_parts + do,
                               minlength=n_parts * n_parts).max()), 1)
    sl = np.full((n_parts, n_parts, cap2), n_local, dtype=np.int32)
    dl = np.full((n_parts, n_parts, cap2), n_local, dtype=np.int32)
    dd = np.zeros((n_parts, n_parts, cap2), dtype=np.float32)
    mk = np.zeros((n_parts, n_parts, cap2), dtype=np.float32)
    fill = np.zeros((n_parts, n_parts), dtype=np.int64)
    for e in range(src.shape[0]):
        p, q = so[e], do[e]
        i = fill[p, q]
        sl[p, q, i] = src[e] - p * n_local
        dl[p, q, i] = dst[e] - q * n_local
        dd[p, q, i] = dist[e]
        mk[p, q, i] = 1.0
        fill[p, q] += 1
    return RoutedEdges(src_local=jnp.asarray(sl), dst_local=jnp.asarray(dl),
                       dist=jnp.asarray(dd), mask=jnp.asarray(mk)), n_local, cap2


def partition_graph_for_push(n, src, dst, dist, n_parts):
    """Host-side prep for REAL runs: contiguous round-robin ownership,
    edges grouped by src owner, padded to the max per-partition count."""
    n_local = -(-n // n_parts)
    owner = src // n_local
    order = np.argsort(owner, kind="stable")
    src, dst, dist = src[order], dst[order], dist[order]
    counts = np.bincount(owner[order], minlength=n_parts)
    e_cap = int(counts.max())
    sl = np.full((n_parts, e_cap), n_local, dtype=np.int32)
    dg = np.full((n_parts, e_cap), n_parts * n_local, dtype=np.int32)
    dd = np.zeros((n_parts, e_cap), dtype=np.float32)
    mk = np.zeros((n_parts, e_cap), dtype=np.float32)
    off = 0
    for p in range(n_parts):
        c = counts[p]
        sl[p, :c] = (src[off:off + c] - p * n_local)
        dg[p, :c] = dst[off:off + c]
        dd[p, :c] = dist[off:off + c]
        mk[p, :c] = 1.0
        off += c
    return PartEdges(src_local=jnp.asarray(sl), dst_global=jnp.asarray(dg),
                     dist=jnp.asarray(dd), mask=jnp.asarray(mk)), n_local, e_cap
