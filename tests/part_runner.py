"""Partitioned push-based SchNet == dense SchNet (8 virtual devices)."""
import os
import sys

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import numpy as np  # noqa: E402
import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from repro.core.graph import erdos_renyi  # noqa: E402
from repro.models.gnn.common import GraphBatch  # noqa: E402
from repro.models.gnn.partitioned import (make_partitioned_schnet,  # noqa: E402
                                          partition_graph_for_push)
from repro.models.gnn.schnet import init_schnet, schnet_forward  # noqa: E402
from repro.train.optim import adamw_init  # noqa: E402


def main():
    n, m, d_in, d_out = 64, 400, 12, 5
    P_ = 8
    src, dst, _ = erdos_renyi(n, m, seed=0)
    rng = np.random.default_rng(0)
    pos = rng.normal(size=(n, 3)).astype(np.float32) * 2
    feat = rng.normal(size=(n, d_in)).astype(np.float32)
    dist = np.sqrt(((pos[src] - pos[dst]) ** 2).sum(-1) + 1e-12).astype(np.float32)

    hp = dict(d_hidden=16, n_interactions=2, n_rbf=20, cutoff=6.0)
    params = init_schnet(jax.random.PRNGKey(0), d_in=d_in, d_out=d_out, **hp)

    # dense reference
    g = GraphBatch(node_feat=jnp.asarray(feat), src=jnp.asarray(src, jnp.int32),
                   dst=jnp.asarray(dst, jnp.int32),
                   edge_mask=jnp.ones(src.shape[0]),
                   positions=jnp.asarray(pos))
    ref = np.asarray(schnet_forward(params, g, n_rbf=20, cutoff=6.0))

    # partitioned
    mesh = jax.make_mesh((8,), ("data",),
                         axis_types=(jax.sharding.AxisType.Auto,))
    edges, n_local, e_cap = partition_graph_for_push(n, src, dst, dist, P_)
    step, edge_spec = make_partitioned_schnet(
        mesh, n_local=n_local, e_cap=e_cap, halo_cap=m, d_in=d_in,
        d_out=d_out, **hp)
    feat_p = jnp.asarray(feat.reshape(P_, n_local, d_in))
    labels = jnp.asarray(rng.integers(0, d_out, size=(P_, n_local)), jnp.int32)
    opt = adamw_init(params)

    # check the forward through the loss: compare loss value against a
    # dense-computed CE over the same logits
    p2, o2, loss = jax.jit(step)(params, opt, feat_p, edges, labels)
    logits = ref.astype(np.float64)
    lse = np.log(np.exp(logits - logits.max(-1, keepdims=True)).sum(-1)) + \
        logits.max(-1)
    gold = logits[np.arange(n), np.asarray(labels).reshape(-1)]
    ref_loss = float(np.mean(lse - gold))
    err = abs(float(loss) - ref_loss)
    assert err < 1e-3, (float(loss), ref_loss)
    print(f"OK partitioned-schnet loss={float(loss):.5f} ref={ref_loss:.5f}")

    # v2: host-pre-routed edges, same exactness
    from repro.models.gnn.partitioned import (make_partitioned_schnet_v2,
                                              route_graph_for_push_v2)
    edges2, n_local2, cap2 = route_graph_for_push_v2(n, src, dst, dist, P_)
    step2, _ = make_partitioned_schnet_v2(
        mesh, n_local=n_local2, cap2=cap2, d_in=d_in, d_out=d_out, **hp)
    p3, o3, loss2 = jax.jit(step2)(params, opt, feat_p, edges2, labels)
    err2 = abs(float(loss2) - ref_loss)
    assert err2 < 1e-3, (float(loss2), ref_loss)
    print(f"OK partitioned-schnet-v2 loss={float(loss2):.5f} ref={ref_loss:.5f}")


if __name__ == "__main__":
    main()
