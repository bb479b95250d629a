"""The GNN inference workloads: the paper's five (§7.1.1) plus monotonic.

GC-S   GraphConv + sum            h^l = relu(W_l x^l + b_l)
GS-S   GraphSAGE + sum            h^l = relu(W_self h^{l-1} + W_nbr x^l + b_l)
GC-M   GraphConv + mean           x^l = S^l / k
GI-S   GINConv + sum              h^l = MLP_l((1+eps) h^{l-1} + x^l)
GC-W   GraphConv + weighted sum   x^l = sum_j alpha_ij h_j
GS-MAX GraphSAGE + max            x^l = max_j h_j   (elementwise)
GC-MIN GraphConv + min            x^l = min_j h_j   (elementwise)
GA-S   GraphSAGE + attention      x^l = sum_j softmax_j(logit(h_j)) h_j
GP-M   GraphConv + PNA tower      x^l = [log1p(k)*mean_j, std_j, max_j] h_j

where S^l is the *unnormalized* aggregate of h^{l-1} over in-neighbors and
x^l its normalized form.  Storing (S, k) instead of x keeps ``mean`` exact
under in-degree changes from streaming topology updates (DESIGN.md §2);
for max/min, S holds the tracked extremum (identity in empty rows) and the
engines additionally track contributor refs (see core/aggregators.py).

Each workload is a pure-function spec: parameter pytree + an ``update_fn``
mapping (params_l, h_prev, x) -> h_l.  The per-family UPDATE bodies are
written once against an array-module parameter ``xp`` (NumPy or jax.numpy),
so the host engines and the jitted engines share ONE family table instead
of hand-mirrored implementations.  All engines (full, RC, RIPPLE, device,
distributed) consume these definitions so correctness tests compare
engines, never re-implementations.
"""
from __future__ import annotations

from dataclasses import dataclass
from functools import partial
from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np

from .aggregators import Aggregator, get_aggregator


def matmul_f32(a, b):
    """Matrix product at full float32 precision.  A TPU runs a float32
    ``jnp`` product on bf16 passes by default, which at width 128 puts
    the final layer ~1e-1 away from a float32 reference; NumPy operands
    take the plain product."""
    if isinstance(a, np.ndarray) and isinstance(b, np.ndarray):
        return a @ b
    return jnp.matmul(a, b, precision=jax.lax.Precision.HIGHEST)


def _gc_update(xp, p, h_prev, x, *, last: bool):
    out = matmul_f32(x, p["w"]) + p["b"]
    return out if last else xp.maximum(out, 0.0)


def _sage_update(xp, p, h_prev, x, *, last: bool):
    out = matmul_f32(h_prev, p["w_self"]) + matmul_f32(x, p["w_nbr"]) \
        + p["b"]
    return out if last else xp.maximum(out, 0.0)


def _gin_update(xp, p, h_prev, x, *, last: bool):
    z = (1.0 + p["eps"]) * h_prev + x
    out = matmul_f32(xp.maximum(matmul_f32(z, p["w1"]) + p["b1"], 0.0),
                     p["w2"]) + p["b2"]
    return out if last else xp.maximum(out, 0.0)


# the ONE family table: every engine (NumPy host, jitted device, shard_map
# distributed epilogues aside) derives its UPDATE from these entries
FAMILY_UPDATE = {"gc": _gc_update, "sage": _sage_update, "gin": _gin_update}
_FAMILY_SELF_DEP = {"gc": False, "sage": True, "gin": True}


@dataclass(frozen=True)
class WorkloadSpec:
    """A GNN inference workload: model family x aggregation function."""

    name: str
    aggregator: str  # "sum" | "mean" | "wsum" | "max" | "min"
    self_dependent: bool  # does h^l read h^{l-1}_self directly?
    n_layers: int
    dims: tuple[int, ...]  # (d0, d1, ..., dL)

    @property
    def weighted(self) -> bool:
        return get_aggregator(self.aggregator).weighted

    @property
    def monotonic(self) -> bool:
        return get_aggregator(self.aggregator).algebra == "monotonic"

    @property
    def bounded(self) -> bool:
        return get_aggregator(self.aggregator).algebra == "bounded"


@dataclass(frozen=True)
class Workload:
    spec: WorkloadSpec
    family: str

    @property
    def agg(self) -> Aggregator:
        """The aggregation algebra this workload runs on."""
        return get_aggregator(self.spec.aggregator)

    def init_params(self, key: jax.Array) -> list[dict]:
        dims = self.spec.dims
        # the bounded family's PNA tower widens the neighbor aggregate x
        # (x_multiplier dims per input dim) — only x-consuming weights grow
        mult = self.agg.x_multiplier
        params = []
        for l in range(self.spec.n_layers):
            d_in, d_out = dims[l], dims[l + 1]
            d_x = d_in * mult
            key, *ks = jax.random.split(key, 6)
            scale = 1.0 / np.sqrt(d_x)
            if self.family == "gc":
                p = {"w": jax.random.normal(ks[0], (d_x, d_out)) * scale,
                     "b": jnp.zeros((d_out,))}
            elif self.family == "sage":
                p = {"w_self": jax.random.normal(ks[0], (d_in, d_out))
                     * (1.0 / np.sqrt(d_in)),
                     "w_nbr": jax.random.normal(ks[1], (d_x, d_out)) * scale,
                     "b": jnp.zeros((d_out,))}
            elif self.family == "gin":
                d_hid = d_out
                p = {"eps": jnp.zeros(()),
                     "w1": jax.random.normal(ks[0], (d_x, d_hid)) * scale,
                     "b1": jnp.zeros((d_hid,)),
                     "w2": jax.random.normal(ks[1], (d_hid, d_out)) * (1.0 / np.sqrt(d_hid)),
                     "b2": jnp.zeros((d_out,))}
            else:
                raise ValueError(self.family)
            params.append(p)
        return params

    def update_fn(self, layer: int, xp=jnp) -> Callable:
        """The layer's UPDATE bound to an array module (jnp by default;
        host engines pass ``xp=np`` and get the same body over NumPy)."""
        last = layer == self.spec.n_layers - 1
        return partial(FAMILY_UPDATE[self.family], xp, last=last)

    def normalize(self, S: jax.Array, k: jax.Array) -> jax.Array:
        """Aggregate normalization x = norm(S, k)."""
        return self.agg.normalize(S, k, xp=jnp)


_WORKLOAD_TABLE = {
    "gc-s": ("gc", "sum"),
    "gs-s": ("sage", "sum"),
    "gc-m": ("gc", "mean"),
    "gi-s": ("gin", "sum"),
    "gc-w": ("gc", "wsum"),
    "gs-max": ("sage", "max"),
    "gc-min": ("gc", "min"),
    "ga-s": ("sage", "attn"),
    "gp-m": ("gc", "pna"),
}


def make_workload(name: str, n_layers: int = 2, d_in: int = 32,
                  d_hidden: int = 32, n_classes: int = 8) -> Workload:
    """Factory for the registered workloads: the paper's five (gc-s, gs-s,
    gc-m, gi-s, gc-w), the monotonic pair (gs-max, gc-min), and the
    bounded-recompute pair (ga-s attention-SAGE, gp-m PNA-GraphConv)."""
    name = name.lower()
    family, agg = _WORKLOAD_TABLE[name]
    dims = (d_in,) + (d_hidden,) * (n_layers - 1) + (n_classes,)
    spec = WorkloadSpec(name=name, aggregator=agg,
                        self_dependent=_FAMILY_SELF_DEP[family],
                        n_layers=n_layers, dims=dims)
    return Workload(spec=spec, family=family)


WORKLOAD_NAMES = tuple(_WORKLOAD_TABLE)
MONOTONIC_WORKLOAD_NAMES = tuple(n for n, (_, a) in _WORKLOAD_TABLE.items()
                                 if get_aggregator(a).algebra == "monotonic")
BOUNDED_WORKLOAD_NAMES = tuple(n for n, (_, a) in _WORKLOAD_TABLE.items()
                               if get_aggregator(a).algebra == "bounded")
