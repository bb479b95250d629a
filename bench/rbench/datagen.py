"""Graph, features and weights of a configuration, all drawn from a seed.

The graph generator is a copy of the program's own
(``repro.core.graph.erdos_renyi``), kept here so that a change to the
program cannot move the benchmark's inputs.
"""
from __future__ import annotations

from functools import partial

import numpy as np


def seeds(seed: int, n: int) -> list[np.random.SeedSequence]:
    """``n`` independent child seed sequences of a run's ``--seed``."""
    return np.random.SeedSequence(int(seed)).spawn(n)


def erdos_renyi(n: int, m: int, rng: np.random.Generator):
    """Random simple directed graph with ``m`` unique non-loop edges."""
    k = int(m * 1.3) + 16
    src = rng.integers(0, n, size=k)
    dst = rng.integers(0, n, size=k)
    return _unique_edges(src, dst, n, m)


def _unique_edges(src, dst, n, m):
    keep = src != dst
    src, dst = src[keep], dst[keep]
    _, idx = np.unique(src * n + dst, return_index=True)
    idx = np.sort(idx)[:m]
    if idx.size < m:
        raise ValueError(f"generator drew {idx.size} unique edges, need {m}")
    return src[idx].astype(np.int64), dst[idx].astype(np.int64)


GRAPHS = {"er": erdos_renyi}


def make_graph(cfg: dict, ss: np.random.SeedSequence):
    """(snapshot (src, dst), holdout (src, dst)): the configuration's graph
    with ``holdout_frac`` of its edges held out (paper protocol 7.1.2)."""
    rng = np.random.default_rng(ss)
    src, dst = GRAPHS[cfg["graph"]](cfg["n"], cfg["m"], rng)
    hold = rng.random(src.shape[0]) < cfg["holdout_frac"]
    return (src[~hold], dst[~hold]), (src[hold], dst[hold])


def dims(cfg: dict) -> tuple[int, ...]:
    """Layer widths (d0, d1, ..., dL)."""
    return ((cfg["d_in"],) + (cfg["d_hidden"],) * (cfg["n_layers"] - 1)
            + (cfg["n_classes"],))


FAMILY_KEYS = {"gc": ("w",), "sage": ("w_self", "w_nbr")}


def family(cfg: dict) -> str:
    return {"gc-s": "gc", "gs-max": "sage"}[cfg["workload"]]


def aggregator(cfg: dict) -> str:
    return {"gc-s": "sum", "gs-max": "max"}[cfg["workload"]]


def make_inputs(cfg: dict, ss: np.random.SeedSequence):
    """Features ``[n, d0]`` and per-layer weights, made on the device in
    one jitted call from the seed, float32."""
    import jax

    key = int(ss.generate_state(1)[0])
    fn = jax.jit(partial(_inputs, n=cfg["n"], dims=dims(cfg),
                         keys=FAMILY_KEYS[family(cfg)]))
    return fn(jax.random.PRNGKey(key))


def _inputs(key, *, n, dims, keys):
    import jax
    import jax.numpy as jnp

    key, kx = jax.random.split(key)
    x = jax.random.normal(kx, (n, dims[0]), jnp.float32)
    params = []
    for d_in, d_out in zip(dims[:-1], dims[1:]):
        p = {}
        for name in keys:
            key, k = jax.random.split(key)
            p[name] = jax.random.normal(k, (d_in, d_out), jnp.float32) \
                / np.sqrt(d_in)
        p["b"] = jnp.zeros((d_out,), jnp.float32)
        params.append(p)
    return x, params
