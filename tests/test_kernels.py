"""Per-kernel shape/dtype sweeps: Pallas (interpret mode) vs pure-jnp oracle."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core.graph import erdos_renyi

RNG = np.random.default_rng(0)


def _tol(dtype):
    return dict(atol=2e-2, rtol=2e-2) if dtype == jnp.bfloat16 \
        else dict(atol=2e-5, rtol=2e-5)


# ---------------------------------------------------------------------------
@pytest.mark.parametrize("n,m,d,blk", [(100, 400, 32, 32), (257, 1500, 64, 64),
                                       (64, 300, 128, 64), (300, 2000, 16, 128)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_segment_mm(n, m, d, blk, dtype):
    from repro.kernels.segment_mm import segment_mm
    from repro.kernels.segment_mm.ref import segment_mm_ref
    src, dst, w = erdos_renyi(n, m, seed=1, weighted=True)
    x = jnp.asarray(RNG.normal(size=(n, d)), dtype)
    out = segment_mm(src, dst, w, x, n, blk=blk, interpret=True)
    ref = segment_mm_ref(jnp.asarray(src), jnp.asarray(dst),
                         jnp.asarray(w).astype(dtype), x, n)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), **_tol(dtype))


# ---------------------------------------------------------------------------
@pytest.mark.parametrize("R,Din,Dout", [(64, 32, 16), (128, 128, 128),
                                        (33, 48, 7), (256, 64, 200)])
@pytest.mark.parametrize("mean,relu", [(False, True), (True, False), (True, True)])
def test_delta_apply(R, Din, Dout, mean, relu):
    from repro.kernels.delta_apply import delta_apply
    from repro.kernels.delta_apply.ref import delta_apply_ref
    S = jnp.asarray(RNG.normal(size=(R, Din)), jnp.float32)
    M = jnp.asarray(RNG.normal(size=(R, Din)), jnp.float32)
    k = jnp.asarray(RNG.integers(0, 6, size=R), jnp.float32)
    W = jnp.asarray(RNG.normal(size=(Din, Dout)), jnp.float32)
    b = jnp.asarray(RNG.normal(size=Dout), jnp.float32)
    Sn, h = delta_apply(S, M, k, W, b, mean=mean, relu=relu,
                        interpret=True)
    Sr, hr = delta_apply_ref(S, M, k, W, b, mean=mean, relu=relu)
    np.testing.assert_allclose(Sn, Sr, atol=1e-5, rtol=1e-5)
    np.testing.assert_allclose(h, hr, atol=1e-4, rtol=1e-4)


# ---------------------------------------------------------------------------
@pytest.mark.parametrize("R,Din,Dout", [(64, 32, 16), (128, 128, 128),
                                        (33, 48, 7), (256, 64, 200)])
@pytest.mark.parametrize("maximize,relu", [(True, True), (False, True),
                                           (True, False)])
def test_extremum_apply(R, Din, Dout, maximize, relu):
    from repro.kernels.extremum_apply import extremum_apply
    from repro.kernels.extremum_apply.ref import extremum_apply_ref
    ident = -jnp.inf if maximize else jnp.inf
    S = jnp.asarray(RNG.normal(size=(R, Din)), jnp.float32)
    # empty tracked rows hold the aggregator identity
    S = S.at[jnp.asarray(RNG.choice(R, size=R // 8, replace=False))].set(ident)
    M = jnp.asarray(RNG.normal(size=(R, Din)), jnp.float32)
    # rows with no candidates this hop carry the identity mailbox
    M = M.at[jnp.asarray(RNG.choice(R, size=R // 4, replace=False))].set(ident)
    W = jnp.asarray(RNG.normal(size=(Din, Dout)), jnp.float32)
    b = jnp.asarray(RNG.normal(size=Dout), jnp.float32)
    Sn, h = extremum_apply(S, M, W, b, maximize=maximize, relu=relu,
                           interpret=True)
    Sr, hr = extremum_apply_ref(S, M, W, b, maximize=maximize, relu=relu)
    np.testing.assert_array_equal(np.asarray(Sn), np.asarray(Sr))
    np.testing.assert_allclose(h, hr, atol=1e-4, rtol=1e-4)


# ---------------------------------------------------------------------------
@pytest.mark.parametrize("R,Din,Dout", [(64, 32, 16), (128, 128, 128),
                                        (33, 48, 7)])
@pytest.mark.parametrize("maximize", [True, False])
def test_extremum_apply_masked(R, Din, Dout, maximize):
    """Per-dim SHRINK variant: masked cells swap in their re-aggregated
    value before the candidate fold, fused into the same pass."""
    from repro.kernels.extremum_apply import extremum_apply
    from repro.kernels.extremum_apply.ref import extremum_apply_ref
    ident = -jnp.inf if maximize else jnp.inf
    S = jnp.asarray(RNG.normal(size=(R, Din)), jnp.float32)
    M = jnp.asarray(RNG.normal(size=(R, Din)), jnp.float32)
    M = M.at[jnp.asarray(RNG.choice(R, size=R // 4, replace=False))].set(ident)
    # sparse shrink mask: a few (row, dim) cells re-derive their extremum
    mask = jnp.asarray(RNG.random((R, Din)) < 0.07, jnp.float32)
    RG = jnp.asarray(RNG.normal(size=(R, Din)), jnp.float32) * mask
    W = jnp.asarray(RNG.normal(size=(Din, Dout)), jnp.float32)
    b = jnp.asarray(RNG.normal(size=Dout), jnp.float32)
    Sn, h = extremum_apply(S, M, W, b, reagg=RG, mask=mask,
                           maximize=maximize, relu=True, interpret=True)
    Sr, hr = extremum_apply_ref(S, M, W, b, reagg=RG, mask=mask,
                                maximize=maximize, relu=True)
    np.testing.assert_array_equal(np.asarray(Sn), np.asarray(Sr))
    np.testing.assert_allclose(h, hr, atol=1e-4, rtol=1e-4)


# ---------------------------------------------------------------------------
@pytest.mark.parametrize("R,Din,Dh,Dout", [(64, 32, 32, 16),
                                           (128, 128, 128, 128),
                                           (33, 48, 20, 7)])
@pytest.mark.parametrize("mean,relu", [(False, True), (True, False)])
def test_mlp_apply(R, Din, Dh, Dout, mean, relu):
    """GIN's fused two-matmul apply vs the pure-jnp oracle."""
    from repro.kernels.mlp_apply import mlp_apply
    from repro.kernels.mlp_apply.ref import mlp_apply_ref
    S = jnp.asarray(RNG.normal(size=(R, Din)), jnp.float32)
    M = jnp.asarray(RNG.normal(size=(R, Din)), jnp.float32)
    hp = jnp.asarray(RNG.normal(size=(R, Din)), jnp.float32)
    k = jnp.asarray(RNG.integers(0, 6, size=R), jnp.float32)
    eps = jnp.float32(0.37)
    W1 = jnp.asarray(RNG.normal(size=(Din, Dh)), jnp.float32)
    b1 = jnp.asarray(RNG.normal(size=Dh), jnp.float32)
    W2 = jnp.asarray(RNG.normal(size=(Dh, Dout)), jnp.float32)
    b2 = jnp.asarray(RNG.normal(size=Dout), jnp.float32)
    Sn, h = mlp_apply(S, M, hp, k, eps, W1, b1, W2, b2, mean=mean, relu=relu,
                      interpret=True)
    Sr, hr = mlp_apply_ref(S, M, hp, k, eps, W1, b1, W2, b2,
                           mean=mean, relu=relu)
    np.testing.assert_allclose(Sn, Sr, atol=1e-5, rtol=1e-5)
    np.testing.assert_allclose(h, hr, atol=1e-4, rtol=1e-4)


# ---------------------------------------------------------------------------
@pytest.mark.parametrize("V,B,hot,d", [(100, 8, 1, 16), (1000, 32, 4, 64),
                                       (5000, 16, 8, 128)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_embedding_bag(V, B, hot, d, dtype):
    from repro.kernels.embedding_bag import embedding_bag_kernel
    from repro.kernels.embedding_bag.ref import embedding_bag_ref
    table = jnp.asarray(RNG.normal(size=(V, d)), dtype)
    idx = jnp.asarray(RNG.integers(0, V, size=(B, hot)), jnp.int32)
    out = embedding_bag_kernel(table, idx, interpret=True)
    ref = embedding_bag_ref(table, idx)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), **_tol(dtype))


@pytest.mark.parametrize("V,R,hot,d", [(64, 16, 8, 16), (200, 48, 12, 32),
                                       (200, 40, 1000, 16)])
def test_embedding_bag_engine_pattern(V, R, hot, d):
    """The bounded device engine's gather shape: a [R, hot] in-neighbor id
    rectangle padded with sentinel V pointing at a zero row appended to the
    table — the kernel's bag sum must equal the masked dense sum (this is
    gp-m's per-row first-moment gather under ``use_pallas``).  The last
    case holds more indices than one SMEM prefetch, so it runs chunked."""
    from repro.kernels.embedding_bag import embedding_bag_pallas
    table = jnp.asarray(RNG.normal(size=(V, d)), jnp.float32)
    padded = jnp.concatenate([table, jnp.zeros((1, d), jnp.float32)])
    degs = RNG.integers(0, hot + 1, size=R)
    idx = np.full((R, hot), V, dtype=np.int32)
    for r, deg in enumerate(degs):
        idx[r, :deg] = RNG.integers(0, V, size=deg)
    out = embedding_bag_pallas(jnp.asarray(idx), padded, interpret=True)
    mask = (idx < V)[..., None]
    ref = (np.asarray(table)[np.minimum(idx, V - 1)] * mask).sum(axis=1)
    np.testing.assert_allclose(np.asarray(out), ref, atol=1e-5, rtol=1e-5)


# ---------------------------------------------------------------------------
@pytest.mark.parametrize("B,S,H,Hkv,Dh,bq,bkv",
                         [(2, 64, 4, 2, 16, 16, 16),
                          (1, 128, 8, 8, 32, 32, 64),
                          (2, 96, 6, 2, 8, 32, 32),
                          (1, 256, 4, 1, 64, 64, 128)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention(B, S, H, Hkv, Dh, bq, bkv, dtype):
    from repro.kernels.flash_attention import flash_attention
    from repro.kernels.flash_attention.ref import flash_attention_ref
    q = jnp.asarray(RNG.normal(size=(B, S, H, Dh)), dtype)
    k = jnp.asarray(RNG.normal(size=(B, S, Hkv, Dh)), dtype)
    v = jnp.asarray(RNG.normal(size=(B, S, Hkv, Dh)), dtype)
    out = flash_attention(q, k, v, bq=bq, bkv=bkv, interpret=True)
    ref = flash_attention_ref(q, k, v)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               **(_tol(dtype) if dtype == jnp.bfloat16
                                  else dict(atol=1e-5, rtol=1e-4)))


# flash attention must also match the model's chunked-jnp attention path
def test_flash_matches_model_attention():
    from repro.kernels.flash_attention import flash_attention
    from repro.models.lm.config import LMConfig
    from repro.models.lm.model import causal_attention
    cfg = LMConfig(name="t", n_layers=1, d_model=64, n_heads=4, n_kv_heads=2,
                   d_ff=64, vocab=32, d_head=16, attn_chunk=32)
    q = jnp.asarray(RNG.normal(size=(2, 64, 4, 16)), jnp.float32)
    k = jnp.asarray(RNG.normal(size=(2, 64, 2, 16)), jnp.float32)
    v = jnp.asarray(RNG.normal(size=(2, 64, 2, 16)), jnp.float32)
    a = flash_attention(q, k, v, bq=32, bkv=32, interpret=True)
    b = causal_attention(q, k, v, cfg)
    np.testing.assert_allclose(a, b, atol=1e-5, rtol=1e-4)
