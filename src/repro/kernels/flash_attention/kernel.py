"""Causal FlashAttention (arXiv:2205.14135) for TPU with GQA.

Online-softmax over KV blocks with running (max, denom) carried in VMEM
scratch; causal *block skipping* (kv_block > q_block contributes nothing and
is masked; on TPU the grid is dense but the masked branch is cheap VPU work,
and the block-level `pl.when` skips the MXU matmuls entirely).

Grid: (B * Hkv, q_blocks, kv_blocks) — kv innermost so the scratch
accumulator for one q block stays resident across its kv sweep.  Each q
block is [rep * BQ, Dh] (all query heads of the KV group processed
together, MaxText-style), keeping MXU tiles >= 128 even for small BQ.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG = -1e30


def _kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref,
            *, bq: int, bkv: int, rep: int, n_kv: int, scale: float):
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(ki <= qi)  # causal block skip
    def _compute():
        q = q_ref[0, 0]                        # [rep*bq, d]
        k = k_ref[0]                           # [bkv, d]
        s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * scale
        # causal mask inside the diagonal block
        q_pos = qi * bq + (jax.lax.iota(jnp.int32, rep * bq) % bq)
        k_pos = ki * bkv + jax.lax.iota(jnp.int32, bkv)
        s = jnp.where(q_pos[:, None] >= k_pos[None, :], s, NEG)

        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        l_ref[...] = l_ref[...] * alpha + p.sum(axis=1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jnp.dot(
            p.astype(v_ref.dtype), v_ref[0],
            preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    @pl.when(ki == n_kv - 1)
    def _fin():
        o_ref[0, 0] = (acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)
                       ).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("bq", "bkv", "interpret"))
def flash_attention_pallas(q, k, v, *, bq: int = 128, bkv: int = 128,
                           interpret: bool):
    """q [B,S,H,Dh]; k/v [B,S,Hkv,Dh] -> [B,S,H,Dh] (causal)."""
    B, S, H, Dh = q.shape
    Hkv = k.shape[2]
    rep = H // Hkv
    bq = min(bq, S)
    bkv = min(bkv, S)
    assert S % bq == 0 and S % bkv == 0
    scale = 1.0 / np.sqrt(Dh)

    # layout: fold the rep query heads of each KV group into the q-block
    # row dim -> [B*Hkv, n_q, rep*bq, Dh]
    n_q = S // bq
    qg = (q.reshape(B, n_q, bq, Hkv, rep, Dh).transpose(0, 3, 1, 4, 2, 5)
          .reshape(B * Hkv, n_q, rep * bq, Dh))
    kg = k.transpose(0, 2, 1, 3).reshape(B * Hkv, S, Dh)
    vg = v.transpose(0, 2, 1, 3).reshape(B * Hkv, S, Dh)
    n_kv = S // bkv

    kern = functools.partial(_kernel, bq=bq, bkv=bkv, rep=rep, n_kv=n_kv,
                             scale=scale)
    out = pl.pallas_call(
        kern,
        grid=(B * Hkv, n_q, n_kv),
        in_specs=[
            pl.BlockSpec((1, 1, rep * bq, Dh), lambda b, i, j: (b, i, 0, 0)),
            pl.BlockSpec((1, bkv, Dh), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, bkv, Dh), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, rep * bq, Dh), lambda b, i, j: (b, i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((B * Hkv, n_q, rep * bq, Dh), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((rep * bq, 1), jnp.float32),   # running max
            pltpu.VMEM((rep * bq, 1), jnp.float32),   # running denom
            pltpu.VMEM((rep * bq, Dh), jnp.float32),  # ctx accumulator
        ],
        interpret=interpret,
    )(qg, kg, vg)
    # undo the layout
    out = (out.reshape(B, Hkv, n_q, rep, bq, Dh).transpose(0, 2, 4, 1, 3, 5)
           .reshape(B, S, H, Dh))
    return out
