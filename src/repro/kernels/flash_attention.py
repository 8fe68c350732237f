"""Pallas TPU kernel: causal flash attention (prefill), GQA-aware.

Online-softmax tiling: grid = (batch, heads, q_blocks); the q tile
(BQ × D) stays VMEM-resident while K/V stream in BK-sized chunks.  The
causal structure bounds the inner loop at ⌈(q_hi)/BK⌉ chunks, skipping the
upper triangle entirely (≈2× prefill win).  GQA is expressed in the
BlockSpec index map: kv block index = h // group — no K/V repeat in HBM.

VMEM per cell (BQ=128, BK=512, D=128, bf16): q 32 KB + k/v 2×128 KB +
fp32 acc 64 KB ≈ 0.36 MB.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

BQ = 128
BK = 512
NEG = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, *, bk: int, scale: float):
    i = pl.program_id(2)
    q = q_ref[0, 0].astype(jnp.float32) * scale              # (BQ, D)
    bq, d = q.shape
    q_lo = i * bq
    n_chunks = (q_lo + bq + bk - 1) // bk                    # causal bound

    def body(c, carry):
        acc, m_i, l_i = carry
        k = k_ref[0, 0, pl.dslice(c * bk, bk), :].astype(jnp.float32)
        v = v_ref[0, 0, pl.dslice(c * bk, bk), :].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        row = q_lo + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
        col = c * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        s = jnp.where(col <= row, s, NEG)
        m_new = jnp.maximum(m_i, s.max(axis=1))
        p = jnp.exp(s - m_new[:, None])
        alpha = jnp.exp(m_i - m_new)
        l_new = alpha * l_i + p.sum(axis=1)
        acc = acc * alpha[:, None] + jax.lax.dot(
            p.astype(v.dtype), v, preferred_element_type=jnp.float32)
        return acc, m_new, l_new

    acc0 = jnp.zeros((bq, d), jnp.float32)
    m0 = jnp.full((bq,), NEG, jnp.float32)
    l0 = jnp.zeros((bq,), jnp.float32)
    acc, m_i, l_i = jax.lax.fori_loop(0, n_chunks, body, (acc0, m0, l0))
    o_ref[0, 0] = (acc / jnp.maximum(l_i, 1e-30)[:, None]).astype(o_ref.dtype)


def flash_attention_pallas(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                           *, interpret: bool) -> jnp.ndarray:
    """q (B, H, S, D); k/v (B, Hkv, S, D); S % BQ == 0; causal."""
    b, h, s, d = q.shape
    hkv = k.shape[1]
    g = h // hkv
    assert s % BQ == 0 and d % 128 == 0
    bk = next(x for x in (BK, 256, BQ) if s % x == 0)   # bk must divide s
    scale = 1.0 / (d ** 0.5)
    kernel = functools.partial(_flash_kernel, bk=bk, scale=scale)
    return pl.pallas_call(
        kernel,
        grid=(b, h, s // BQ),
        in_specs=[
            pl.BlockSpec((1, 1, BQ, d), lambda bb, hh, ii: (bb, hh, ii, 0)),
            pl.BlockSpec((1, 1, s, d), lambda bb, hh, ii: (bb, hh // g, 0, 0)),
            pl.BlockSpec((1, 1, s, d), lambda bb, hh, ii: (bb, hh // g, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, BQ, d), lambda bb, hh, ii: (bb, hh, ii, 0)),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        interpret=interpret,
    )(q, k, v)
