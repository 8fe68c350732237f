"""Pallas TPU kernel: single-token GQA decode attention over a KV cache.

Grid = (batch, kv_heads): each cell serves one KV head's query group
(G = H/Hkv query heads, kept VMEM-resident as a (G × D) tile — MXU-friendly
since G·D is small) against that head's cache, streamed in BK chunks with
an online-softmax carry.  The valid length comes from ``pos`` (per-batch
scalar, (B, 1) block) so padding/unwritten cache slots are masked.

This is the serving hot loop: one call per generated token.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

BK = 512
NEG = -1e30


def _decode_kernel(pos_ref, q_ref, k_ref, v_ref, o_ref, *, bk: int,
                   scale: float, s_max: int):
    pos = pos_ref[0, 0]                                   # scalar int32
    q = q_ref[0, 0].astype(jnp.float32) * scale           # (G, D)
    g, d = q.shape
    n_chunks = (pos + bk) // bk                           # ⌈(pos+1)/bk⌉

    def body(c, carry):
        acc, m_i, l_i = carry
        k = k_ref[0, pl.dslice(c * bk, bk), 0, :].astype(jnp.float32)
        v = v_ref[0, pl.dslice(c * bk, bk), 0, :].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)  # (G, bk)
        col = c * bk + jax.lax.broadcasted_iota(jnp.int32, (g, bk), 1)
        s = jnp.where(col <= pos, s, NEG)
        m_new = jnp.maximum(m_i, s.max(axis=1))
        p = jnp.exp(s - m_new[:, None])
        alpha = jnp.exp(m_i - m_new)
        l_new = alpha * l_i + p.sum(axis=1)
        acc = acc * alpha[:, None] + jax.lax.dot(
            p, v, preferred_element_type=jnp.float32)
        return acc, m_new, l_new

    acc0 = jnp.zeros((g, d), jnp.float32)
    m0 = jnp.full((g,), NEG, jnp.float32)
    l0 = jnp.zeros((g,), jnp.float32)
    acc, m_i, l_i = jax.lax.fori_loop(0, n_chunks, body, (acc0, m0, l0))
    o_ref[0, 0] = (acc / jnp.maximum(l_i, 1e-30)[:, None]).astype(o_ref.dtype)


def decode_attention_pallas(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                            pos: jnp.ndarray, *, interpret: bool):
    """q (B, H, D); k/v (B, S, Hkv, D); pos (B,) int32 — index of the
    newest valid cache entry (attend to [0, pos])."""
    b, h, d = q.shape
    s_max, hkv = k.shape[1], k.shape[2]
    g = h // hkv
    bk = next((x for x in (BK, 256, 128) if s_max % x == 0), s_max)
    q4 = q.reshape(b, hkv, g, d)
    pos2 = pos.reshape(b, 1).astype(jnp.int32)
    kernel = functools.partial(_decode_kernel, bk=bk, scale=1.0 / d ** 0.5,
                               s_max=s_max)
    out = pl.pallas_call(
        kernel,
        grid=(b, hkv),
        in_specs=[
            pl.BlockSpec((1, 1), lambda bb, hh: (bb, 0)),
            pl.BlockSpec((1, 1, g, d), lambda bb, hh: (bb, hh, 0, 0)),
            pl.BlockSpec((1, s_max, 1, d), lambda bb, hh: (bb, 0, hh, 0)),
            pl.BlockSpec((1, s_max, 1, d), lambda bb, hh: (bb, 0, hh, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, g, d), lambda bb, hh: (bb, hh, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((b, hkv, g, d), q.dtype),
        interpret=interpret,
    )(pos2, q4, k, v)
    return out.reshape(b, h, d)
