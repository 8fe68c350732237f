"""Pure-jnp oracles for every Pallas kernel (the allclose ground truth)."""
from __future__ import annotations

import jax
import jax.numpy as jnp

# Similarity scores are exact fp32 products, as in the kernels: XLA's
# default f32 matmul on TPU rounds its operands to bf16.
_F32 = jax.lax.Precision.HIGHEST


def sim_top1_ref(queries: jnp.ndarray, candidates: jnp.ndarray,
                 n_valid: int):
    """queries (Q,D), candidates (N,D) -> (max sim (Q,), argmax (Q,))."""
    scores = jnp.dot(queries.astype(jnp.float32),
                     candidates.astype(jnp.float32).T, precision=_F32)
    col = jnp.arange(candidates.shape[0])
    scores = jnp.where(col[None, :] < n_valid, scores, -jnp.inf)
    return scores.max(axis=1), scores.argmax(axis=1).astype(jnp.int32)


def sim_topk_ref(queries: jnp.ndarray, candidates: jnp.ndarray,
                 n_valid: int, k: int):
    """queries (Q,D), candidates (N,D) -> (vals (Q,K), idx (Q,K)), sorted
    descending; ``lax.top_k`` breaks ties toward the lower index, matching
    the kernel's merge order and a stable descending host sort."""
    scores = jnp.dot(queries.astype(jnp.float32),
                     candidates.astype(jnp.float32).T, precision=_F32)
    col = jnp.arange(candidates.shape[0])
    scores = jnp.where(col[None, :] < n_valid, scores, -jnp.inf)
    vals, idx = jax.lax.top_k(scores, k)
    return vals, idx.astype(jnp.int32)


def sim_topk_q8_ref(q8: jnp.ndarray, qscale: jnp.ndarray,
                    c8: jnp.ndarray, cscale: jnp.ndarray,
                    n_valid: int, k: int):
    """Quantized-slab Top-K oracle: exact int8×int8→int32 scores rescaled
    per row as ``(acc * qscale) * cscale`` — the same fixed multiply order
    as the Pallas kernel and the numpy host gemm, so all engines produce
    bit-identical approximate similarities."""
    acc = jax.lax.dot_general(
        q8, c8, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.int32)
    scores = (acc.astype(jnp.float32)
              * qscale.astype(jnp.float32)[:, None]) \
        * cscale.astype(jnp.float32)[None, :]
    col = jnp.arange(c8.shape[0])
    scores = jnp.where(col[None, :] < n_valid, scores, -jnp.inf)
    vals, idx = jax.lax.top_k(scores, k)
    return vals, idx.astype(jnp.int32)


def attention_ref(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                  causal: bool = True):
    """q (B,H,S,D); k/v (B,Hkv,S,D) -> (B,H,S,D).  fp32 softmax."""
    b, h, s, d = q.shape
    hkv = k.shape[1]
    g = h // hkv
    qf = q.astype(jnp.float32).reshape(b, hkv, g, s, d) / jnp.sqrt(d)
    kf = k.astype(jnp.float32)
    vf = v.astype(jnp.float32)
    scores = jnp.einsum("bkgsd,bktd->bkgst", qf, kf)
    if causal:
        mask = jnp.tril(jnp.ones((s, s), bool))
        scores = jnp.where(mask, scores, -jnp.inf)
    w = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bkgst,bktd->bkgsd", w, vf)
    return out.reshape(b, h, s, d).astype(q.dtype)


def decode_attention_ref(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                         pos: jnp.ndarray):
    """q (B,H,D); k/v (B,S,Hkv,D); pos (B,) -> (B,H,D)."""
    b, h, d = q.shape
    s, hkv = k.shape[1], k.shape[2]
    g = h // hkv
    qf = q.astype(jnp.float32).reshape(b, hkv, g, d) / jnp.sqrt(d)
    kf = k.astype(jnp.float32)
    vf = v.astype(jnp.float32)
    scores = jnp.einsum("bkgd,bskd->bkgs", qf, kf)
    valid = jnp.arange(s)[None, None, None, :] <= pos[:, None, None, None]
    scores = jnp.where(valid, scores, -jnp.inf)
    w = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bkgs,bskd->bkgd", w, vf)
    return out.reshape(b, h, d).astype(q.dtype)


def rac_value_ref(tsi: jnp.ndarray, tid: jnp.ndarray, tp_last: jnp.ndarray,
                  t_last: jnp.ndarray, alpha: float, t_now: int):
    decay = jnp.exp2(-alpha * (t_now - t_last[tid]).astype(jnp.float32))
    return decay * tp_last[tid].astype(jnp.float32) * tsi


def victim_value_ref(tsi: jnp.ndarray, tid: jnp.ndarray, occ: jnp.ndarray,
                     tp_last: jnp.ndarray, t_last: jnp.ndarray, t_now,
                     alpha: float):
    """Occupancy-masked Eq.1 with a traced t_now (free slots -> +inf)."""
    tid = jnp.maximum(tid, 0)                  # free slots carry tid -1
    decay = jnp.exp2(-alpha * (t_now - t_last[tid]).astype(jnp.float32))
    val = decay * tp_last[tid].astype(jnp.float32) * tsi
    return jnp.where(occ > 0, val, jnp.inf)
