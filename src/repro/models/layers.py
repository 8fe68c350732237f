"""Composable transformer layers: norms, RoPE, attention (GQA / MLA /
sliding-window / cross), MLPs (SwiGLU / GeGLU / squared-ReLU / GELU) and
capacity-factor MoE with token dispatch.

Functional style: ``init_*`` builds a param dict; ``*_apply`` consumes it.
Activations are annotated with logical axes via ``repro.distributed.api.lc``
(no-ops outside a mesh-rule context).
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp

from repro.distributed.api import lc
from .config import ModelConfig


def _norm_init(d: int, dtype) -> dict:
    return {"scale": jnp.ones((d,), dtype=dtype)}


def rmsnorm(p: dict, x: jnp.ndarray, eps: float) -> jnp.ndarray:
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    out = xf * jax.lax.rsqrt(var + eps)
    return (out * p["scale"].astype(jnp.float32)).astype(x.dtype)


# ------------------------------------------------------------------- RoPE
def rope_cos_sin(positions: jnp.ndarray, dim: int, theta: float):
    """positions: (...,) int32 -> cos/sin of shape (..., dim//2)."""
    inv = 1.0 / (theta ** (jnp.arange(0, dim, 2, dtype=jnp.float32) / dim))
    ang = positions.astype(jnp.float32)[..., None] * inv
    return jnp.cos(ang), jnp.sin(ang)


def apply_rope(x: jnp.ndarray, cos: jnp.ndarray, sin: jnp.ndarray):
    """x: (..., heads, dim); cos/sin broadcast over the head axis."""
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    c = cos[..., None, :]
    s = sin[..., None, :]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s],
                           axis=-1).astype(x.dtype)


# -------------------------------------------------------------- attention
def init_attention(cfg: ModelConfig, rng, d_model: Optional[int] = None) -> dict:
    d = d_model or cfg.d_model
    hd, h, hkv = cfg.hd, cfg.n_heads, cfg.n_kv_heads
    k = jax.random.split(rng, 5)
    s = 0.02
    p = {
        "wq": jax.random.normal(k[0], (d, h, hd), cfg.pdtype) * s,
        "wk": jax.random.normal(k[1], (d, hkv, hd), cfg.pdtype) * s,
        "wv": jax.random.normal(k[2], (d, hkv, hd), cfg.pdtype) * s,
        "wo": jax.random.normal(k[3], (h, hd, d), cfg.pdtype) * s,
    }
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((h, hd), cfg.pdtype)
        p["bk"] = jnp.zeros((hkv, hd), cfg.pdtype)
        p["bv"] = jnp.zeros((hkv, hd), cfg.pdtype)
    return p


def _band_mask(q_idx, k_idx, causal: bool, window: int):
    """(…,sq,st) boolean mask computed on the fly (never S×S global)."""
    m = jnp.ones(q_idx.shape[:-1] + (q_idx.shape[-1], k_idx.shape[-1]), bool)
    qi = q_idx[..., :, None]
    ki = k_idx[..., None, :]
    if causal:
        m &= ki <= qi
    if window > 0:
        m &= ki > qi - window
    return m


_NAIVE_MAX_SEQ = 1024     # below this, materializing scores is fine


def sdpa(q, k, v, *, causal: bool = True, window: int = 0,
         scale: Optional[float] = None, q_chunk: int = 512):
    """Memory-efficient GQA attention core (XLA 'flash' pattern).

    q (B,S,H,D); k/v (B,T,Hkv,D).  For long sequences, scans over q chunks
    so only an (…, q_chunk, T) score tile is ever live; the scan body is
    rematerialized in the backward pass.  On real TPUs the Pallas kernel
    (kernels/flash_attention.py) replaces this under shard_map; the XLA
    formulation keeps the dry-run memory profile equivalent.
    """
    b, sq, h, d = q.shape
    t = k.shape[1]
    hkv = k.shape[2]
    g = h // hkv
    dv = v.shape[-1]                # may differ from d (MLA fused scores)
    scale = scale if scale is not None else 1.0 / (d ** 0.5)
    cdtype = q.dtype
    qg = q.reshape(b, sq, hkv, g, d)

    def attend(q_i, q_idx):
        # named scope marks the region the Pallas flash kernel fuses in
        # VMEM on TPU — the roofline memory term excludes its HBM traffic
        # (kernels/flash_attention.py is the TPU implementation)
        with jax.named_scope("fused_attn"):
            s = jnp.einsum("bskgd,btkd->bkgst", q_i, k).astype(jnp.float32)
            s = s * scale
            mask = _band_mask(q_idx, jnp.arange(t), causal, window)
            s = jnp.where(mask[None, None, None], s, -1e30)
            w = jax.nn.softmax(s, axis=-1).astype(cdtype)
            return jnp.einsum("bkgst,btke->bskge", w, v)

    if sq <= _NAIVE_MAX_SEQ or sq % q_chunk != 0 or sq <= q_chunk:
        out = attend(qg, jnp.arange(sq))
        return out.reshape(b, sq, h, dv)

    nq = sq // q_chunk
    qs = qg.reshape(b, nq, q_chunk, hkv, g, d).transpose(1, 0, 2, 3, 4, 5)

    @jax.checkpoint
    def step(_, xs):
        i, q_i = xs
        q_idx = i * q_chunk + jnp.arange(q_chunk)
        return None, attend(q_i, q_idx)

    _, outs = jax.lax.scan(step, None, (jnp.arange(nq), qs))
    out = outs.transpose(1, 0, 2, 3, 4, 5).reshape(b, sq, hkv, g, dv)
    return out.reshape(b, sq, h, dv)


def _decode_sdpa(q, k, v, valid_mask, scale: Optional[float] = None):
    """Single-query attention.  q (B,1,H,D); k/v (B,T,Hkv,D);
    valid_mask (B,T) bool.  O(T) memory (never T×T)."""
    b, _, h, d = q.shape
    hkv = k.shape[2]
    g = h // hkv
    scale = scale if scale is not None else 1.0 / (d ** 0.5)
    qg = q.reshape(b, hkv, g, d)
    with jax.named_scope("fused_attn"):
        s = jnp.einsum("bkgd,btkd->bkgt", qg, k).astype(jnp.float32) * scale
        s = jnp.where(valid_mask[:, None, None, :], s, -1e30)
        w = jax.nn.softmax(s, axis=-1).astype(q.dtype)
        out = jnp.einsum("bkgt,btkd->bkgd", w, v)
    return out.reshape(b, 1, h, d)


def attention_apply(p: dict, cfg: ModelConfig, x: jnp.ndarray,
                    positions: jnp.ndarray, *, causal: bool = True,
                    window: int = 0, kv_cache=None, decode_mask=None,
                    use_rope: bool = True,
                    xattn_kv: Optional[jnp.ndarray] = None):
    """GQA attention.  Modes:
       - self-attn train/prefill: kv_cache None; on-the-fly banded mask
       - decode: x is (B,1,d); kv_cache is the layer's cache writer: it
         takes the step's rows dict(k=(B,Hkv,D), v=...), writes them at
         each slot's position and returns the layer's cache dict(k=(B,T,
         Hkv,D), v=...) after the write; decode_mask (B,T) marks valid
         cache slots
       - cross-attn: xattn_kv = encoder states (no rope, no cache logic)
    """
    cd = cfg.cdtype
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"].astype(cd))
    if "bq" in p:
        q = q + p["bq"].astype(cd)
    kv_src = xattn_kv if xattn_kv is not None else x
    k = jnp.einsum("bsd,dhk->bshk", kv_src, p["wk"].astype(cd))
    v = jnp.einsum("bsd,dhk->bshk", kv_src, p["wv"].astype(cd))
    if "bk" in p:
        k = k + p["bk"].astype(cd)
        v = v + p["bv"].astype(cd)
    q = lc(q, "batch", "seq", "heads", None)
    k = lc(k, "batch", "seq", "kv_heads", None)
    v = lc(v, "batch", "seq", "kv_heads", None)
    if use_rope and xattn_kv is None:
        cos, sin = rope_cos_sin(positions, cfg.hd, cfg.rope_theta)
        q = apply_rope(q, cos, sin)
        k = apply_rope(k, cos, sin)     # decode: positions is (B,1) = current
    if kv_cache is not None:
        # decode: write the step's row, then attend over the layer's cache
        kv = kv_cache({"k": k[:, 0], "v": v[:, 0]})
        out = _decode_sdpa(q, kv["k"].astype(cd), kv["v"].astype(cd),
                           decode_mask)
    else:
        out = sdpa(q, k, v, causal=causal and xattn_kv is None,
                   window=window)
    out = lc(out, "batch", "seq", "heads", None)
    y = jnp.einsum("bshk,hkd->bsd", out, p["wo"].astype(cd))
    return lc(y, "batch", "seq", None)


# ------------------------------------------------------------------- MLA
def init_mla(cfg: ModelConfig, rng) -> dict:
    d, h, hd = cfg.d_model, cfg.n_heads, cfg.hd
    r, rh = cfg.kv_lora_rank, cfg.rope_head_dim
    k = jax.random.split(rng, 6)
    s = 0.02
    return {
        "wq": jax.random.normal(k[0], (d, h, hd + rh), cfg.pdtype) * s,
        "wdkv": jax.random.normal(k[1], (d, r), cfg.pdtype) * s,
        "wuk": jax.random.normal(k[2], (r, h, hd), cfg.pdtype) * s,
        "wuv": jax.random.normal(k[3], (r, h, hd), cfg.pdtype) * s,
        "wkr": jax.random.normal(k[4], (d, rh), cfg.pdtype) * s,
        "wo": jax.random.normal(k[5], (h, hd, d), cfg.pdtype) * s,
    }


def mla_apply(p: dict, cfg: ModelConfig, x: jnp.ndarray,
              positions: jnp.ndarray, *, kv_cache=None, decode_mask=None):
    """Multi-head latent attention (DeepSeek-V2).

    Prefill/train: decompress K/V per head and run the shared chunked GQA
    core (rope and nope score terms fused via head-dim concat).
    Decode: *absorbed* path — score and combine directly in the compressed
    c_kv space; the cache stores (c_kv, k_rope) only.  kv_cache is the
    layer's cache writer, as in :func:`attention_apply`.
    """
    cd = cfg.cdtype
    hd, h, rh, r = cfg.hd, cfg.n_heads, cfg.rope_head_dim, cfg.kv_lora_rank
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"].astype(cd))
    q = lc(q, "batch", "seq", "heads", None)
    q_nope, q_rope = q[..., :hd], q[..., hd:]
    cos, sin = rope_cos_sin(positions, rh, cfg.rope_theta)
    q_rope = apply_rope(q_rope, cos, sin)

    c_kv = jnp.einsum("bsd,dr->bsr", x, p["wdkv"].astype(cd))      # (B,S,r)
    k_rope_new = jnp.einsum("bsd,dk->bsk", x, p["wkr"].astype(cd))  # (B,S,rh)
    scale = 1.0 / float(hd + rh) ** 0.5
    if kv_cache is None:
        k_rope = apply_rope(k_rope_new[:, :, None, :], cos, sin)
        k_nope = jnp.einsum("bsr,rhk->bshk", c_kv, p["wuk"].astype(cd))
        vv = jnp.einsum("bsr,rhk->bshk", c_kv, p["wuv"].astype(cd))
        # fuse nope+rope score terms: concat along head_dim (Hkv == H)
        q_cat = jnp.concatenate([q_nope, q_rope], axis=-1)
        k_cat = jnp.concatenate(
            [k_nope, jnp.broadcast_to(k_rope, k_nope.shape[:3] + (rh,))], -1)
        out = sdpa(q_cat, k_cat, vv, causal=True, scale=scale)
    else:
        kr = apply_rope(k_rope_new[:, :, None, :], cos, sin)[:, 0, 0]
        kv = kv_cache({"c_kv": c_kv[:, 0], "k_rope": kr})
        ckv_all, kr_all = kv["c_kv"], kv["k_rope"]
        # absorbed attention in compressed space
        q_abs = jnp.einsum("bshk,rhk->bshr", q_nope, p["wuk"].astype(cd))
        with jax.named_scope("fused_attn"):
            scores = (jnp.einsum("bshr,btr->bhst", q_abs, ckv_all.astype(cd)) +
                      jnp.einsum("bshk,btk->bhst", q_rope, kr_all.astype(cd)))
            scores = scores.astype(jnp.float32) * scale
            scores = jnp.where(decode_mask[:, None, None, :], scores, -1e30)
            w = jax.nn.softmax(scores, axis=-1).astype(cd)
            out_c = jnp.einsum("bhst,btr->bshr", w, ckv_all.astype(cd))
        out = jnp.einsum("bshr,rhk->bshk", out_c, p["wuv"].astype(cd))
    y = jnp.einsum("bshk,hkd->bsd", out, p["wo"].astype(cd))
    return lc(y, "batch", "seq", None)


# ------------------------------------------------------------------- MLPs
def _n_in(mlp: str) -> int:
    return 2 if mlp in ("swiglu", "geglu") else 1


def init_mlp(cfg: ModelConfig, rng, d_ff: Optional[int] = None) -> dict:
    d = cfg.d_model
    f = d_ff or cfg.d_ff
    k = jax.random.split(rng, 3)
    s = 0.02
    p = {"wi": jax.random.normal(k[0], (d, f), cfg.pdtype) * s,
         "wo": jax.random.normal(k[2], (f, d), cfg.pdtype) * s}
    if _n_in(cfg.mlp) == 2:
        p["wg"] = jax.random.normal(k[1], (d, f), cfg.pdtype) * s
    return p


def _act(h, g, kind: str):
    if kind == "swiglu":
        return jax.nn.silu(g) * h
    if kind == "geglu":
        return jax.nn.gelu(g) * h
    if kind == "relu2":
        r = jax.nn.relu(h)
        return r * r
    return jax.nn.gelu(h)


def mlp_apply(p: dict, cfg: ModelConfig, x: jnp.ndarray) -> jnp.ndarray:
    cd = cfg.cdtype
    h = jnp.einsum("bsd,df->bsf", x, p["wi"].astype(cd))
    g = jnp.einsum("bsd,df->bsf", x, p["wg"].astype(cd)) if "wg" in p else None
    h = lc(_act(h, g, cfg.mlp), "batch", "seq", "ffn")
    y = jnp.einsum("bsf,fd->bsd", h, p["wo"].astype(cd))
    return lc(y, "batch", "seq", None)


# -------------------------------------------------------------------- MoE
def init_moe(cfg: ModelConfig, rng) -> dict:
    d, e = cfg.d_model, cfg.n_experts
    f = cfg.expert_d_ff or cfg.d_ff
    k = jax.random.split(rng, 5)
    s = 0.02
    p = {
        "router": jax.random.normal(k[0], (d, e), jnp.float32) * s,
        "wi": jax.random.normal(k[1], (e, d, f), cfg.pdtype) * s,
        "wo": jax.random.normal(k[3], (e, f, d), cfg.pdtype) * s,
    }
    if _n_in(cfg.mlp) == 2:
        p["wg"] = jax.random.normal(k[2], (e, d, f), cfg.pdtype) * s
    if cfg.n_shared_experts:
        sf = f * cfg.n_shared_experts
        sub = dataclasses.replace(cfg, d_ff=sf)
        p["shared"] = init_mlp(sub, k[4], d_ff=sf)
    return p


def moe_apply(p: dict, cfg: ModelConfig, x: jnp.ndarray) -> jnp.ndarray:
    """Top-k capacity-factor MoE with scatter dispatch (Switch-style).

    Expert buffers are sharded over the ``expert`` logical axis (EP); the
    scatter/gather between token- and expert-sharded layouts lowers to
    all-to-all under SPMD.
    """
    cd = cfg.cdtype
    b, s_len, d = x.shape
    e, k = cfg.n_experts, cfg.top_k
    xt = x.reshape(-1, d)                                   # (T, d)
    t = xt.shape[0]
    cap = max(1, -(-int(t * k * cfg.capacity_factor) // e))  # ceil division

    logits = (xt.astype(jnp.float32) @ p["router"])          # (T, E) fp32
    probs = jax.nn.softmax(logits, axis=-1)
    topv, topi = jax.lax.top_k(probs, k)                     # (T, K)
    topv = topv / jnp.maximum(topv.sum(-1, keepdims=True), 1e-9)

    e_flat = topi.reshape(-1)                                # (T*K,)
    onehot = jax.nn.one_hot(e_flat, e, dtype=jnp.int32)      # (T*K, E)
    pos = jnp.cumsum(onehot, axis=0) * onehot                # running count
    pos_in_e = pos.sum(-1) - 1                               # (T*K,)
    keep = pos_in_e < cap
    src = jnp.repeat(jnp.arange(t), k)
    safe_pos = jnp.where(keep, pos_in_e, cap - 1)

    buf = jnp.zeros((e, cap, d), cd)
    buf = buf.at[jnp.where(keep, e_flat, e - 1), safe_pos].add(
        jnp.where(keep[:, None], xt[src].astype(cd), 0))
    buf = lc(buf, "expert", None, None)

    h = jnp.einsum("ecd,edf->ecf", buf, p["wi"].astype(cd))
    g = (jnp.einsum("ecd,edf->ecf", buf, p["wg"].astype(cd))
         if "wg" in p else None)
    h = _act(h, g, cfg.mlp)
    out_buf = jnp.einsum("ecf,efd->ecd", h, p["wo"].astype(cd))
    out_buf = lc(out_buf, "expert", None, None)

    gathered = out_buf[e_flat, safe_pos]                     # (T*K, d)
    gathered = jnp.where(keep[:, None], gathered, 0)
    y = (gathered.reshape(t, k, d) *
         topv.reshape(t, k, 1).astype(cd)).sum(axis=1)
    if "shared" in p:
        y = y + mlp_apply(p["shared"], cfg, x).reshape(t, d)
    return lc(y.reshape(b, s_len, d), "batch", "seq", None)
