"""Composable transformer layers: norms, RoPE (plain or YaRN), attention
(GQA / MLA / sliding-window / cross), MLPs (SwiGLU / GeGLU / squared-ReLU /
GELU) and a dropless top-k MoE.

Functional style: ``init_*`` builds a param dict; ``*_apply`` consumes it.
Activations are annotated with logical axes via ``repro.distributed.api.lc``
(no-ops outside a mesh-rule context).
"""
from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp

from repro.distributed.api import lc
from .config import ModelConfig, Yarn


def _norm_init(d: int, dtype) -> dict:
    return {"scale": jnp.ones((d,), dtype=dtype)}


def rmsnorm(p: dict, x: jnp.ndarray, eps: float) -> jnp.ndarray:
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    out = xf * jax.lax.rsqrt(var + eps)
    return (out * p["scale"].astype(jnp.float32)).astype(x.dtype)


# ------------------------------------------------------------------- RoPE
def yarn_inv_freq(dim: int, theta: float, yarn: Yarn) -> jnp.ndarray:
    """YaRN's frequencies: the plain ones below the correction range,
    ``1/factor`` of them above it, a linear ramp between (HF's
    ``DeepseekV2YarnRotaryEmbedding``)."""
    def corr(rot):
        return (dim * math.log(yarn.original_max_pos / (rot * 2 * math.pi))
                / (2 * math.log(theta)))
    low = max(math.floor(corr(yarn.beta_fast)), 0)
    high = min(math.ceil(corr(yarn.beta_slow)), dim - 1)
    if low == high:
        high += 0.001
    extra = 1.0 / (theta ** (jnp.arange(0, dim, 2, dtype=jnp.float32) / dim))
    ramp = jnp.clip((jnp.arange(dim // 2, dtype=jnp.float32) - low)
                    / (high - low), 0, 1)
    return extra / yarn.factor * ramp + extra * (1 - ramp)


def rope_cos_sin(positions: jnp.ndarray, dim: int, theta: float,
                 yarn: Optional[Yarn] = None):
    """positions: (...,) int32 -> cos/sin of shape (..., dim//2)."""
    if yarn is None:
        inv = 1.0 / (theta ** (jnp.arange(0, dim, 2, dtype=jnp.float32) / dim))
        ang = positions.astype(jnp.float32)[..., None] * inv
        return jnp.cos(ang), jnp.sin(ang)
    ang = positions.astype(jnp.float32)[..., None] * yarn_inv_freq(
        dim, theta, yarn)
    return jnp.cos(ang) * yarn.cos_sin_scale, jnp.sin(ang) * yarn.cos_sin_scale


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
        "kv_norm": _norm_init(r, cfg.pdtype),
        "wuk": jax.random.normal(k[2], (r, h, hd), cfg.pdtype) * s,
        "wuv": jax.random.normal(k[3], (r, h, hd), cfg.pdtype) * s,
        "wkr": jax.random.normal(k[4], (d, rh), cfg.pdtype) * s,
        "wo": jax.random.normal(k[5], (h, hd, d), cfg.pdtype) * s,
    }


def _pairs_to_halves(x: jnp.ndarray) -> jnp.ndarray:
    """Rope dims paired (2i, 2i+1), as DeepSeek-V2's checkpoints lay them,
    moved to the halves (i, i + dim/2) that :func:`apply_rope` rotates."""
    return jnp.concatenate([x[..., 0::2], x[..., 1::2]], axis=-1)


def mla_apply(p: dict, cfg: ModelConfig, x: jnp.ndarray,
              positions: jnp.ndarray, *, kv_cache=None, decode_mask=None):
    """Multi-head latent attention (DeepSeek-V2).

    Prefill/train: decompress K/V per head and run the shared chunked GQA
    core (rope and nope score terms fused via head-dim concat).
    Decode: *absorbed* path — score and combine directly in the compressed
    c_kv space; the cache stores (c_kv, k_rope) only, c_kv after its RMSNorm
    (``kv_a_layernorm``).  kv_cache is the layer's cache writer, as in
    :func:`attention_apply`.  With YaRN the rope frequencies are YaRN's and
    the softmax scale carries mscale squared.
    """
    cd = cfg.cdtype
    hd, h, rh, r = cfg.hd, cfg.n_heads, cfg.rope_head_dim, cfg.kv_lora_rank
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"].astype(cd))
    q = lc(q, "batch", "seq", "heads", None)
    q_nope, q_rope = q[..., :hd], _pairs_to_halves(q[..., hd:])
    cos, sin = rope_cos_sin(positions, rh, cfg.rope_theta, cfg.yarn)
    q_rope = apply_rope(q_rope, cos, sin)

    c_kv = rmsnorm(p["kv_norm"],
                   jnp.einsum("bsd,dr->bsr", x, p["wdkv"].astype(cd)),
                   cfg.norm_eps)                                   # (B,S,r)
    k_rope_new = _pairs_to_halves(
        jnp.einsum("bsd,dk->bsk", x, p["wkr"].astype(cd)))         # (B,S,rh)
    scale = float(hd + rh) ** -0.5
    if cfg.yarn is not None:
        scale *= cfg.yarn.softmax_mscale
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
        p["shared"] = init_mlp(cfg, k[4], d_ff=f * cfg.n_shared_experts)
    return p


def moe_route(p: dict, cfg: ModelConfig, xt: jnp.ndarray):
    """Each row's top-k experts and their weights: softmax over the
    router's logits in float32, greedy top-k, renormalized only with
    ``norm_topk_prob`` (HF's ``routed_scaling_factor`` is 1 in every model
    here).  xt (T, d) -> (T, K) weights (float32) and expert ids."""
    logits = xt.astype(jnp.float32) @ p["router"].astype(jnp.float32)
    topv, topi = jax.lax.top_k(jax.nn.softmax(logits, axis=-1), cfg.top_k)
    if cfg.norm_topk_prob:
        topv = topv / jnp.maximum(topv.sum(-1, keepdims=True), 1e-9)
    return topv, topi


def moe_apply(p: dict, cfg: ModelConfig, x: jnp.ndarray):
    """Dropless top-k MoE.  Returns ``(y, counts)``.

    The token-expert pairs are sorted by expert and every expert's rows go
    through one grouped matmul (``jax.lax.ragged_dot``; on TPU a grouped
    matmul kernel that reads each expert's weights once), so no pair is
    dropped however the router loads the experts.  The weighted sum over a
    token's experts is taken in float32, beside the shared experts.
    ``counts`` (int32[4]) is what the routing did: token-expert pairs,
    experts with at least one row, the busiest expert's rows, and 1 (the
    layer-step), for the decode cache's MoE counters.
    """
    cd = cfg.cdtype
    b, s_len, d = x.shape
    e, k = cfg.n_experts, cfg.top_k
    xt = x.reshape(-1, d)                                   # (T, d)
    t = xt.shape[0]
    topv, topi = moe_route(p, cfg, xt)
    e_flat = topi.reshape(-1)                               # (T*K,)
    order = jnp.argsort(e_flat)                             # pairs by expert
    sizes = jnp.bincount(e_flat, length=e).astype(jnp.int32)
    xs = xt[order // k].astype(cd)                          # (T*K, d)
    h = jax.lax.ragged_dot(xs, p["wi"].astype(cd), sizes)
    g = (jax.lax.ragged_dot(xs, p["wg"].astype(cd), sizes)
         if "wg" in p else None)
    out = jax.lax.ragged_dot(_act(h, g, cfg.mlp), p["wo"].astype(cd), sizes)
    pairs = out[jnp.argsort(order)]                         # token order
    y = jnp.einsum("tkd,tk->td", pairs.reshape(t, k, d).astype(jnp.float32),
                   topv)
    if "shared" in p:
        y = y + mlp_apply(p["shared"], cfg, x).reshape(t, d)
    counts = jnp.stack([jnp.int32(t * k), jnp.sum(sizes > 0, dtype=jnp.int32),
                        jnp.max(sizes), jnp.int32(1)])
    return lc(y.astype(cd).reshape(b, s_len, d), "batch", "seq", None), counts
