"""Plain float32 reference of DeepSeek-V2's decoder (DeepSeek-V2-Lite).

The forward pass as HuggingFace's ``modeling_deepseek.py``
(``DeepseekV2ForCausalLM``) defines it, in straightforward ``jax.numpy``
under ``jax.default_matmul_precision("highest")``: no cache, no kernels,
the whole causal sequence at once.  Per layer: RMSNorm; multi-head latent
attention computed decompressed (``kv_a_proj_with_mqa``, RMSNorm
``kv_a_layernorm`` over the compressed KV, ``kv_b_proj`` to every head's
full K and V), with YaRN rotary embeddings on the rope part; the residual;
RMSNorm; a dense SwiGLU MLP on the first ``first_k_dense_replace`` layers,
routed experts plus shared experts on the rest; the residual.  A final
RMSNorm and the (untied) output head.

Departures from ``modeling_deepseek.py``, each at its place below:

* weights are read in the layout the program's model keeps: a list of
  layers (or layers stacked on the leading axis), ``q_proj`` as ``wq``
  (d, heads, nope + rope), ``kv_a_proj_with_mqa`` split into ``wdkv``
  (d, r) and ``wkr`` (d, rope), ``kv_b_proj`` split into ``wuk`` and
  ``wuv`` (r, heads, 128), ``o_proj`` as ``wo`` (heads, 128, d);
* every token's routed experts are computed densely over all experts, each
  expert's output weighted by the token's routing weight, which is zero
  off its top-k (HF gathers each expert's tokens; the sum is the same);
* everything is float32: HF adds the shared experts to the routed output
  after casting it to the model's dtype;
* training-only parts (the auxiliary loss, dropout) are left out.

``weights`` makes the benchmark's weights from the run's seed, on the
device, in the type it serves (bfloat16) and one layer at a time, so that
no float32 copy of a layer's 64 experts beside another is ever held.
``forward`` upcasts one layer's weights at a time, so the reference runs
beside bfloat16 weights of the whole model on one chip.

This file stands alone (``jax`` and ``numpy`` only): the benchmark keeps an
identical copy as ``chipbench/reference/deepseek_v2.py``.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

INIT_STD = 0.02


def dims(m: dict) -> dict:
    """The sizes the reference reads from an HF ``config.json`` dict."""
    ys = m.get("rope_scaling") or None
    return {"d": int(m["hidden_size"]), "h": int(m["num_attention_heads"]),
            "r": int(m["kv_lora_rank"]), "nope": int(m["qk_nope_head_dim"]),
            "rope": int(m["qk_rope_head_dim"]), "vd": int(m["v_head_dim"]),
            "f": int(m["intermediate_size"]),
            "fe": int(m["moe_intermediate_size"]),
            "E": int(m["n_routed_experts"]), "K": int(m["num_experts_per_tok"]),
            "shared": int(m["n_shared_experts"] or 0),
            "dense": int(m["first_k_dense_replace"]),
            "L": int(m["num_hidden_layers"]), "V": int(m["vocab_size"]),
            "eps": float(m["rms_norm_eps"]), "theta": float(m["rope_theta"]),
            "norm_topk": bool(m["norm_topk_prob"]),
            "scaling": float(m["routed_scaling_factor"]), "yarn": ys}


# ------------------------------------------------------------- weights
def _key(seed: int):
    s = int(seed) % 2 ** 63
    return jax.random.fold_in(jax.random.PRNGKey(s & 0xFFFFFFFF), s >> 32)


def layer_shapes(m: dict, moe: bool) -> dict:
    """One decoder layer's matrices, in the program's layout."""
    z = dims(m)
    d, h, r, rh, nope, vd = (z[k] for k in ("d", "h", "r", "rope", "nope",
                                            "vd"))
    out = {"attn": {"wq": (d, h, nope + rh), "wdkv": (d, r), "wkr": (d, rh),
                    "wuk": (r, h, nope), "wuv": (r, h, vd), "wo": (h, vd, d)}}
    if not moe:
        out["mlp"] = {"wi": (d, z["f"]), "wg": (d, z["f"]), "wo": (z["f"], d)}
        return out
    e, fe, fs = z["E"], z["fe"], z["fe"] * z["shared"]
    out["moe"] = {"router": (d, e), "wi": (e, d, fe), "wg": (e, d, fe),
                  "wo": (e, fe, d)}
    if fs:
        out["moe"]["shared"] = {"wi": (d, fs), "wg": (d, fs), "wo": (fs, d)}
    return out


def weights(m: dict, seed: int, dtype=jnp.bfloat16):
    """Seeded weights: matrices N(0, 0.02^2), norm scales 1, stored in
    ``dtype``.  Each layer is drawn on its own (one program per kind of
    layer), so float32 never holds more than one layer, and the layers are
    a list, as the program reads them unstacked (``scan_layers=False``)."""
    z = dims(m)
    d, r = z["d"], z["r"]
    key = _key(seed)

    def draw(shapes_, k):
        leaves, tree = jax.tree_util.tree_flatten(
            shapes_, is_leaf=lambda s: isinstance(s, tuple))
        ks = jax.random.split(k, len(leaves))
        return jax.tree_util.tree_unflatten(tree, [
            (jax.random.normal(kk, s, jnp.float32) * INIT_STD).astype(dtype)
            for kk, s in zip(ks, leaves)])

    makers = {moe: jax.jit(lambda k, moe=moe: draw(layer_shapes(m, moe), k))
              for moe in (False, True)}

    def layer(i):
        w = makers[i >= z["dense"]](jax.random.fold_in(key, i))
        w["ln1"] = {"scale": jnp.ones((d,), dtype)}
        w["ln2"] = {"scale": jnp.ones((d,), dtype)}
        w["attn"]["kv_norm"] = {"scale": jnp.ones((r,), dtype)}
        return w

    emb = jax.jit(lambda k: draw({"tok": (z["V"], d),
                                  "unembed": (d, z["V"])}, k))(
        jax.random.fold_in(key, z["L"]))
    emb["norm_f"] = {"scale": jnp.ones((d,), dtype)}
    p = {"emb": emb, "blocks": [layer(i) for i in range(z["dense"], z["L"])]}
    if z["dense"]:
        p["dense"] = [layer(i) for i in range(z["dense"])]
    return p


# ------------------------------------------------------------- forward
def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def yarn_get_mscale(scale: float, mscale: float) -> float:
    return 1.0 if scale <= 1 else 0.1 * mscale * math.log(scale) + 1.0


def inv_freq_and_mscale(z: dict):
    """Rope frequencies (rope/2,) and the cos/sin factor: plain, or
    ``DeepseekV2YarnRotaryEmbedding``'s."""
    dim, base = z["rope"], z["theta"]
    extra = 1.0 / base ** (np.arange(0, dim, 2, dtype=np.float32) / dim)
    ys = z["yarn"]
    if not ys:
        return extra, 1.0
    f, orig = float(ys["factor"]), int(ys["original_max_position_embeddings"])

    def corr(rot):            # yarn_find_correction_dim
        return dim * math.log(orig / (rot * 2 * math.pi)) / (2 * math.log(base))
    low = max(math.floor(corr(ys["beta_fast"])), 0)
    high = min(math.ceil(corr(ys["beta_slow"])), dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(dim // 2, dtype=np.float32) - low)
                   / (high - low), 0, 1)
    mask = 1.0 - ramp                                  # inv_freq_mask
    inv = extra / f * (1 - mask) + extra * mask
    ms = (yarn_get_mscale(f, ys["mscale"])
          / yarn_get_mscale(f, ys["mscale_all_dim"]))
    return inv.astype(np.float32), ms


def softmax_scale(z: dict) -> float:
    s = (z["nope"] + z["rope"]) ** -0.5
    ys = z["yarn"]
    if ys and ys.get("mscale_all_dim"):
        ms = yarn_get_mscale(float(ys["factor"]), ys["mscale_all_dim"])
        s = s * ms * ms
    return s


def _rope(x, pos, z):
    """HF's ``apply_rotary_pos_emb``: pairs (2i, 2i+1) moved to halves,
    then ``x * cos + rotate_half(x) * sin``.  x (B, S, H, rope)."""
    inv, ms = inv_freq_and_mscale(z)
    freqs = pos[:, None].astype(jnp.float32) * jnp.asarray(inv)[None]
    emb = jnp.concatenate([freqs, freqs], -1)               # (S, rope)
    cos, sin = jnp.cos(emb) * ms, jnp.sin(emb) * ms
    b, s, h, d = x.shape
    x = x.reshape(b, s, h, d // 2, 2).swapaxes(3, 4).reshape(b, s, h, d)
    rot = jnp.concatenate([-x[..., d // 2:], x[..., :d // 2]], -1)
    return x * cos[None, :, None] + rot * sin[None, :, None]


def _attention(x, w, z):
    b, s, _ = x.shape
    pos = jnp.arange(s)
    q = jnp.einsum("bsd,dhk->bshk", x, w["wq"])
    q_nope, q_pe = q[..., :z["nope"]], q[..., z["nope"]:]
    ckv = _rms(jnp.einsum("bsd,dr->bsr", x, w["wdkv"]),
               w["kv_norm"]["scale"], z["eps"])              # kv_a_layernorm
    k_pe = jnp.einsum("bsd,dk->bsk", x, w["wkr"])[:, :, None]
    k_nope = jnp.einsum("bsr,rhk->bshk", ckv, w["wuk"])
    v = jnp.einsum("bsr,rhk->bshk", ckv, w["wuv"])
    q_pe, k_pe = _rope(q_pe, pos, z), _rope(k_pe, pos, z)
    qs = jnp.concatenate([q_nope, q_pe], -1)
    ks = jnp.concatenate([k_nope, jnp.broadcast_to(
        k_pe, k_nope.shape[:3] + (z["rope"],))], -1)
    sc = jnp.einsum("bqhk,bthk->bhqt", qs, ks) * softmax_scale(z)
    mask = pos[None, :] <= pos[:, None]
    sc = jnp.where(mask[None, None], sc, -jnp.inf)
    a = jnp.einsum("bhqt,bthk->bqhk", jax.nn.softmax(sc, -1), v)
    return jnp.einsum("bqhk,hkd->bqd", a, w["wo"])


def _mlp(x, w):
    return jnp.einsum("...f,fd->...d",
                      jax.nn.silu(jnp.einsum("...d,df->...f", x, w["wg"]))
                      * jnp.einsum("...d,df->...f", x, w["wi"]), w["wo"])


def route(x, router, z):
    """MoEGate, greedy: softmax scores in float32, top-k, renormalized
    with ``norm_topk_prob``, else times ``routed_scaling_factor`` (HF applies
    one or the other).  Returns the dense (T, E) weights (zero off each
    row's top-k) and the top-k ids."""
    scores = jax.nn.softmax(x @ router, -1)
    topv, topi = jax.lax.top_k(scores, z["K"])
    if z["norm_topk"] and z["K"] > 1:
        topv = topv / topv.sum(-1, keepdims=True)
    else:
        topv = topv * z["scaling"]
    dense = jnp.zeros_like(scores).at[
        jnp.arange(x.shape[0])[:, None], topi].set(topv)
    return dense, topi


def _moe(x, w, z, fp8: bool):
    """Routed experts (dense over all experts, masked by the routing
    weights) plus the shared experts.  The routed experts' weights come as
    stored and are upcast one expert at a time, so a layer's 64 experts are
    never held in float32 together.  Also returns how many rows' top-k sets
    differ when the router's input is rounded to bfloat16 (the program's
    activations' type)."""
    b, s, d = x.shape
    xt = x.reshape(-1, d)
    wts, topi = route(xt, w["router"], z)
    _, topi16 = route(xt.astype(jnp.bfloat16).astype(jnp.float32),
                      w["router"], z)
    flips = jnp.sum(jnp.any(jnp.sort(topi, -1) != jnp.sort(topi16, -1), -1))
    stacks = {k: w[k] for k in ("wi", "wg", "wo")}
    scales = {k: _fp8_scale(a) for k, a in stacks.items()}

    def expert(y, e):
        we = {k: _cast(a[e], scales[k] if fp8 else None)
              for k, a in stacks.items()}
        a = (jax.nn.silu(xt @ we["wg"]) * (xt @ we["wi"])
             * jax.lax.dynamic_slice_in_dim(wts, e, 1, axis=1))
        return y + a @ we["wo"], None

    y, _ = jax.lax.scan(expert, jnp.zeros_like(xt), jnp.arange(z["E"]))
    if "shared" in w:
        y = y + _mlp(xt, w["shared"])
    return y.reshape(b, s, d), flips


def _fp8_scale(w):
    return jnp.max(jnp.abs(w)).astype(jnp.float32) / 448.0


def _cast(w, fp8_scale=None):
    """``w`` in float32; with ``fp8_scale``, first rounded to scaled
    float8 (e4m3)."""
    w = w.astype(jnp.float32)
    if fp8_scale is None:
        return w
    return ((w / fp8_scale).astype(jnp.float8_e4m3fn).astype(jnp.float32)
            * fp8_scale)


def _upcast(tree, fp8: bool):
    """Every weight in float32; with ``fp8`` each matrix first rounded to
    float8 with a scale of its own (per tensor)."""
    return jax.tree.map(
        lambda a: _cast(a, _fp8_scale(a) if fp8 and a.ndim >= 2 else None),
        tree)


def _layer(x, w, m: dict, fp8: bool):
    """One decoder layer, its weights upcast inside (the routed experts
    one at a time, in :func:`_moe`)."""
    z = dims(m)
    experts = {}
    if "moe" in w:
        experts = {k: w["moe"][k] for k in ("wi", "wg", "wo")}
        w = {**w, "moe": {k: a for k, a in w["moe"].items()
                          if k not in experts}}
    w = _upcast(w, fp8)
    with jax.default_matmul_precision("highest"):
        x = x + _attention(_rms(x, w["ln1"]["scale"], z["eps"]),
                           w["attn"], z)
        h = _rms(x, w["ln2"]["scale"], z["eps"])
        if "moe" in w:
            y, flips = _moe(h, {**w["moe"], **experts}, z, fp8)
        else:
            y, flips = _mlp(h, w["mlp"]), jnp.int32(0)
    return x + y, flips


def _embed(emb, tokens, fp8: bool):
    tok = emb["tok"]
    return _cast(tok[tokens], _fp8_scale(tok) if fp8 else None)


def _head(emb, x, m: dict, fp8: bool):
    z = dims(m)
    with jax.default_matmul_precision("highest"):
        x = _rms(x, emb["norm_f"]["scale"].astype(jnp.float32), z["eps"])
        return jnp.einsum("bsd,dv->bsv", x, _upcast(emb["unembed"], fp8))


_LAYER = jax.jit(_layer, static_argnums=(2, 3))
_EMBED = jax.jit(_embed, static_argnums=(2,))
_HEAD = jax.jit(_head, static_argnums=(2, 3))


class _Frozen(dict):
    """A config dict that can be a static argument of a jitted function."""

    def __hash__(self):
        return hash(repr(sorted(self.items())))


def forward(p, tokens, m: dict, *, fp8: bool = False, flips=None):
    """Logits (B, S, V) in float32 for token rows (B, S), causal.  ``p``
    may hold bfloat16: each layer's weights are upcast (with ``fp8``, also
    rounded to float8) only while it runs.  ``flips``, a list, gets for
    each MoE layer the rows whose top-k set changes when the router's input
    is rounded to bfloat16, and the rows routed."""
    m = _Frozen(m)
    x = _EMBED(p["emb"], tokens, fp8)
    for name in ("dense", "blocks"):
        for w in _layers(p.get(name, [])):
            x, n = _LAYER(x, w, m, fp8)
            if flips is not None and name == "blocks":
                flips.append((int(n), int(tokens.size)))
    return _HEAD(p["emb"], x, m, fp8)


def _layers(stack) -> list:
    """A list of layers, from a list or from layers stacked on axis 0."""
    if isinstance(stack, list):
        return stack
    n = jax.tree.leaves(stack)[0].shape[0]
    return [jax.tree.map(lambda a: a[i], stack) for i in range(n)]


def token_gaps(m: dict, seed: int, seqs: list, control: bool = False,
               block: int = 4, flips=None) -> np.ndarray:
    """For each served sequence ``(prompt, out)``: how far below the
    reference's best logit each served token's logit lies, at every output
    position.  ``control=True`` reads instead the token that the reference
    computed with float8 weights puts first (the lower-precision control).
    Sequences run in blocks of ``block`` rows, padded at the end; the
    weights are made once, in bfloat16, and upcast a layer at a time."""
    if not seqs:
        return np.zeros(0)
    p = weights(m, seed)
    width = -(-max(len(pr) + len(out) - 1 for pr, out in seqs) // 64) * 64
    gaps = []
    for i in range(0, len(seqs), block):
        part = seqs[i:i + block]
        toks = np.zeros((block, width), np.int32)
        for j, (pr, out) in enumerate(part):
            row = list(pr) + list(out[:-1])
            toks[j, :len(row)] = row
        toks = jnp.asarray(toks)
        lr = np.asarray(forward(p, toks, m, flips=flips))
        lg = np.asarray(forward(p, toks, m, fp8=True)) if control else lr
        for j, (pr, out) in enumerate(part):
            at = np.arange(len(pr) - 1, len(pr) - 1 + len(out))
            pick = (np.argmax(lg[j, at], -1) if control
                    else np.asarray(out))
            best = lr[j, at].max(-1)
            gaps.append(best - lr[j, at, pick])
    return np.concatenate(gaps) if gaps else np.zeros(0)
