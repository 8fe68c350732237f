"""Plain float32 reference of a Llama-architecture decoder (SmolLM-360M).

The forward pass as HuggingFace's ``LlamaForCausalLM`` defines it, in
straightforward ``jax.numpy`` at ``precision=HIGHEST``: token embedding,
per layer RMSNorm, grouped-query attention with rotary embeddings on the
two halves of each head, the residual, RMSNorm, a SwiGLU MLP
(``down(silu(gate(x)) * up(x))``), the residual; a final RMSNorm and the
tied embedding as the output head.  No cache, no batching tricks: the
whole causal sequence at once.

``weights`` makes the benchmark's weights from the run's seed, on the
device in one jitted call, in the layout the program's model reads (layers
stacked on the leading axis) and in the type it serves (bfloat16).  The
program is handed them; the reference makes them again from the seed.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

INIT_STD = 0.02


def dims(m: dict) -> dict:
    d = int(m["hidden_size"])
    h = int(m["num_attention_heads"])
    return {"d": d, "h": h, "hkv": int(m["num_key_value_heads"]),
            "hd": int(m.get("head_dim", d // h)), "f": int(m["intermediate_size"]),
            "L": int(m["num_hidden_layers"]), "V": int(m["vocab_size"]),
            "eps": float(m["rms_norm_eps"]), "theta": float(m["rope_theta"])}


def _key(seed: int):
    s = int(seed) % 2 ** 63
    return jax.random.fold_in(jax.random.PRNGKey(s & 0xFFFFFFFF), s >> 32)


def weights(m: dict, seed: int, dtype=jnp.bfloat16):
    """Seeded weights: matrices N(0, 0.02^2), norm scales 1."""
    z = dims(m)
    L, d, h, hkv, hd, f, V = (z[k] for k in ("L", "d", "h", "hkv", "hd",
                                             "f", "V"))
    shapes = {"tok": (V, d), "wq": (L, d, h, hd), "wk": (L, d, hkv, hd),
              "wv": (L, d, hkv, hd), "wo": (L, h, hd, d), "wi": (L, d, f),
              "wg": (L, d, f), "wd": (L, f, d)}

    @jax.jit
    def make(key):
        ks = dict(zip(shapes, jax.random.split(key, len(shapes))))
        w = {k: (jax.random.normal(ks[k], s, jnp.float32) * INIT_STD
                 ).astype(dtype) for k, s in shapes.items()}
        ones = jnp.ones((d,), dtype)
        return {"emb": {"tok": w["tok"], "norm_f": {"scale": ones}},
                "blocks": {"ln1": {"scale": jnp.ones((L, d), dtype)},
                           "ln2": {"scale": jnp.ones((L, d), dtype)},
                           "attn": {"wq": w["wq"], "wk": w["wk"],
                                    "wv": w["wv"], "wo": w["wo"]},
                           "mlp": {"wi": w["wi"], "wg": w["wg"],
                                   "wo": w["wd"]}}}

    return make(_key(seed))


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def _rope(x, pos, theta):
    hd = x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = pos[:, None].astype(jnp.float32) * inv[None, :]       # (S, hd/2)
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    x1, x2 = x[..., : hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def forward(p, tokens, m: dict):
    """Logits (B, S, V) in float32 for token rows (B, S), causal."""
    z = dims(m)
    g = z["h"] // z["hkv"]
    b, s = tokens.shape
    pos = jnp.arange(s)
    mask = pos[None, :] <= pos[:, None]                          # (S, S)

    def layer(x, w):
        h = _rms(x, w["ln1"]["scale"], z["eps"])
        q = _rope(jnp.einsum("bsd,dhk->bshk", h, w["attn"]["wq"]), pos,
                  z["theta"])
        k = _rope(jnp.einsum("bsd,dhk->bshk", h, w["attn"]["wk"]), pos,
                  z["theta"])
        v = jnp.einsum("bsd,dhk->bshk", h, w["attn"]["wv"])
        k = jnp.repeat(k, g, axis=2)                     # head j reads kv j//g
        v = jnp.repeat(v, g, axis=2)
        sc = jnp.einsum("bqhk,bthk->bhqt", q, k) / np.sqrt(z["hd"])
        sc = jnp.where(mask[None, None], sc, -jnp.inf)
        a = jnp.einsum("bhqt,bthk->bqhk", jax.nn.softmax(sc, -1), v)
        x = x + jnp.einsum("bqhk,hkd->bqd", a, w["attn"]["wo"])
        h = _rms(x, w["ln2"]["scale"], z["eps"])
        up = jnp.einsum("bsd,df->bsf", h, w["mlp"]["wi"])
        gate = jnp.einsum("bsd,df->bsf", h, w["mlp"]["wg"])
        return x + jnp.einsum("bsf,fd->bsd", jax.nn.silu(gate) * up,
                              w["mlp"]["wo"]), None

    with jax.default_matmul_precision("highest"):
        x, _ = jax.lax.scan(layer, p["emb"]["tok"][tokens], p["blocks"])
        x = _rms(x, p["emb"]["norm_f"]["scale"], z["eps"])
        return jnp.einsum("bsd,vd->bsv", x, p["emb"]["tok"])


def _fp8(w):
    """Per-tensor scaled float8 (e4m3) weights, back in float32."""
    s = jnp.max(jnp.abs(w)) / 448.0
    return (w / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def token_gaps(m: dict, seed: int, seqs: list, control: bool = False,
               block: int = 8) -> np.ndarray:
    """For each served sequence ``(prompt, out)``: how far below the
    reference's best logit each served token's logit lies, at every output
    position.  ``control=True`` reads instead the token that the reference
    computed with float8 weights puts first (the lower-precision control).
    Sequences run in blocks of ``block`` rows, padded at the end."""
    if not seqs:
        return np.zeros(0)
    p32 = jax.tree.map(lambda w: w.astype(jnp.float32), weights(m, seed))
    if control:
        p32 = jax.tree.map(lambda w: _fp8(w) if w.ndim >= 2 else w, p32)
        ref = jax.tree.map(lambda w: w.astype(jnp.float32), weights(m, seed))
    fwd = jax.jit(lambda p, t: forward(p, t, m))
    # every block at one width (a multiple of 64), so one program serves all
    width = -(-max(len(pr) + len(out) - 1 for pr, out in seqs) // 64) * 64
    gaps = []
    for i in range(0, len(seqs), block):
        part = seqs[i:i + block]
        rows = [list(pr) + list(out[:-1]) for pr, out in part]
        toks = np.zeros((block, width), np.int32)
        for j, r in enumerate(rows):
            toks[j, :len(r)] = r
        lg = np.asarray(fwd(p32, jnp.asarray(toks)))
        lr = np.asarray(fwd(ref, jnp.asarray(toks))) if control else lg
        for j, (pr, out) in enumerate(part):
            at = np.arange(len(pr) - 1, len(pr) - 1 + len(out))
            pick = (np.argmax(lg[j, at], -1) if control
                    else np.asarray(out))
            best = lr[j, at].max(-1)
            gaps.append(best - lr[j, at, pick])
    return np.concatenate(gaps) if gaps else np.zeros(0)
