"""Model assembly: decoder-only LMs (dense / MoE / MLA / hybrid / xLSTM),
encoder-decoder (whisper) and VLM (frontend-stub) backbones.

Layer stacks are scanned (``jax.lax.scan`` over stacked params) so HLO size
and compile time are layer-count independent (the one-token decode step
unrolls its scan, see ``Model._run_stack``); each scanned block is
optionally rematerialized (``cfg.remat``) for training memory.

Three entry points per model (built by :func:`build_model`):
  - ``forward(params, batch)``          -> logits  (teacher-forced, causal)
  - ``prefill(params, batch)``          -> (last-position logits, cache)
  - ``decode_step(params, cache, batch)`` -> (logits, cache)  (one token)
"""
from __future__ import annotations

from typing import Any, Optional

import jax
import jax.ad_checkpoint
import jax.numpy as jnp

from repro.distributed.api import lc  # logical sharding constraint (no-op
                                      # outside a mesh-rule context)
from .config import ModelConfig
from . import layers as L
from . import ssm as S


# --------------------------------------------------------------- embeddings
def init_embeddings(cfg: ModelConfig, rng) -> dict:
    k1, k2 = jax.random.split(rng)
    p = {"tok": jax.random.normal(k1, (cfg.padded_vocab, cfg.d_model),
                                  cfg.pdtype) * 0.02,
         "norm_f": L._norm_init(cfg.d_model, cfg.pdtype)}
    if not cfg.tie_embeddings:
        p["unembed"] = jax.random.normal(
            k2, (cfg.d_model, cfg.padded_vocab), cfg.pdtype) * 0.02
    return p


def embed(p: dict, cfg: ModelConfig, tokens: jnp.ndarray, *,
          one_hot: bool = False) -> jnp.ndarray:
    """Token rows of the embedding table.  ``one_hot`` looks them up as a
    one-hot matmul over the whole table (exact: one nonzero term a row).
    Decode uses it when the unembedding reads the same table each step
    anyway: both then read it in its own layout, where a gather on TPU
    first copies the whole table into row-major (94 MB a step for
    SmolLM-360M)."""
    tok = p["tok"].astype(cfg.cdtype)
    if one_hot:
        x = jnp.einsum("bsv,vd->bsd",
                       jax.nn.one_hot(tokens, tok.shape[0], dtype=tok.dtype),
                       tok)
    else:
        x = jnp.take(tok, tokens, axis=0)
    return lc(x, "batch", "seq", None)


def unembed(p: dict, cfg: ModelConfig, x: jnp.ndarray) -> jnp.ndarray:
    x = lc(x, "batch", "seq", None)     # gather SP residual before the head
    x = L.rmsnorm(p["norm_f"], x, cfg.norm_eps)
    w = (p["tok"].T if cfg.tie_embeddings else p["unembed"]).astype(cfg.cdtype)
    logits = jnp.einsum("bsd,dv->bsv", x, w)
    return lc(logits, "batch", "seq", "vocab")


# ------------------------------------------------------------------ blocks
def init_block(cfg: ModelConfig, rng) -> dict:
    """One decoder block's params (family-dependent)."""
    ks = jax.random.split(rng, 6)
    p: dict[str, Any] = {"ln1": L._norm_init(cfg.d_model, cfg.pdtype),
                         "ln2": L._norm_init(cfg.d_model, cfg.pdtype)}
    if cfg.family == "ssm":
        # xLSTM: both cell kinds present; per-layer selector picks one
        p["mlstm"] = S.init_mlstm(cfg, ks[0])
        p["slstm"] = S.init_slstm(cfg, ks[1])
        return p
    if cfg.attention == "mla":
        p["attn"] = L.init_mla(cfg, ks[0])
    elif cfg.attention != "none":
        p["attn"] = L.init_attention(cfg, ks[0])
    if cfg.family == "hybrid" and cfg.ssm_state > 0:
        p["mamba"] = S.init_mamba(cfg, ks[1])
    if cfg.is_moe:
        p["moe"] = L.init_moe(cfg, ks[2])
    elif cfg.d_ff > 0:
        p["mlp"] = L.init_mlp(cfg, ks[2])
    return p


def block_apply(p: dict, cfg: ModelConfig, x: jnp.ndarray,
                positions: jnp.ndarray, decode_mask=None,
                cache: Optional["LayerCache"] = None, layer_is_slstm=None):
    """Returns x.  In decode, ``cache`` is the layer's view of the stacked
    cache: the block reads its states from it and writes its updates into
    it.  decode_mask (B,T) marks valid cache slots (decode only);
    train/prefill masks are banded on the fly."""
    # TP-region input: gathered to FULL sequence exactly once here (Megatron
    # SP boundary); qkv/MLP dots consume it locally, outputs reduce-scatter
    # back into the seq-sharded residual (§Perf: constraining h to stay
    # seq-sharded made every projection gather independently — 3× traffic)
    h = lc(L.rmsnorm(p["ln1"], x, cfg.norm_eps), "batch", "seq", "dmodel")
    h = jax.ad_checkpoint.checkpoint_name(h, "blk_attn_in")
    window = cfg.sliding_window if cfg.attention == "sliding" else 0
    kv_cache = None if cache is None else cache.writer("kv")
    state = (lambda key: None) if cache is None else cache.read
    if cfg.family == "ssm":
        m_out, m_state = S.mlstm_apply(p["mlstm"], cfg, h, state("mlstm"))
        s_out, s_state = S.slstm_apply(p["slstm"], cfg, h, state("slstm"))
        sel = layer_is_slstm.astype(h.dtype)
        attn_out = sel * s_out + (1 - sel) * m_out
        if cache is not None:
            cache.write("mlstm", m_state)
            cache.write("slstm", s_state)
    elif cfg.family == "hybrid":
        a_out = L.attention_apply(p["attn"], cfg, h, positions, window=window,
                                  kv_cache=kv_cache, decode_mask=decode_mask)
        mb_out, mb_state = S.mamba_apply(p["mamba"], cfg, h, state("mamba"))
        attn_out = 0.5 * (a_out + mb_out)          # parallel heads (hymba)
        if cache is not None:
            cache.write("mamba", mb_state)
    elif cfg.attention == "mla":
        attn_out = L.mla_apply(p["attn"], cfg, h, positions,
                               kv_cache=kv_cache, decode_mask=decode_mask)
    else:
        attn_out = L.attention_apply(p["attn"], cfg, h, positions,
                                     window=window, kv_cache=kv_cache,
                                     decode_mask=decode_mask)
    # residual stream is sequence-sharded between TP regions (Megatron SP);
    # "seq_sp" maps to the model axis for train/prefill of wide models
    x = lc(x + attn_out, "batch", "seq_sp", "dmodel")
    h2 = lc(L.rmsnorm(p["ln2"], x, cfg.norm_eps), "batch", "seq", "dmodel")
    h2 = jax.ad_checkpoint.checkpoint_name(h2, "blk_mlp_in")
    if cfg.is_moe:
        x = x + L.moe_apply(p["moe"], cfg, h2)
    elif cfg.d_ff > 0:
        x = x + L.mlp_apply(p["mlp"], cfg, h2)
    return lc(x, "batch", "seq_sp", "dmodel")


# ----------------------------------------------------------- encoder blocks
def init_enc_block(cfg: ModelConfig, rng) -> dict:
    ks = jax.random.split(rng, 2)
    return {"ln1": L._norm_init(cfg.d_model, cfg.pdtype),
            "ln2": L._norm_init(cfg.d_model, cfg.pdtype),
            "attn": L.init_attention(cfg, ks[0]),
            "mlp": L.init_mlp(cfg, ks[1])}


def enc_block_apply(p: dict, cfg: ModelConfig, x: jnp.ndarray,
                    positions: jnp.ndarray):
    h = L.rmsnorm(p["ln1"], x, cfg.norm_eps)
    a = L.attention_apply(p["attn"], cfg, h, positions, causal=False)
    x = x + a
    h2 = L.rmsnorm(p["ln2"], x, cfg.norm_eps)
    return x + L.mlp_apply(p["mlp"], cfg, h2)


def init_xattn_block(cfg: ModelConfig, rng) -> dict:
    ks = jax.random.split(rng, 3)
    return {"ln1": L._norm_init(cfg.d_model, cfg.pdtype),
            "lnx": L._norm_init(cfg.d_model, cfg.pdtype),
            "ln2": L._norm_init(cfg.d_model, cfg.pdtype),
            "attn": L.init_attention(cfg, ks[0]),
            "xattn": L.init_attention(cfg, ks[1]),
            "mlp": L.init_mlp(cfg, ks[2])}


def xattn_block_apply(p: dict, cfg: ModelConfig, x, positions,
                      decode_mask, enc_out, cache=None):
    h = L.rmsnorm(p["ln1"], x, cfg.norm_eps)
    a = L.attention_apply(p["attn"], cfg, h, positions,
                          kv_cache=None if cache is None else cache.writer("kv"),
                          decode_mask=decode_mask)
    x = lc(x + a, "batch", "seq_sp", "dmodel")
    hx = L.rmsnorm(p["lnx"], x, cfg.norm_eps)
    xa = L.attention_apply(p["xattn"], cfg, hx, positions, causal=False,
                           use_rope=False, xattn_kv=enc_out)
    x = lc(x + xa, "batch", "seq_sp", "dmodel")
    h2 = L.rmsnorm(p["ln2"], x, cfg.norm_eps)
    x = x + L.mlp_apply(p["mlp"], cfg, h2)
    return lc(x, "batch", "seq_sp", "dmodel")


# ------------------------------------------------------------ decode cache
def write_layer(buf: jnp.ndarray, layer, upd: jnp.ndarray,
                write_at) -> jnp.ndarray:
    """Write one layer's decode update into a stacked cache buffer
    ``(L, B, ...)``, in place when the cache is donated.

    The update follows the buffer's layout: a recurrent state comes whole,
    ``(B, ...)``, and goes in at ``[layer]``; a positional cache ``(L, B, T,
    ...)`` gets one row per slot, ``(B, ...)``, where ``write_at`` (B, T)
    marks each slot's position (one per row, made once a step, outside the
    layer loop).  The rows go in as a select over the layer's slice
    written back with one dynamic-update-slice, which XLA fuses into one
    in-place pass in whatever layout it gave the cache.  A row scatter is
    not layout-free: on TPU it wants the row's axes minor, the padding-free
    layout of a (T, 5, 64) cache puts T minor, and XLA then copies the whole
    cache into the scatter's layout and back."""
    upd = upd.astype(buf.dtype)
    if upd.ndim == buf.ndim - 2:
        old = jax.lax.dynamic_index_in_dim(buf, layer, 0, False,
                                           allow_negative_indices=False)
        at = write_at.reshape(write_at.shape + (1,) * (old.ndim - 2))
        upd = jnp.where(at, upd[:, None], old)
    return jax.lax.dynamic_update_index_in_dim(buf, upd, layer, 0,
                                               allow_negative_indices=False)


class LayerCache:
    """Layer ``layer``'s view of the stacked decode cache that the layer
    loop carries.  ``read(key)`` gives the layer's slice of ``stack[key]``;
    ``write(key, new)`` puts the layer's update in (:func:`write_layer`) and
    returns the slice as it is after the write, so attention reads the
    current token from the same buffer the step updates in place."""

    def __init__(self, stack: dict, layer, write_at):
        self.stack, self.layer, self.write_at = stack, layer, write_at

    def read(self, key: str):
        return jax.tree.map(
            lambda a: jax.lax.dynamic_index_in_dim(
                a, self.layer, 0, False, allow_negative_indices=False),
            self.stack[key])

    def write(self, key: str, new):
        self.stack = {**self.stack, key: jax.tree.map(
            lambda buf, upd: write_layer(buf, self.layer, upd,
                                         self.write_at),
            self.stack[key], new)}
        return self.read(key)

    def writer(self, key: str):
        return lambda new: self.write(key, new)


# ------------------------------------------------------------------- Model
class Model:
    """Family-dispatching functional model."""

    def __init__(self, cfg: ModelConfig):
        self.cfg = cfg

    # -- init ---------------------------------------------------------
    def init(self, rng) -> dict:
        cfg = self.cfg
        k_emb, k_blocks, k_enc = jax.random.split(rng, 3)
        params = {"emb": init_embeddings(cfg, k_emb)}
        block_init = init_xattn_block if cfg.n_enc_layers else init_block
        kd = jax.random.split(k_blocks, cfg.n_layers)
        if cfg.scan_layers:
            params["blocks"] = jax.vmap(lambda k: block_init(cfg, k))(kd)
        else:
            params["blocks"] = [block_init(cfg, k) for k in kd]
        if cfg.n_enc_layers:
            ks = jax.random.split(k_enc, cfg.n_enc_layers)
            if cfg.scan_layers:
                params["enc"] = jax.vmap(lambda k: init_enc_block(cfg, k))(ks)
            else:
                params["enc"] = [init_enc_block(cfg, k) for k in ks]
        return params

    def init_shapes(self, rng=None) -> dict:
        """Parameter ShapeDtypeStructs without allocation (dry-run path)."""
        rng = rng if rng is not None else jax.random.PRNGKey(0)
        return jax.eval_shape(self.init, rng)

    # -- helpers ------------------------------------------------------
    def _slstm_mask(self) -> jnp.ndarray:
        m = jnp.zeros((self.cfg.n_layers, 1, 1, 1))
        for i in self.cfg.slstm_at:
            m = m.at[i].set(1.0)
        return m

    def _run_stack(self, params, x, positions, decode_mask=None, cache=None,
                   write_at=None, enc_out=None):
        """Apply the layer stack.  In decode the stacked cache rides in the
        carry: layer ``l`` writes its update at ``[l]`` and reads its slice
        back (:class:`LayerCache`), so a donated cache is updated in place
        and no step copies it.  The decode scan is unrolled: a one-token
        step is small, and a rolled loop's own work (slicing every weight
        stack and the layer index, the trip counter, ops XLA cannot fuse
        across the loop boundary) adds a quarter to its device ops."""
        cfg = self.cfg
        slstm_sel = self._slstm_mask() if cfg.family == "ssm" else None

        def body(carry, scanned):
            carry_x, stack = carry
            layer_p, sel, layer = scanned
            view = None if stack is None else LayerCache(stack, layer,
                                                         write_at)
            if enc_out is not None:
                out = xattn_block_apply(layer_p, cfg, carry_x, positions,
                                        decode_mask, enc_out, view)
            else:
                out = block_apply(layer_p, cfg, carry_x, positions,
                                  decode_mask, view, layer_is_slstm=sel)
            return (out, None if view is None else view.stack), None

        sel = (slstm_sel if slstm_sel is not None
               else jnp.zeros((cfg.n_layers, 1, 1, 1)))
        if cfg.scan_layers:
            fn = body
            if cfg.remat:
                policy = (jax.checkpoint_policies.save_only_these_names(
                    "blk_attn_in", "blk_mlp_in")
                    if cfg.remat_policy == "save_boundaries" else None)
                fn = jax.checkpoint(body, prevent_cse=False, policy=policy)
            (x, cache), _ = jax.lax.scan(
                fn, (x, cache),
                (params["blocks"], sel, jnp.arange(cfg.n_layers)),
                unroll=cache is not None)
            return x, cache
        for i in range(cfg.n_layers):
            (x, cache), _ = body((x, cache), (params["blocks"][i], sel[i], i))
        return x, cache

    def _encode(self, params, audio_embeds):
        cfg = self.cfg
        x = audio_embeds.astype(cfg.cdtype)
        positions = jnp.broadcast_to(jnp.arange(x.shape[1])[None],
                                     x.shape[:2])
        if cfg.scan_layers:
            def body(carry, layer_p):
                return enc_block_apply(layer_p, cfg, carry, positions), None
            x, _ = jax.lax.scan(body, x, params["enc"])
        else:
            for i in range(cfg.n_enc_layers):
                x = enc_block_apply(params["enc"][i], cfg, x, positions)
        return x

    # -- full-sequence forward (train) --------------------------------
    def forward(self, params, batch: dict) -> jnp.ndarray:
        cfg = self.cfg
        tokens = batch["tokens"]
        b, s_len = tokens.shape
        x = embed(params["emb"], cfg, tokens)
        if cfg.family == "vlm":
            img = batch["image_embeds"].astype(cfg.cdtype)
            n_img = img.shape[1]
            assert n_img <= s_len, (
                f"vlm: {n_img} image tokens exceed seq_len {s_len}")
            x = jnp.concatenate([img, x[:, n_img:]], axis=1)
        positions = jnp.broadcast_to(jnp.arange(s_len)[None], (b, s_len))
        enc_out = None
        if cfg.n_enc_layers:
            enc_out = self._encode(params, batch["audio_embeds"])
        x, _ = self._run_stack(params, x, positions, cache=None,
                               enc_out=enc_out)
        return unembed(params["emb"], cfg, x)

    # -- caches --------------------------------------------------------
    def cache_spec(self, batch: int, max_seq: int) -> dict:
        """Shapes/dtypes of the decode cache (per layer, stacked on L)."""
        cfg = self.cfg
        kd = jnp.dtype(cfg.compute_dtype)
        ls = cfg.n_layers

        def stack(shape):
            return (ls, *shape)
        if cfg.family == "ssm":
            spec = {"mlstm": {k: (stack(v), jnp.float32)
                              for k, v in S.mlstm_state_shape(cfg, batch).items()},
                    "slstm": {k: (stack(v), jnp.float32)
                              for k, v in S.slstm_state_shape(cfg, batch).items()}}
            return spec
        if cfg.family == "hybrid":
            w = min(cfg.sliding_window or max_seq, max_seq)
            spec = {"kv": {"k": (stack((batch, w, cfg.n_kv_heads, cfg.hd)), kd),
                           "v": (stack((batch, w, cfg.n_kv_heads, cfg.hd)), kd)},
                    "mamba": {k: (stack(v), jnp.float32)
                              for k, v in S.mamba_state_shape(cfg, batch).items()}}
            return spec
        if cfg.attention == "mla":
            return {"kv": {"c_kv": (stack((batch, max_seq, cfg.kv_lora_rank)), kd),
                           "k_rope": (stack((batch, max_seq, cfg.rope_head_dim)), kd)}}
        return {"kv": {"k": (stack((batch, max_seq, cfg.n_kv_heads, cfg.hd)), kd),
                       "v": (stack((batch, max_seq, cfg.n_kv_heads, cfg.hd)), kd)}}

    def init_cache(self, batch: int, max_seq: int) -> dict:
        return jax.tree.map(lambda sd: jnp.zeros(sd[0], sd[1]),
                            self.cache_spec(batch, max_seq),
                            is_leaf=lambda x: isinstance(x, tuple)
                            and len(x) == 2 and isinstance(x[0], tuple))

    def cache_shape_structs(self, batch: int, max_seq: int):
        return jax.tree.map(lambda sd: jax.ShapeDtypeStruct(sd[0], sd[1]),
                            self.cache_spec(batch, max_seq),
                            is_leaf=lambda x: isinstance(x, tuple)
                            and len(x) == 2 and isinstance(x[0], tuple))

    # -- decode --------------------------------------------------------
    def decode_step(self, params, cache, batch: dict):
        """One-token decode.  batch: tokens (B,1), pos (B,) current position,
        plus enc/vlm extras.  Returns the logits and the cache with each
        layer's new row (or recurrent state) written in.  The serving jit
        donates the cache (``ServingEngine``), so a call consumes its input
        cache and updates it in place: use only the returned one."""
        cfg = self.cfg
        tokens, pos = batch["tokens"], batch["pos"]
        b = tokens.shape[0]
        x = embed(params["emb"], cfg, tokens, one_hot=cfg.tie_embeddings)
        positions = pos[:, None]
        # enc-dec decode: encoder output was computed once at prefill and is
        # carried alongside the cache (real engines cache cross-attn KV)
        enc_out = batch.get("enc_out")

        if cfg.family == "ssm":
            decode_mask = None
            write_at = None                 # recurrent states only
        elif cfg.family == "hybrid":
            # ring-buffer window cache: slot i holds absolute position
            # p ≡ i (mod W); mask stale/unwritten/out-of-window slots
            w = cache["kv"]["k"].shape[2]
            slot_age = jnp.mod(pos[:, None] - jnp.arange(w)[None], w)
            write_at = slot_age == 0                      # (B, W)
            valid = (pos[:, None] - slot_age) >= 0
            within = slot_age < (cfg.sliding_window or 10**9)
            decode_mask = valid & within                  # (B, W)
        else:
            max_seq = (cache["kv"]["k"].shape[2] if cfg.attention != "mla"
                       else cache["kv"]["c_kv"].shape[2])
            write_at = jnp.arange(max_seq)[None] == pos[:, None]     # (B,S)
            decode_mask = jnp.arange(max_seq)[None] <= pos[:, None]  # (B,S)
        x, new_cache = self._run_stack(params, x, positions, decode_mask,
                                       cache=cache, write_at=write_at,
                                       enc_out=enc_out)
        logits = unembed(params["emb"], cfg, x)
        return logits[:, 0], new_cache

    # -- prefill -------------------------------------------------------
    def prefill(self, params, batch: dict):
        """Teacher-forced pass returning last-position logits (the cache
        write-back for prefill is exercised via decode; prefill measures the
        compute cost of context ingestion, which dominates)."""
        logits = self.forward(params, batch)
        return logits[:, -1]


def build_model(cfg: ModelConfig) -> Model:
    return Model(cfg)
