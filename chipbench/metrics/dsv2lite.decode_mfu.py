"""dsv2lite.decode_mfu: the model FLOPs of the tokens DeepSeek-V2-Lite
decoded in the traced window over the window times the chip's bf16 peak, %.

A decoded token (a prompt token fed through a decode step, or a generated
one) costs 2 FLOPs per multiply-add of the step as the program computes it:
on every layer, absorbed latent attention (the query projection, the
compressed KV and rope key, the query's absorption through ``kv_b_proj``'s
key half and the output's through its value half, the output projection);
the dense SwiGLU MLP on the leading dense layers; on the others the router,
the top-k routed experts and the shared experts; and the output head.
Attention over its context costs, per cached position per layer, 2 x heads
x (2 x kv_lora_rank + qk_rope_head_dim) FLOPs (scores against the latent
and rope keys, the weighted sum of latents).  Only occupied slots count;
``slot_steps`` and ``ctx_tokens`` come from the harness's record.  The keys
are those of the published ``config.json``, at the top level of the
configuration file."""


def macs_per_token(m: dict) -> int:
    d, h = m["hidden_size"], m["num_attention_heads"]
    r, rope = m["kv_lora_rank"], m["qk_rope_head_dim"]
    nope, vd = m["qk_nope_head_dim"], m["v_head_dim"]
    L, nd = m["num_hidden_layers"], m["first_k_dense_replace"]
    attn = (d * h * (nope + rope) + d * (r + rope) + h * nope * r
            + h * r * vd + h * vd * d)
    dense = 3 * d * m["intermediate_size"]
    experts = m["num_experts_per_tok"] + (m["n_shared_experts"] or 0)
    moe = 3 * d * m["moe_intermediate_size"] * experts \
        + d * m["n_routed_experts"]
    return L * attn + nd * dense + (L - nd) * moe + d * m["vocab_size"]


def flops(m: dict, tokens: int, ctx_positions: int) -> float:
    """FLOPs of ``tokens`` decoded tokens that attend over
    ``ctx_positions`` cached positions in all."""
    per_pos = 2 * m["num_attention_heads"] * (
        2 * m["kv_lora_rank"] + m["qk_rope_head_dim"])
    return (2.0 * macs_per_token(m) * tokens
            + per_pos * m["num_hidden_layers"] * ctx_positions)


def read(ctx):
    rec, tr = ctx["rec"], ctx["trace"]
    if not rec.get("slot_steps") or tr["window_s"] <= 0 or not ctx["peaks"]:
        return None
    f = flops(ctx["cfg"], rec["slot_steps"], rec["ctx_tokens"])
    return 100.0 * f / (tr["window_s"] * ctx["peaks"]["bf16_flops"])
