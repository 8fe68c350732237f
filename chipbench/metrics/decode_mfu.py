"""decode_mfu: the model FLOPs of the tokens decoded in the traced window
over the window times the chip's bf16 peak, %.

A decoded token (a prompt token fed through a decode step, or a generated
one) costs 2 FLOPs per matmul parameter (the attention projections, the
SwiGLU MLP and the output head) plus attention over its context:
2 x 2 x heads x head_dim per context position per layer (scores and the
weighted sum).  Only occupied slots count; idle slots of a step are not
model work."""


def matmul_params(m: dict) -> int:
    d, f = m["hidden_size"], m["intermediate_size"]
    h, hkv = m["num_attention_heads"], m["num_key_value_heads"]
    hd = m.get("head_dim", d // h)
    per_layer = d * h * hd + 2 * d * hkv * hd + h * hd * d + 3 * d * f
    return m["num_hidden_layers"] * per_layer + m["vocab_size"] * d


def flops(m: dict, tokens: int, ctx_positions: int) -> float:
    """FLOPs of ``tokens`` decoded tokens that attend over
    ``ctx_positions`` cached positions in all."""
    d, h = m["hidden_size"], m["num_attention_heads"]
    hd = m.get("head_dim", d // h)
    attn = 4 * h * hd * m["num_hidden_layers"]
    return 2.0 * matmul_params(m) * tokens + attn * ctx_positions


def read(ctx):
    rec, tr = ctx["rec"], ctx["trace"]
    if not rec.get("slot_steps") or tr["window_s"] <= 0 or not ctx["peaks"]:
        return None
    f = flops(ctx["cfg"]["model"], rec["slot_steps"], rec["ctx_tokens"])
    return 100.0 * f / (tr["window_s"] * ctx["peaks"]["bf16_flops"])
