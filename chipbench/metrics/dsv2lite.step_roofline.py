"""dsv2lite.step_roofline: the decode steps' least time over their device
time, %: the roofline share of DeepSeek-V2-Lite's whole decode step, whose
expert path (one grouped matmul over the experts a layer's rows reach)
reads most of its bytes.

The least bytes of the window's decode steps are what each step must read
at least once: every weight outside the routed experts (attention, norms,
the dense MLP, the router and shared experts, the output head; of the token
embedding only the slots' rows), each routed expert that at least one row
reached in that layer-step (3 x hidden x moe_intermediate x 2 B, from the
engine's ``moe_experts_touched`` counter), and the latent and rope keys of
every attended position on every layer (kv_lora_rank + qk_rope_head_dim
values of 2 B, from the harness's ``ctx_tokens``).  Over the chip's HBM
bandwidth that is the least time; the device time is that of the window's
``jit_decode_step`` programs in the trace.  bfloat16 weights and cache."""

PROGRAM = "jit_decode_step"
BYTES = 2                                          # bfloat16


def fixed_bytes(m: dict, slots: int) -> int:
    """A step's bytes outside the routed experts and the KV cache."""
    d, h = m["hidden_size"], m["num_attention_heads"]
    r, rope = m["kv_lora_rank"], m["qk_rope_head_dim"]
    nope, vd = m["qk_nope_head_dim"], m["v_head_dim"]
    L, nd = m["num_hidden_layers"], m["first_k_dense_replace"]
    attn = (d * h * (nope + rope) + d * (r + rope) + r + r * h * (nope + vd)
            + h * vd * d + 2 * d)                  # + kv, input, post norms
    dense = 3 * d * m["intermediate_size"]
    moe = (3 * d * m["moe_intermediate_size"] * (m["n_shared_experts"] or 0)
           + d * m["n_routed_experts"])
    head = d * m["vocab_size"] + d + slots * d     # + final norm, token rows
    return BYTES * (L * attn + nd * dense + (L - nd) * moe + head)


def expert_bytes(m: dict) -> int:
    return BYTES * 3 * m["hidden_size"] * m["moe_intermediate_size"]


def kv_bytes_per_position(m: dict) -> int:
    """One cached position of one layer: 1,152 B at DeepSeek-V2's widths."""
    return BYTES * (m["kv_lora_rank"] + m["qk_rope_head_dim"])


def least_bytes(m: dict, rec: dict) -> int:
    return (rec["steps"] * fixed_bytes(m, rec["max_batch"])
            + rec["moe_experts_touched"] * expert_bytes(m)
            + rec["ctx_tokens"] * m["num_hidden_layers"]
            * kv_bytes_per_position(m))


def read(ctx):
    rec = ctx["rec"]
    runs, spent = ctx["trace"]["programs"].get(PROGRAM, (0, 0.0))
    if not rec.get("steps") or "moe_experts_touched" not in rec \
            or spent <= 0 or not ctx["peaks"]:
        return None
    if runs != rec["steps"]:
        raise ValueError(f"{PROGRAM}: {runs} runs in the trace, "
                         f"{rec['steps']} decode steps counted")
    bw = ctx["peaks"]["hbm_bytes_per_s"]
    return 100.0 * least_bytes(ctx["cfg"], rec) / bw / spent
