"""deepseek-v2-lite-16b [moe]: DeepSeek-V2-Lite as published
(huggingface.co/deepseek-ai/DeepSeek-V2-Lite, config.json; arXiv:2405.04434).

27 layers, d_model 2048.  MLA with 16 heads, no q-LoRA, kv_lora_rank 512,
qk_nope/v head dims 128, qk_rope 64, YaRN rope (factor 40 over 4,096
original positions).  Layer 0 is a dense SwiGLU of width 10,944
(first_k_dense_replace 1); layers 1-26 route each token to 6 of 64 experts
of width 1,408 by softmax scores, greedy, without renormalizing
(norm_topk_prob false, routed_scaling_factor 1), beside 2 shared experts.
Untied embeddings over 102,400 tokens.  15,706,484,224 parameters.
"""
from repro.models.config import ModelConfig, Yarn

CONFIG = ModelConfig(
    name="deepseek-v2-lite-16b", family="moe",
    n_layers=27, d_model=2048, n_heads=16, n_kv_heads=16, head_dim=128,
    d_ff=10_944, vocab_size=102_400, mlp="swiglu", norm_eps=1e-6,
    attention="mla", kv_lora_rank=512, rope_head_dim=64,
    yarn=Yarn(factor=40.0, original_max_pos=4096, beta_fast=32.0,
              beta_slow=1.0, mscale=0.707, mscale_all_dim=0.707),
    n_experts=64, n_shared_experts=2, top_k=6, expert_d_ff=1408,
    n_dense_layers=1, norm_topk_prob=False,
    # layers unstacked: the grouped matmul kernel reads an expert stack only
    # whole, so a stacked layer's slice would be copied each step (5.87 GB
    # of temporaries at 9 layers; the v5e compile runs out of memory)
    scan_layers=False,
)
