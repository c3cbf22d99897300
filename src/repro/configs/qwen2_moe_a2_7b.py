"""qwen2-moe-a2.7b [moe] — Qwen1.5-MoE-A2.7B: 24L d=2048, 16 heads of 128
(MHA, q/k/v bias), RoPE theta 1e6, vocab 151936 (untied), every layer
sparse: 60 routed SwiGLU experts of 1408, top-4 from a softmax over all 60
logits with no renormalisation, plus 4 shared experts of 1408 fused into
one SwiGLU block of 5632 whose output is scaled by sigmoid(x . w_g).
14.3 B parameters, 2.7 B active.  [hf:Qwen/Qwen1.5-MoE-A2.7B config.json,
model_type qwen2_moe]
"""

from repro.configs.base import ArchConfig

CONFIG = ArchConfig(
    name="qwen2-moe-a2.7b",
    family="moe",
    n_layers=24,
    d_model=2048,
    n_heads=16,
    n_kv_heads=16,
    head_dim=128,
    d_ff=5632,
    vocab=151936,
    qkv_bias=True,
    norm="rmsnorm",
    mlp="swiglu",
    rope=True,
    rope_theta=1e6,
    tie_embeddings=False,
    n_experts=60,
    top_k=4,
    n_shared_experts=4,
    d_ff_expert=1408,
    norm_topk_prob=False,
    max_seq=8192,
)
