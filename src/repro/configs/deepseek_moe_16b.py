"""deepseek-moe-16b — fine-grained MoE: 2 shared + 64 routed top-6
[arXiv:2401.06066; hf:deepseek-ai/deepseek-moe-16b-base].

28L d_model=2048 16H (MHA, kv=16) vocab=102400. The first layer is dense
(first_k_dense_replace 1, d_ff 10944); the other 27 hold 64 routed experts
of width 1408 and 2 shared, with raw softmax gates (norm_topk_prob false)
and a per-sequence balance loss of weight 0.001.
"""
from repro.models.config import ModelConfig

CONFIG = ModelConfig(
    name="deepseek-moe-16b",
    family="moe",
    num_layers=28,
    first_dense_layers=1,
    d_model=2048,
    num_heads=16,
    num_kv_heads=16,
    d_ff=10944,            # the leading dense layer
    vocab_size=102400,
    moe_num_experts=64,
    moe_top_k=6,
    moe_num_shared=2,
    moe_d_ff=1408,
    moe_norm_topk=False,
    moe_aux_coef=0.001,
    rope_theta=1e4,
    norm_eps=1e-6,
)

SMOKE_CONFIG = CONFIG.replace(
    name="deepseek-moe-16b-smoke",
    num_layers=2,
    d_model=64,
    num_heads=4,
    num_kv_heads=4,
    head_dim=16,
    d_ff=32,
    vocab_size=257,
    moe_num_experts=8,
    moe_top_k=2,
    moe_num_shared=1,
    moe_d_ff=32,
)
