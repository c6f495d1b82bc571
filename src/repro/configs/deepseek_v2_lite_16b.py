"""DeepSeek-V2-Lite 16B [arXiv:2405.04434] — MLA + 64-expert MoE.

Published config: https://huggingface.co/deepseek-ai/DeepSeek-V2-Lite
(config.json): 64 routed experts of 1408 and 2 shared, 6 a token by a
softmax over all 64 (weights not renormalized), a sequence-wise balance
loss (``seq_aux``, ``aux_loss_alpha`` 0.001), and YaRN rope scaling.
"""
from repro.configs.base import MLAConfig, MoEConfig, ModelConfig, RopeScaling

CONFIG = ModelConfig(
    name="deepseek-v2-lite-16b",
    family="moe",
    n_layers=27,
    d_model=2048,
    n_heads=16,
    n_kv_heads=16,
    head_dim=0,
    d_ff=10944,                 # dense prefix-layer FFN
    vocab_size=102400,
    source="arXiv:2405.04434",
    rope_scaling=RopeScaling(factor=40.0, original_max_position=4096,
                             beta_fast=32.0, beta_slow=1.0, mscale=0.707,
                             mscale_all_dim=0.707),
    moe=MoEConfig(n_experts=64, top_k=6, d_expert=1408, n_shared=2,
                  n_dense_prefix=1, router_mode="softmax_topk",
                  router_aux_weight=0.001, seq_aux=True),
    mla=MLAConfig(kv_lora_rank=512, q_lora_rank=0,
                  qk_nope_dim=128, qk_rope_dim=64, v_head_dim=128),
    tie_embeddings=False,
)
