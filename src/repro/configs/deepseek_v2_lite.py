"""DeepSeek-V2-Lite — MLA (kv_lora=512, no q-LoRA) + YaRN RoPE, one dense
layer then 26 MoE layers of 2 shared / 64 routed top-6 experts without
renormalised gates.  15.7 B total, 2.4 B active.
[hf deepseek-ai/DeepSeek-V2-Lite config.json; arXiv:2405.04434]"""

from .base import ModelConfig, register

register(ModelConfig(
    name="deepseek_v2_lite",
    family="moe",
    n_layers=27,
    d_model=2048,
    n_heads=16,
    n_kv_heads=16,           # nominal; MLA replaces the KV path
    d_ff=1408,               # per routed expert
    vocab_size=102400,
    head_dim=128,
    max_position=163840,
    rope_theta=1e4,
    yarn_factor=40.0,
    yarn_mscale_all_dim=0.707,
    norm_eps=1e-6,
    mla=True,
    q_lora_rank=0,           # q_lora_rank: null — a direct query projection
    kv_lora_rank=512,
    qk_nope_dim=128,
    qk_rope_dim=64,
    v_head_dim=128,
    moe=True,
    n_experts=64,
    n_shared_experts=2,
    experts_per_token=6,
    norm_topk_prob=False,
    moe_d_ff=1408,
    first_k_dense=1,         # layer 0 is a dense FFN
    dense_d_ff=10944,
    source="https://huggingface.co/deepseek-ai/DeepSeek-V2-Lite",
))
