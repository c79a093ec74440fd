"""Moonlight-16B-A3B: DeepSeek-V3 blocks at 2,048 wide — latent attention,
64 routed experts top-6 with sigmoid scoring and 2 shared experts, one
leading dense layer.  [hf:moonshotai/Moonlight-16B-A3B config.json;
arXiv:2502.16982; layer equations arXiv:2412.19437 §2.1, MLA
arXiv:2405.04434]"""
from .base import ModelConfig, register

CONFIG = register(ModelConfig(
    name="moonlight-16b-a3b",
    family="moe",
    num_layers=27,
    d_model=2048,
    num_heads=16,
    num_kv_heads=16,
    d_ff=11264,              # the leading dense layer's FFN width
    vocab_size=163840,
    rope_theta=50_000.0,
    norm_eps=1e-5,
    num_experts=64,
    experts_per_token=6,
    moe_d_ff=1408,
    num_shared_experts=2,
    first_dense_layers=1,
    router_score="sigmoid",  # noaux_tc: selection bias, no aux loss
    routed_scale=2.446,
    router_aux_weight=0.0,
    kv_lora_rank=512,        # q_lora_rank is null: q is one projection
    qk_nope_head_dim=128,
    qk_rope_head_dim=64,
    v_head_dim=128,
    tie_embeddings=False,
    source="hf:moonshotai/Moonlight-16B-A3B (deepseek_v3: MLA, 64e top-6 "
           "sigmoid, 2 shared)",
))
