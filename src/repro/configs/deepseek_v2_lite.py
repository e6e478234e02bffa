"""DeepSeek-V2-Lite (15.7B total, 2.4B active) — MLA without a query LoRA,
64 routed experts (top 6) + 2 shared, YaRN rope.

[hf deepseek-ai/DeepSeek-V2-Lite config.json]
27 layers, hidden 2048, vocabulary 102400, untied head. Layer 0 is a dense
SwiGLU layer of width 10944 (first_k_dense_replace=1); layers 1..26 are MoE:
64 routed experts of width 1408, softmax scores, greedy top-6 without
renormalisation (norm_topk_prob false, routed_scaling_factor 1), and two
shared experts served as one FFN of width 2816. Attention is MLA with
q_lora_rank null (one direct query projection, no query norm), kv_lora_rank
512, qk_nope 128, qk_rope 64, v_head 128 over 16 heads; the latent cache
holds 512 + 64 values per token and layer.
"""
from repro.configs.base import LayerSpec, ModelConfig, RopeScaling, register


@register("deepseek-v2-lite")
def deepseek_v2_lite() -> ModelConfig:
    return ModelConfig(
        name="deepseek-v2-lite",
        family="moe",
        source="[hf:deepseek-ai/DeepSeek-V2-Lite; hf]",
        n_layers=27,
        d_model=2048,
        n_heads=16,
        n_kv_heads=16,  # MLA: every head reads the one latent cache
        head_dim=128,
        d_ff=10944,  # dense layer 0
        vocab_size=102400,
        mla=True,
        q_lora_rank=0,
        kv_lora_rank=512,
        qk_nope_head_dim=128,
        qk_rope_head_dim=64,
        v_head_dim=128,
        n_experts=64,
        top_k=6,
        moe_d_ff=1408,
        n_shared_experts=2,
        shared_d_ff=2816,  # 2 shared experts x 1408
        norm_topk=False,
        rope_theta=10000.0,
        rope_scaling=RopeScaling(factor=40.0, original_max_position_embeddings=4096,
                                 beta_fast=32.0, beta_slow=1.0, mscale=0.707,
                                 mscale_all_dim=0.707),
        norm_eps=1e-6,
        layer_specs=tuple(LayerSpec(ffn="dense" if i == 0 else "moe") for i in range(27)),
        n_prefix_layers=1,
        scan_period=1,
        max_seq_len=163840,
    )
