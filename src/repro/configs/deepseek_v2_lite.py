"""deepseek-v2-lite [moe] — MLA with YaRN rotary scaling, 2 shared + 64
routed experts top-6; the sparse-expert proxy scorer.

27L d_model=2048 16H (MLA, no q compression, kv_lora=512, nope/rope/v
=128/64/128), YaRN factor 40 over 4096 original positions (beta 32/1,
mscale = mscale_all_dim = 0.707), first layer dense (d_ff=10944), then 26
MoE layers of moe_d_ff=1408, softmax gates not renormalised
(routed_scaling_factor 1), untied head, vocab=102400; 15.7B parameters,
2.4B active [hf:deepseek-ai/DeepSeek-V2-Lite config.json]. The rotary
dims use the halves layout (`layers.apply_rope`); DeepSeek's checkpoint
interleaves pairs, the same model up to a fixed permutation of the 64
rope columns of q_proj and kv_a_proj_with_mqa.
"""
from repro.configs.base import ModelConfig

YARN = dict(yarn_factor=40.0, yarn_original_max_positions=4096,
            yarn_beta_fast=32.0, yarn_beta_slow=1.0, yarn_mscale=0.707,
            yarn_mscale_all_dim=0.707)

CONFIG = ModelConfig(
    name="deepseek-v2-lite", family="moe",
    num_layers=27, d_model=2048, num_heads=16, num_kv_heads=16,
    d_ff=10944, vocab_size=102400,
    use_mla=True, q_lora_rank=0, kv_lora_rank=512,
    qk_nope_head_dim=128, qk_rope_head_dim=64, v_head_dim=128,
    moe=True, num_experts=64, num_experts_per_tok=6,
    num_shared_experts=2, moe_d_ff=1408, dense_d_ff=10944, first_k_dense=1,
    norm_eps=1e-6, **YARN,
)


def smoke():
    """The same blocks at CPU size, holding experts 0-3 of 16."""
    return ModelConfig(
        name="dsv2-lite-smoke", family="moe",
        num_layers=3, d_model=64, num_heads=4, num_kv_heads=4,
        d_ff=96, vocab_size=128,
        use_mla=True, q_lora_rank=0, kv_lora_rank=16,
        qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
        moe=True, num_experts=16, num_experts_per_tok=4,
        num_shared_experts=2, moe_d_ff=32, dense_d_ff=96, first_k_dense=1,
        num_held_experts=4, norm_eps=1e-6, dtype="float32", **YARN,
    )
