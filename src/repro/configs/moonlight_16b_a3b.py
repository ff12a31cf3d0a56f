"""moonlight-16b-a3b — Moonlight-16B-A3B (DeepSeek-V3 architecture):
MLA attention, one leading dense layer, then 26 MoE layers of 64
sigmoid-routed experts (top-6, ``noaux_tc``) and 2 shared experts
[hf:moonshotai/Moonlight-16B-A3B, config.json]."""

from repro.configs.base import ModelConfig


def config() -> ModelConfig:
    return ModelConfig(
        name="moonlight-16b-a3b", family="moe",
        n_layers=27, d_model=2048, n_heads=16, n_kv_heads=16,
        d_ff=11264, vocab=163840, rope_theta=50_000.0, norm_eps=1e-5,
        kv_lora_rank=512, qk_nope_dim=128, qk_rope_dim=64,
        v_head_dim=128,
        n_experts=64, top_k=6, dropless=True, n_shared_experts=2,
        d_expert=1408, n_dense_layers=1, routed_scale=2.446,
        soi_block=128,
        train_accum=2,
    )


def smoke_config() -> ModelConfig:
    return ModelConfig(
        name="moonlight-smoke", family="moe",
        n_layers=3, d_model=64, n_heads=4, n_kv_heads=4,
        d_ff=96, vocab=256, rope_theta=50_000.0, norm_eps=1e-5,
        kv_lora_rank=32, qk_nope_dim=16, qk_rope_dim=8, v_head_dim=16,
        n_experts=8, top_k=2, dropless=True, n_shared_experts=2,
        d_expert=24, n_dense_layers=1, routed_scale=2.446,
        soi_block=32, attn_chunk=64,
    )
