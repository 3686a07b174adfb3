"""mellum2-12b-a2.5b — JetBrains' code model: GQA 32/4 x 128, layers in
periods of three sliding-window (1,024) and one full-attention layer, YaRN
on the full layers, and every MLP a 64-expert top-8 MoE of width 896.
[hf:JetBrains/Mellum2-12B-A2.5B-Instruct config.json]"""
from repro.configs.base import ModelConfig, MoEConfig, YarnConfig

ARCH_ID = "mellum2-12b-a2.5b"

PATTERN = ("sliding", "sliding", "sliding", "full")

# rope_parameters.full_attention of the published config
YARN = YarnConfig(factor=16.0, original_max_position=8192, beta_fast=32.0,
                  beta_slow=1.0, attention_factor=1.2772588722239782)


def config() -> ModelConfig:
    return ModelConfig(
        name=ARCH_ID, family="moe",
        n_layers=28, d_model=2304, n_heads=32, n_kv_heads=4,
        d_ff=896, vocab=98304, head_dim=128,
        moe=MoEConfig(num_experts=64, top_k=8),
        layer_pattern=PATTERN, sliding_window=1024, yarn=YARN,
        rope_theta=5e5, norm_eps=1e-6, tie_embeddings=False, act="silu",
    )


def smoke_config() -> ModelConfig:
    """Two periods of the 3:1 pattern at a window of 8, 8 experts top-2."""
    return ModelConfig(
        name=ARCH_ID + "-smoke", family="moe",
        n_layers=8, d_model=64, n_heads=4, n_kv_heads=2,
        d_ff=32, vocab=256, head_dim=16,
        moe=MoEConfig(num_experts=8, top_k=2),
        layer_pattern=PATTERN, sliding_window=8, yarn=YARN,
        rope_theta=5e5, norm_eps=1e-6, tie_embeddings=False, act="silu",
    )
