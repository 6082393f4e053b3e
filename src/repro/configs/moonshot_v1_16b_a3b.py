"""moonshot-v1-16b-a3b — a 48-layer GQA stand-in named after
Moonlight-16B-A3B (hf:moonshotai/Moonlight-16B-A3B), for the planner and
cluster-builder tables: 48L d_model=2048 16H (GQA kv=16) d_ff=1408 (per
expert) vocab=163840, 64 experts top-6 routed by softmax, every layer MoE.

It departs from the published model, which has 27 layers, latent
attention (MLA), a dense first layer and sigmoid (noaux_tc) routing.  The
served Moonlight is `benchmarks/chip/configs/moonlight-16b-a3b-ep8.json`
(families/deepseek_v3.py builds its ModelConfig).
"""
from repro.configs.base import FULL_ATTENTION_SKIP, ModelConfig, register


@register("moonshot-v1-16b-a3b")
def config() -> ModelConfig:
    return ModelConfig(
        name="moonshot-v1-16b-a3b",
        family="moe",
        n_layers=48,
        d_model=2048,
        n_heads=16,
        n_kv_heads=16,
        d_ff=1408,
        vocab_size=163840,
        n_experts=64,
        top_k=6,
        moe_every=1,
        n_shared_experts=2,
        mlp_style="swiglu",
        act="silu",
        rope_theta=50_000.0,
        skip_cells=("long_500k",),
        skip_reason=FULL_ATTENTION_SKIP,
    )
