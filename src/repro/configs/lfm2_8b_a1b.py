"""LFM2-8B-A1B (8.3B total / 1.5B active) [LiquidAI, 2025; conv/attention hybrid MoE].

Published config (huggingface.co/LiquidAI/LFM2-8B-A1B, config.json): 24 layers,
hidden 2048, vocab 65536; 18 gated short-convolution layers (conv_L_cache 3,
no bias) and 6 GQA layers (32 query heads, 8 kv heads, head_dim 64, RoPE θ
1e6) at indices 2, 6, 10, 14, 18, 21; the first 2 layers have a dense
SwiGLU of width 7168, the other 22 have 32 routed experts of width 1792,
top-4, sigmoid scores with a per-expert selection bias, top-k weights
renormalized, routed scale 1.

Block: ``h = x + mixer(norm(x))``, ``out = h + ffn(norm(h))``.  Conv
mixer: ``B, C, x = split3(conv_in(x))``, ``conv_out(C ⊙ dwconv3(B ⊙ x))``.
Attention mixer: per-head RMSNorm on q and k before RoPE (the family's
implementation; no config key states it).  Assumed, as the config does
not say: tied embeddings, SiLU, the dense width as the literal 7168.
The stack's norms are this repo's RMSNorm (eps 1e-6, weight 1 + scale;
published eps 1e-5).
"""

from repro.configs.base import BlockDef, ModelConfig, register

LAYER_TYPES = (
    "conv", "conv", "full_attention", "conv", "conv", "conv", "full_attention", "conv",
    "conv", "conv", "full_attention", "conv", "conv", "conv", "full_attention", "conv",
    "conv", "conv", "full_attention", "conv", "conv", "full_attention", "conv", "conv",
)
NUM_DENSE_LAYERS = 2

_PATTERN = tuple(
    BlockDef(
        kind="attn" if t == "full_attention" else "conv",
        mlp="dense" if i < NUM_DENSE_LAYERS else "moe",
    )
    for i, t in enumerate(LAYER_TYPES)
)

CONFIG = register(
    ModelConfig(
        name="lfm2_8b_a1b",
        d_model=2048,
        n_heads=32,
        n_kv_heads=8,
        head_dim=64,
        d_ff=7168,
        vocab=65536,
        pattern=_PATTERN,
        n_periods=1,
        rope_theta=1_000_000.0,
        tie_embeddings=True,
        qk_norm=True,
        n_experts=32,
        top_k=4,
        moe_d_ff=1792,
        router_norm_topk=True,
        router_score="sigmoid",
        router_bias=True,
        ssm_conv=3,
    )
)
