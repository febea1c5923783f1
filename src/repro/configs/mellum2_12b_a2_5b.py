"""mellum2-12b-a2.5b [moe] — 28L d_model=2304 32H (GQA kv=4, head_dim
128), every MLP sparse: 64 experts of width 896, top-8, softmax router
renormalised over the 8 (no shared expert); layers repeat (sliding,
sliding, sliding, full) with a 1024-token window; sliding layers use
default RoPE at theta 500000, full layers YaRN (factor 16 over 8192
positions, beta 32/1, attention factor 1.2773); untied head, vocab
98304.  [hf:JetBrains/Mellum2-12B-A2.5B-Instruct]
"""
from .base import ModelConfig, RopeSpec

CONFIG = ModelConfig(
    name="mellum2-12b-a2.5b",
    family="moe",
    vocab=98304,
    d_model=2304,
    n_layers=28,
    n_heads=32,
    kv_heads=4,
    head_dim=128,
    d_ff=896,                      # per-expert FFN hidden
    moe_experts=64,
    moe_top_k=8,
    mlp_pattern=("moe",),
    window=1024,
    attn_kinds=("sliding", "sliding", "sliding", "full"),
    rope_theta=500000.0,
    rope_full=RopeSpec(theta=500000.0, yarn_factor=16.0,
                       yarn_original_max=8192, yarn_beta_fast=32.0,
                       yarn_beta_slow=1.0,
                       yarn_attention_factor=1.2772588722239782),
    norm_type="rmsnorm",
    activation="silu",
    gated_mlp=True,
    tie_embeddings=False,
    param_dtype="bfloat16",
    activ_dtype="bfloat16",
    notes="the config has no q/k-norm key (none assumed) and no MTP head "
          "(left out: serving does not need it).",
)
