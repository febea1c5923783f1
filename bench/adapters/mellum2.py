"""The program under test (``src/repro``) as it serves a Mellum2-architecture
configuration: its ``ModelConfig`` -- per-layer window and RoPE, the
held share of the experts -- and its parameter tree, mapped from the
configuration file and the reference's stacked weights."""
from __future__ import annotations

from typing import Dict

import jax.numpy as jnp

from repro.configs.base import ModelConfig, RopeSpec

# the program's RMSNorm epsilon (models/layers.rmsnorm), which it does not
# take from the configuration
PROGRAM_RMS_EPS = 1e-6
KINDS = {"sliding_attention": "sliding", "full_attention": "full"}


def program_config(cfg: Dict, name: str) -> ModelConfig:
    if cfg["rms_norm_eps"] != PROGRAM_RMS_EPS:
        raise ValueError(f"the program's RMSNorm epsilon is {PROGRAM_RMS_EPS},"
                         f" the configuration states {cfg['rms_norm_eps']}")
    if cfg["hidden_act"] != "silu" or cfg["attention_bias"]:
        raise ValueError("unsupported activation or attention bias")
    if not cfg["norm_topk_prob"]:
        raise ValueError("the program renormalises the top-k router weights")
    rp = cfg["rope_parameters"]
    full, slide = rp["full_attention"], rp["sliding_attention"]
    if slide["rope_type"] != "default" or full["rope_type"] != "yarn":
        raise ValueError(f"unsupported rope parameters {rp}")
    dtype = cfg["torch_dtype"]
    n_layers = cfg["num_hidden_layers"]
    return ModelConfig(
        name=name, family="moe", vocab=cfg["vocab_size"],
        d_model=cfg["hidden_size"], n_layers=n_layers,
        n_heads=cfg["num_attention_heads"],
        kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        d_ff=cfg["moe_intermediate_size"],
        moe_experts=cfg["router_experts"],
        moe_top_k=cfg["num_experts_per_tok"],
        moe_experts_held=cfg["num_experts"],
        moe_expert_offset=cfg["expert_offset"], mlp_pattern=("moe",),
        window=cfg["sliding_window"],
        attn_kinds=tuple(KINDS[k] for k in cfg["layer_types"][:n_layers]),
        rope_theta=float(slide["rope_theta"]),
        rope_full=RopeSpec(
            theta=float(full["rope_theta"]),
            yarn_factor=float(full["factor"]),
            yarn_original_max=int(full["original_max_position_embeddings"]),
            yarn_beta_fast=float(full["beta_fast"]),
            yarn_beta_slow=float(full["beta_slow"]),
            yarn_attention_factor=float(full["attention_factor"])),
        qkv_bias=False, norm_type="rmsnorm",
        activation="silu", gated_mlp=True,
        tie_embeddings=bool(cfg["tie_word_embeddings"]),
        param_dtype=dtype, activ_dtype=dtype, remat="none")


def program_params(w: Dict[str, jnp.ndarray]) -> Dict:
    """The program's parameter tree (``models/transformer.init_params``
    layout) holding the reference's weights."""
    layers = []
    for i in range(w["wq"].shape[0]):
        layers.append({
            "pre_norm": {"scale": w["ln1"][i]},
            "attn": {
                "wq": {"kernel": w["wq"][i]},
                "wk": {"kernel": w["wk"][i]},
                "wv": {"kernel": w["wv"][i]},
                "wo": {"kernel": w["wo"][i]},
            },
            "post_norm": {"scale": w["ln2"][i]},
            "moe": {
                "router": {"kernel": w["router"][i]},
                "experts_gate": w["e_gate"][i],
                "experts_up": w["e_up"][i],
                "experts_down": w["e_down"][i],
            },
        })
    return {"embed": {"embedding": w["embed"]}, "layers": layers,
            "final_norm": {"scale": w["final_norm"]},
            "lm_head": {"embedding": w["lm_head"]}}
