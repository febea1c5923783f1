"""The program under test (``src/repro``) as it serves a Qwen2-architecture
configuration: its ``ModelConfig`` and its parameter tree, mapped from the
configuration file and the reference's stacked weights."""
from __future__ import annotations

from typing import Dict

import jax.numpy as jnp

from repro.configs.base import ModelConfig

# the program's RMSNorm epsilon (models/layers.rmsnorm), which it does not
# take from the configuration
PROGRAM_RMS_EPS = 1e-6


def program_config(cfg: Dict, name: str) -> ModelConfig:
    if cfg["rms_norm_eps"] != PROGRAM_RMS_EPS:
        raise ValueError(f"the program's RMSNorm epsilon is {PROGRAM_RMS_EPS},"
                         f" the configuration states {cfg['rms_norm_eps']}")
    if cfg["hidden_act"] != "silu":
        raise ValueError(f"unsupported activation {cfg['hidden_act']!r}")
    dtype = cfg["torch_dtype"]
    return ModelConfig(
        name=name, family="lm", vocab=cfg["vocab_size"],
        d_model=cfg["hidden_size"], n_layers=cfg["num_hidden_layers"],
        n_heads=cfg["num_attention_heads"],
        kv_heads=cfg["num_key_value_heads"],
        d_ff=cfg["intermediate_size"], qkv_bias=True,
        rope_theta=float(cfg["rope_theta"]), norm_type="rmsnorm",
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
                "wq": {"kernel": w["wq"][i], "bias": w["bq"][i]},
                "wk": {"kernel": w["wk"][i], "bias": w["bk"][i]},
                "wv": {"kernel": w["wv"][i], "bias": w["bv"][i]},
                "wo": {"kernel": w["wo"][i]},
            },
            "post_norm": {"scale": w["ln2"][i]},
            "mlp": {
                "w_up": {"kernel": w["w_up"][i]},
                "w_down": {"kernel": w["w_down"][i]},
                "w_gate": {"kernel": w["w_gate"][i]},
            },
        })
    params = {"embed": {"embedding": w["embed"]}, "layers": layers,
              "final_norm": {"scale": w["final_norm"]}}
    if "lm_head" in w:
        params["lm_head"] = {"embedding": w["lm_head"]}
    return params
