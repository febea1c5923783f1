"""Operations and bytes that a windowed, mixture-of-experts configuration
(``architecture`` ``mellum2``) needs for the work it served, from the
configuration's shapes alone, so that no change to the program can move
the yardstick.

Weights and KV are counted at the configuration's stated type
(``torch_dtype``), whatever the program keeps.  Experts are the held
share: the file's ``num_experts`` of the router's ``router_experts``.
"""
from __future__ import annotations

from typing import Dict

import numpy as np

BYTES = {"bfloat16": 2, "float16": 2, "float32": 4}


def sizes(cfg: Dict) -> Dict[str, int]:
    n = cfg["num_hidden_layers"]
    kinds = cfg["layer_types"][:n]
    return {"d": cfg["hidden_size"], "f": cfg["moe_intermediate_size"],
            "layers": n, "heads": cfg["num_attention_heads"],
            "kv_heads": cfg["num_key_value_heads"],
            "head_dim": cfg["head_dim"], "vocab": cfg["vocab_size"],
            "held": cfg["num_experts"], "experts": cfg["router_experts"],
            "top_k": cfg["num_experts_per_tok"],
            "window": cfg["sliding_window"],
            "sliding": sum(k == "sliding_attention" for k in kinds),
            "full": sum(k == "full_attention" for k in kinds)}


def expert_weights(cfg: Dict) -> int:
    """Weights of one expert: gate, up and down."""
    s = sizes(cfg)
    return 3 * s["d"] * s["f"]


def token_flops(cfg: Dict, context: np.ndarray) -> float:
    """Operations of the forward passes that produce tokens attending
    ``context`` earlier positions each: two per weight a token uses --
    attention, router and LM head, and the held experts it is routed to
    on average (top_k x held / experts of them) -- plus QK and PV over
    the whole context in full layers and the window's part of it in
    sliding layers."""
    s = sizes(cfg)
    d, hd = s["d"], s["heads"] * s["head_dim"]
    kv = s["kv_heads"] * s["head_dim"]
    attn = d * hd * 2 + d * kv * 2
    router = d * s["experts"]
    experts = s["top_k"] * s["held"] / s["experts"] * expert_weights(cfg)
    per = 2.0 * (s["layers"] * (attn + router + experts)
                 + s["vocab"] * d)
    context = np.asarray(context, np.float64)
    seen = (s["full"] * context.sum()
            + s["sliding"] * np.minimum(context, s["window"]).sum())
    return float(per * context.size + 4.0 * hd * seen)


def window_kv_bytes(cfg: Dict, cache_len: np.ndarray) -> float:
    """Bytes of K and V the sliding layers of the decode steps must read:
    a step at ``cache_len`` sees its last ``window - 1`` cached positions
    (its own K/V it computes)."""
    s = sizes(cfg)
    seen = np.minimum(np.asarray(cache_len, np.float64), s["window"] - 1)
    return float(seen.sum() * s["sliding"] * 2 * s["kv_heads"]
                 * s["head_dim"] * BYTES[cfg["torch_dtype"]])


def expert_tick_bytes(cfg: Dict, rows: int) -> float:
    """Bytes of held expert weights one decode tick of ``rows`` rows
    touches, at the share of held experts that uniform routing touches:
    ``1 - (1 - top_k / experts) ** rows``."""
    s = sizes(cfg)
    share = 1.0 - (1.0 - s["top_k"] / s["experts"]) ** rows
    return (s["layers"] * s["held"] * share * expert_weights(cfg)
            * BYTES[cfg["torch_dtype"]])
