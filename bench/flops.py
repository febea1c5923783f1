"""Operations and bytes that the served work needs, computed from the
configuration's shapes alone, so that no change to the program can move
the yardstick.

Matmul weights are counted at the configuration's stated type
(``torch_dtype``) and KV at that type too, whatever the program keeps;
a pruned configuration counts only its kept tiles.
"""
from __future__ import annotations

from typing import Dict

import numpy as np

BYTES = {"bfloat16": 2, "float16": 2, "float32": 4}


def sizes(cfg: Dict) -> Dict[str, int]:
    h = cfg["num_attention_heads"]
    return {"d": cfg["hidden_size"], "f": cfg["intermediate_size"],
            "layers": cfg["num_hidden_layers"], "heads": h,
            "kv_heads": cfg["num_key_value_heads"],
            "head_dim": cfg["hidden_size"] // h, "vocab": cfg["vocab_size"]}


def matmuls(cfg: Dict) -> Dict[str, tuple]:
    """{name: (in, out)} of the matmuls of one decoder layer."""
    s = sizes(cfg)
    d, f, q, kv = s["d"], s["f"], s["heads"] * s["head_dim"], \
        s["kv_heads"] * s["head_dim"]
    return {"wq": (d, q), "wk": (d, kv), "wv": (d, kv), "wo": (q, d),
            "w_gate": (d, f), "w_up": (d, f), "w_down": (f, d)}


def layer_weights(cfg: Dict) -> int:
    """Matmul weights of all decoder layers that the served model keeps."""
    total = sizes(cfg)["layers"] * sum(k * n for k, n in matmuls(cfg).values())
    prune = cfg.get("prune")
    if not prune:
        return int(total)
    bk, bn = prune["block"]
    tiles = total // (bk * bn)
    return int(int(tiles * (1.0 - prune["sparsity"]) + 1e-9) * bk * bn)


def head_weights(cfg: Dict) -> int:
    s = sizes(cfg)
    return s["vocab"] * s["d"]


def token_flops(cfg: Dict, context: np.ndarray) -> float:
    """Operations of the forward passes that produce tokens whose
    attention spans ``context`` earlier positions each: two per live
    weight, plus QK and PV over the context."""
    s = sizes(cfg)
    context = np.asarray(context, np.float64)
    per = 2.0 * (layer_weights(cfg) + head_weights(cfg))
    attn = 4.0 * s["layers"] * s["heads"] * s["head_dim"]
    return float(per * context.size + attn * context.sum())


def kv_bytes_per_position(cfg: Dict) -> int:
    s = sizes(cfg)
    return 2 * s["layers"] * s["kv_heads"] * s["head_dim"] * \
        BYTES[cfg["torch_dtype"]]


def weight_bytes(cfg: Dict) -> int:
    """Bytes of every weight a decode step reads: the kept layer matmuls
    and the LM head, at the stated type (norms and biases left out)."""
    return (layer_weights(cfg) + head_weights(cfg)) * BYTES[cfg["torch_dtype"]]


def bsr_decode_ideal_s(cfg: Dict, rows: int, peak_flops: float,
                       peak_bw: float) -> float:
    """Least time one decode step's packed matmuls could take on the chip:
    per layer and matmul, the larger of its operations over peak and its
    bytes (kept tiles, ``rows`` activation rows in and out) over
    bandwidth."""
    prune = cfg["prune"]
    keep = 1.0 - prune["sparsity"]
    b = BYTES[cfg["torch_dtype"]]
    total = 0.0
    for k, n in matmuls(cfg).values():
        w = k * n * keep
        flops = 2.0 * rows * w
        moved = w * b + rows * (k + n) * b
        total += max(flops / peak_flops, moved / peak_bw)
    return total * sizes(cfg)["layers"]
