"""Plain float32 reference of the Qwen2 decoder (the architecture of the
Qwen1.5 checkpoints), written from the published description: HF
``transformers`` ``Qwen2ForCausalLM`` and the Qwen technical report.

Pre-norm decoder, RMSNorm (``rms_norm_eps``), rotary position embedding
on q and k (rotate-half convention, ``rope_theta``), biases on the q/k/v
projections and none on the output projection, grouped-query attention
with a causal softmax, SwiGLU MLP ``down(silu(gate(x)) * up(x))``, a final
RMSNorm and an LM head tied to the embedding when
``tie_word_embeddings``.

It imports nothing of the program under test.  Departures from the
published model: the weights are random, drawn from a seed (no
checkpoint is loaded), and there is no dropout, sliding window or KV
cache -- one full causal forward over the whole sequence.

Matmul weights are stored (in, out) and stacked over layers, so one
``lax.scan`` walks the layers one at a time.
"""
from __future__ import annotations

import math
from typing import Callable, Dict

import jax
import jax.numpy as jnp

# stacked (layers, in, out) matmul weights -- what a tile pruning acts on
MATMULS = ("wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down")
HIGHEST = jax.lax.Precision.HIGHEST


def sizes(hf: Dict) -> Dict[str, int]:
    h = hf["num_attention_heads"]
    return {"d": hf["hidden_size"], "f": hf["intermediate_size"],
            "layers": hf["num_hidden_layers"], "heads": h,
            "kv_heads": hf["num_key_value_heads"],
            "head_dim": hf["hidden_size"] // h, "vocab": hf["vocab_size"]}


def init_weights(hf: Dict, key, dtype) -> Dict[str, jnp.ndarray]:
    """Random weights from ``key`` in ``dtype``.  Matmuls and the
    embedding draw from N(0, initializer_range^2), as the published
    initializer does; norm scales are 1 + N(0, 0.1^2) and the q/k/v
    biases N(0, 0.1^2) where the initializer sets 1 and 0, so that a
    dropped scale or bias moves the logits."""
    s = sizes(hf)
    d, f, n_l, h, kv, dh, v = (s["d"], s["f"], s["layers"], s["heads"],
                               s["kv_heads"], s["head_dim"], s["vocab"])
    std = hf["initializer_range"]
    shapes = {
        "embed": ((v, d), std),
        "wq": ((n_l, d, h * dh), std),
        "wk": ((n_l, d, kv * dh), std),
        "wv": ((n_l, d, kv * dh), std),
        "wo": ((n_l, h * dh, d), std),
        "w_gate": ((n_l, d, f), std),
        "w_up": ((n_l, d, f), std),
        "w_down": ((n_l, f, d), std),
        "bq": ((n_l, h * dh), 0.1),
        "bk": ((n_l, kv * dh), 0.1),
        "bv": ((n_l, kv * dh), 0.1),
        "ln1": ((n_l, d), 0.1),
        "ln2": ((n_l, d), 0.1),
        "final_norm": ((d,), 0.1),
    }
    if not hf["tie_word_embeddings"]:
        shapes["lm_head"] = ((v, d), std)
    keys = jax.random.split(key, len(shapes))
    out = {}
    for k, (name, (shape, std)) in zip(keys, sorted(shapes.items())):
        x = jax.random.normal(k, shape, jnp.float32) * std
        if name in ("ln1", "ln2", "final_norm"):
            x = x + 1.0
        out[name] = x.astype(dtype)
    return out


def _dot(x, w):
    return jnp.dot(x, w, precision=HIGHEST, preferred_element_type=jnp.float32)


def _rmsnorm(x, scale, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * scale


def _rope(x, positions, theta):
    """x (T, heads, dh): rotate-half rotary embedding at ``positions``."""
    dh = x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, dh, 2, dtype=jnp.float32) / dh)
    ang = positions.astype(jnp.float32)[:, None] * inv[None]      # (T, dh/2)
    cos = jnp.cos(ang)[:, None, :]
    sin = jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., : dh // 2], x[..., dh // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def hidden(w: Dict, tokens: jnp.ndarray, hf: Dict,
           mm: Callable = _dot) -> jnp.ndarray:
    """Final-normed hidden states (T, d) fp32 of one sequence ``tokens``
    (T,).  ``mm(x, w)`` is every weight matmul: fp32 at the highest
    precision by default; the control passes a lower-precision one."""
    s = sizes(hf)
    h, kv, dh = s["heads"], s["kv_heads"], s["head_dim"]
    eps, theta = hf["rms_norm_eps"], hf["rope_theta"]
    t = tokens.shape[0]
    pos = jnp.arange(t)
    causal = pos[None, :] <= pos[:, None]                        # (Tq, Tk)
    x = w["embed"][tokens].astype(jnp.float32)

    def layer(x, lw):
        y = _rmsnorm(x, lw["ln1"], eps)
        q = (mm(y, lw["wq"]) + lw["bq"]).reshape(t, h, dh)
        k = (mm(y, lw["wk"]) + lw["bk"]).reshape(t, kv, dh)
        v = (mm(y, lw["wv"]) + lw["bv"]).reshape(t, kv, dh)
        q, k = _rope(q, pos, theta), _rope(k, pos, theta)
        k = jnp.repeat(k, h // kv, axis=1)
        v = jnp.repeat(v, h // kv, axis=1)
        scores = jnp.einsum("qhd,khd->hqk", q, k, precision=HIGHEST)
        scores = jnp.where(causal[None], scores / math.sqrt(dh), -jnp.inf)
        p = jax.nn.softmax(scores, axis=-1)
        o = jnp.einsum("hqk,khd->qhd", p, v, precision=HIGHEST)
        x = x + mm(o.reshape(t, h * dh), lw["wo"])
        y = _rmsnorm(x, lw["ln2"], eps)
        x = x + mm(jax.nn.silu(mm(y, lw["w_gate"])) * mm(y, lw["w_up"]),
                   lw["w_down"])
        return x, None

    stacked = {k: w[k] for k in MATMULS + ("bq", "bk", "bv", "ln1", "ln2")}
    x, _ = jax.lax.scan(layer, x, stacked)
    return _rmsnorm(x, w["final_norm"], eps)


def head(w: Dict, hf: Dict) -> jnp.ndarray:
    """The LM head as a (d, vocab) matmul weight."""
    table = w["embed"] if hf["tie_word_embeddings"] else w["lm_head"]
    return table.T
