"""Plain float32 reference of the Mellum2 decoder, written from its published
configuration (HF ``JetBrains/Mellum2-12B-A2.5B-Instruct`` config.json)
and the block equations of HF ``transformers``:

* pre-norm decoder, RMSNorm (``rms_norm_eps``), a final RMSNorm and an
  untied LM head;
* grouped-query attention (``num_attention_heads`` over
  ``num_key_value_heads``, ``head_dim``), no biases, no q/k norm, causal
  softmax.  Layers follow ``layer_types``: in a ``sliding_attention``
  layer query i sees key j iff ``i - sliding_window < j <= i``; a
  ``full_attention`` layer sees every earlier key;
* rotary embedding on q and k (rotate-half), per layer kind from
  ``rope_parameters``: default RoPE at ``rope_theta`` for sliding layers,
  YaRN for full layers -- HF's ``_compute_yarn_parameters`` (frequencies
  blended between extrapolation and 1/factor interpolation over the
  truncated correction range of ``beta_fast``/``beta_slow`` rotations at
  ``original_max_position_embeddings``), cos and sin scaled by
  ``attention_factor``;
* every MLP sparse: a router to ``router_experts`` logits, softmax in
  float32 over all of them, the top ``num_experts_per_tok``, their
  weights renormalised to sum to 1 (``norm_topk_prob``); each expert a
  SwiGLU ``down(silu(gate(x)) * up(x))`` of width
  ``moe_intermediate_size``; no shared expert.

One chip's share of the experts: the file's ``num_experts`` experts,
``[expert_offset, expert_offset + num_experts)`` of the router's
``router_experts``, are held.  A token's MLP output is the sum over its
routed experts that are held of weight x expert output; the others add
nothing, exactly as on the chip the reference is compared with.  Every
held expert is computed for every token and masked by the routing.

It imports nothing of the program under test.  Departures from the
published model: random weights drawn from a seed (no checkpoint), no
MTP head, no dropout or KV cache -- one full forward over the whole
sequence.  Matmul weights are stored (in, out) and stacked over layers.
"""
from __future__ import annotations

import math
from typing import Callable, Dict

import jax
import jax.numpy as jnp
import numpy as np

# stacked (layers, ..., in, out) matmul weights
MATMULS = ("wq", "wk", "wv", "wo", "router", "e_gate", "e_up", "e_down")
HIGHEST = jax.lax.Precision.HIGHEST


def sizes(hf: Dict) -> Dict[str, int]:
    return {"d": hf["hidden_size"], "f": hf["moe_intermediate_size"],
            "layers": hf["num_hidden_layers"],
            "heads": hf["num_attention_heads"],
            "kv_heads": hf["num_key_value_heads"], "head_dim": hf["head_dim"],
            "vocab": hf["vocab_size"], "held": hf["num_experts"],
            "experts": hf["router_experts"],
            "top_k": hf["num_experts_per_tok"]}


def sliding_layers(hf: Dict) -> np.ndarray:
    """(layers,) bool: which of the layers held here slide."""
    kinds = hf["layer_types"][:hf["num_hidden_layers"]]
    return np.asarray([k == "sliding_attention" for k in kinds])


def init_weights(hf: Dict, key, dtype) -> Dict[str, jnp.ndarray]:
    """Random weights from ``key`` in ``dtype``: matmuls, router and
    embeddings N(0, initializer_range^2); norm scales 1 + N(0, 0.1^2)
    where the initializer sets 1, so that a dropped scale moves the
    logits."""
    s = sizes(hf)
    d, f, n_l, h, kv, dh, v, e = (s["d"], s["f"], s["layers"], s["heads"],
                                  s["kv_heads"], s["head_dim"], s["vocab"],
                                  s["held"])
    std = hf["initializer_range"]
    shapes = {
        "embed": ((v, d), std),
        "lm_head": ((v, d), std),
        "wq": ((n_l, d, h * dh), std),
        "wk": ((n_l, d, kv * dh), std),
        "wv": ((n_l, d, kv * dh), std),
        "wo": ((n_l, h * dh, d), std),
        "router": ((n_l, d, s["experts"]), std),
        "e_gate": ((n_l, e, d, f), std),
        "e_up": ((n_l, e, d, f), std),
        "e_down": ((n_l, e, f, d), std),
        "ln1": ((n_l, d), 0.1),
        "ln2": ((n_l, d), 0.1),
        "final_norm": ((d,), 0.1),
    }
    keys = jax.random.split(key, len(shapes))
    out = {}
    for k, (name, (shape, sd)) in zip(keys, sorted(shapes.items())):
        x = jax.random.normal(k, shape, jnp.float32) * sd
        if name in ("ln1", "ln2", "final_norm"):
            x = x + 1.0
        out[name] = x.astype(dtype)
    return out


def _dot(x, w):
    return jnp.dot(x, w, precision=HIGHEST, preferred_element_type=jnp.float32)


def _rmsnorm(x, scale, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * scale


def default_inv_freq(dh: int, theta: float) -> np.ndarray:
    return 1.0 / theta ** (np.arange(0, dh, 2, dtype=np.float64) / dh)


def yarn_inv_freq(dh: int, p: Dict) -> np.ndarray:
    """HF ``_compute_yarn_parameters`` (truncate=True), in float64."""
    base, factor = p["rope_theta"], p["factor"]
    orig = p["original_max_position_embeddings"]

    def corr(rot):
        return dh * math.log(orig / (rot * 2 * math.pi)) / (2 * math.log(base))

    low = max(math.floor(corr(p["beta_fast"])), 0)
    high = min(math.ceil(corr(p["beta_slow"])), dh - 1)
    if low == high:
        high += 0.001
    pos = base ** (np.arange(0, dh, 2, dtype=np.float64) / dh)
    extra, inter = 1.0 / pos, 1.0 / (factor * pos)
    ramp = np.clip((np.arange(dh // 2) - low) / (high - low), 0, 1)
    keep = 1.0 - ramp
    return inter * (1 - keep) + extra * keep


def _rope(x, positions, inv, scale):
    """x (T, heads, dh): rotate-half rotary embedding; cos/sin x scale."""
    dh = x.shape[-1]
    ang = positions.astype(jnp.float32)[:, None] * jnp.asarray(
        inv, jnp.float32)[None]                                  # (T, dh/2)
    cos = (jnp.cos(ang) * scale)[:, None, :]
    sin = (jnp.sin(ang) * scale)[:, None, :]
    x1, x2 = x[..., : dh // 2], x[..., dh // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def moe(lw: Dict, y: jnp.ndarray, hf: Dict, mm: Callable = _dot) -> jnp.ndarray:
    """One layer's sparse MLP (T, d) on normed ``y`` (T, d): the held
    experts' share of the routed mixture."""
    s = sizes(hf)
    probs = jax.nn.softmax(mm(y, lw["router"]), axis=-1)         # (T, E)
    top, idx = jax.lax.top_k(probs, s["top_k"])
    top = top / jnp.sum(top, axis=-1, keepdims=True)
    out = jnp.zeros_like(y)
    for e in range(s["held"]):
        weight = jnp.sum(jnp.where(idx == hf["expert_offset"] + e, top, 0.0),
                         axis=-1)
        ffn = mm(jax.nn.silu(mm(y, lw["e_gate"][e])) * mm(y, lw["e_up"][e]),
                 lw["e_down"][e])
        out = out + weight[:, None] * ffn
    return out


def hidden(w: Dict, tokens: jnp.ndarray, hf: Dict,
           mm: Callable = _dot) -> jnp.ndarray:
    """Final-normed hidden states (T, d) fp32 of one sequence ``tokens``
    (T,).  ``mm(x, w)`` is every weight matmul (router included): fp32 at
    the highest precision by default; the control passes a
    lower-precision one."""
    s = sizes(hf)
    h, kv, dh = s["heads"], s["kv_heads"], s["head_dim"]
    eps, window = hf["rms_norm_eps"], hf["sliding_window"]
    rp = hf["rope_parameters"]
    slide_inv = default_inv_freq(dh, rp["sliding_attention"]["rope_theta"])
    full_inv = yarn_inv_freq(dh, rp["full_attention"])
    full_scale = rp["full_attention"]["attention_factor"]
    t = tokens.shape[0]
    pos = jnp.arange(t)
    causal = pos[None, :] <= pos[:, None]                        # (Tq, Tk)
    in_window = causal & (pos[None, :] > pos[:, None] - window)
    x = w["embed"][tokens].astype(jnp.float32)

    def layer(x, lw):
        y = _rmsnorm(x, lw["ln1"], eps)
        q = mm(y, lw["wq"]).reshape(t, h, dh)
        kk = mm(y, lw["wk"]).reshape(t, kv, dh)
        v = mm(y, lw["wv"]).reshape(t, kv, dh)
        slide = lw["sliding"]
        q = jnp.where(slide, _rope(q, pos, slide_inv, 1.0),
                      _rope(q, pos, full_inv, full_scale))
        kk = jnp.where(slide, _rope(kk, pos, slide_inv, 1.0),
                       _rope(kk, pos, full_inv, full_scale))
        kk = jnp.repeat(kk, h // kv, axis=1)
        v = jnp.repeat(v, h // kv, axis=1)
        scores = jnp.einsum("qhd,khd->hqk", q, kk, precision=HIGHEST)
        mask = jnp.where(slide, in_window, causal)
        scores = jnp.where(mask[None], scores / math.sqrt(dh), -jnp.inf)
        p = jax.nn.softmax(scores, axis=-1)
        o = jnp.einsum("hqk,khd->qhd", p, v, precision=HIGHEST)
        x = x + mm(o.reshape(t, h * dh), lw["wo"])
        return x + moe(lw, _rmsnorm(x, lw["ln2"], eps), hf, mm), None

    stacked = {n: w[n] for n in MATMULS + ("ln1", "ln2")}
    stacked["sliding"] = jnp.asarray(sliding_layers(hf))
    x, _ = jax.lax.scan(layer, x, stacked)
    return _rmsnorm(x, w["final_norm"], eps)


def head(w: Dict, hf: Dict) -> jnp.ndarray:
    """The LM head as a (d, vocab) matmul weight."""
    return w["lm_head"].T
