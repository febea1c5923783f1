"""Mixture-of-Experts: top-k token-choice routing with capacity, sort-based
dispatch (no (T, E, C) one-hot blow-up), expert-parallel shardable.

Design (see DESIGN.md §4):
* tokens are split into ``groups`` (sharded on the data axis) and routed
  within each group — GShard-style grouping keeps the dispatch buffers
  O(T·k·cf) and evenly sharded;
* position-within-expert comes from a stable sort by expert id + a
  searchsorted for each expert's start — O(T log T), no E-wide cumsum;
* expert FFNs are a batched (E, C, D) x (E, D, F) matmul with the expert
  dim on the TP axis (EP) when E divides it, else intra-expert TP
  (mixtral's E=8 on a 16-way axis);
* aux load-balancing loss (Switch-style) is returned for the trainer.

Expert weights are 3-D (E, D, F): the pruning structures treat E as a
plane dim, so the knapsack can drop single MXU tiles *or* (at high
sparsity) whole experts — the paper's coarse/fine structure mix.
"""
from __future__ import annotations

import math
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.distributed.sharding import _ambient_mesh, logical_constraint
from repro.kernels.ops import Epilogue
from .layers import expert_matmul, matmul, truncated_normal_init


def _cap_axis_ok(num_experts: int) -> bool:
    """Capacity-dim sharding pairs with FSDP'd expert weights (E divides
    the TP axis); under the intra-expert-TP fallback (mixtral E=8 < 16)
    it would fight the weights' own model-axis sharding — measured +88%
    collective on mixtral/train_4k (§Perf)."""
    mesh = _ambient_mesh()
    if mesh is None or "model" not in mesh.axis_names:
        return False
    return num_experts % mesh.shape["model"] == 0

__all__ = ["moe_init", "moe_apply"]


def moe_init(
    key,
    d_model: int,
    d_ff: int,
    num_experts: int,
    *,
    gated: bool = True,
    dtype=jnp.float32,
) -> Dict:
    ks = jax.random.split(key, 4)
    std_in = 1.0 / math.sqrt(d_model)
    std_out = 1.0 / math.sqrt(d_ff)
    p = {
        "router": {"kernel": truncated_normal_init(ks[0], (d_model, num_experts), std_in, jnp.float32)},
        "experts_up": truncated_normal_init(ks[1], (num_experts, d_model, d_ff), std_in, dtype),
        "experts_down": truncated_normal_init(ks[2], (num_experts, d_ff, d_model), std_out, dtype),
    }
    if gated:
        p["experts_gate"] = truncated_normal_init(ks[3], (num_experts, d_model, d_ff), std_in, dtype)
    return p


def moe_apply(
    p: Dict,
    x: jnp.ndarray,               # (B, S, D)
    *,
    num_experts: int,
    top_k: int,
    capacity_factor: float = 1.25,
    groups: Optional[int] = None,
    activation: str = "silu",
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Returns (output (B,S,D), aux_loss scalar fp32)."""
    b, s, d = x.shape
    t = b * s
    g = groups or b
    g = math.gcd(g, t)
    n = t // g                                    # tokens per group
    cap = int(math.ceil(n * top_k * capacity_factor / num_experts))
    cap = max(cap, top_k)

    xt = x.reshape(g, n, d)
    xt = logical_constraint(xt, "batch", None, "embed")

    # --- routing (fp32) ----------------------------------------------------
    # routed through the sparse dispatch for uniformity; the default prune
    # include list keeps the router dense (it decides *where* tokens go)
    logits = matmul(xt, p["router"]["kernel"], accum=jnp.float32)
    # pin the expert dim replicated: propagation otherwise shards E over
    # the model axis and the router backward turns into a (g,n,d) f32 AR
    # per layer (+ top_k all-gathers) — §Perf granite G3
    logits = logical_constraint(logits, "batch", None, None)
    probs = jax.nn.softmax(logits, axis=-1)       # (g, n, E)
    gate, expert = jax.lax.top_k(probs, top_k)    # (g, n, k)
    gate = gate / jnp.maximum(gate.sum(-1, keepdims=True), 1e-9)

    # Switch aux loss: E * mean_e(frac_tokens_e * mean_prob_e)
    me = jnp.mean(probs, axis=(0, 1))                               # (E,)
    assign1 = jax.nn.one_hot(expert[..., 0], num_experts)           # top-1 frac
    ce = jnp.mean(assign1, axis=(0, 1))
    aux = num_experts * jnp.sum(me * ce)

    # --- sort-based dispatch -------------------------------------------------
    eflat = expert.reshape(g, n * top_k)          # (g, nk)
    # gates cast to the activation dtype BEFORE entering the dispatch
    # arithmetic: keeps every (g, nk, d) dispatch tensor (and its
    # cotangents) in bf16 — halves dispatch collective bytes (§Perf)
    gflat = gate.reshape(g, n * top_k).astype(x.dtype)
    order = jnp.argsort(eflat, axis=-1, stable=True)               # (g, nk)
    se = jnp.take_along_axis(eflat, order, axis=-1)
    sg = jnp.take_along_axis(gflat, order, axis=-1)
    stok = order // top_k                          # source token per slot

    starts = jax.vmap(lambda row: jnp.searchsorted(row, jnp.arange(num_experts)))(se)
    pos = jnp.arange(n * top_k)[None, :] - jnp.take_along_axis(starts, se, axis=-1)
    keep = pos < cap                               # capacity drop
    pos_c = jnp.where(keep, pos, 0)

    gathered = jnp.take_along_axis(xt, stok[..., None], axis=1)     # (g, nk, d)

    def scatter_group(buf_tokens, e_idx, p_idx, k_mask):
        buf = jnp.zeros((num_experts, cap, d), buf_tokens.dtype)
        vals = jnp.where(k_mask[:, None], buf_tokens, 0)
        return buf.at[e_idx, p_idx].add(vals, mode="drop")

    # scatter is local per data shard; the buffer's CAPACITY dim is then
    # sharded over the model axis ("expert_cap") — expert compute uses
    # data x model in full, expert weights stay replicated/FSDP (no token
    # travel, no weight travel; §Perf granite iteration G2)
    buffer = jax.vmap(scatter_group)(gathered, se, pos_c, keep)     # (g, E, C, d)
    cap_ax = "expert_cap" if _cap_axis_ok(num_experts) else None
    buffer = logical_constraint(buffer, "batch", None, cap_ax, None)

    # --- expert compute (EP batched matmul; BSRPlanes skip pruned tiles;
    # activation + SwiGLU gate fused into the matmul epilogue) --------------
    if "experts_gate" in p:
        up = expert_matmul(buffer, p["experts_up"], accum=jnp.float32)
        h = expert_matmul(buffer, p["experts_gate"], accum=jnp.float32,
                          epilogue=Epilogue(activation=activation,
                                            multiplier=up))
    else:
        h = expert_matmul(buffer, p["experts_up"], accum=jnp.float32,
                          epilogue=Epilogue(activation=activation))
    h = h.astype(x.dtype)
    h = logical_constraint(h, "batch", None, cap_ax, None)
    out_e = expert_matmul(h, p["experts_down"],
                          accum=jnp.float32).astype(x.dtype)
    out_e = logical_constraint(out_e, "batch", None, cap_ax, None)

    # --- combine --------------------------------------------------------------
    if cap_ax is not None:
        # 2-D gather straight from the (E, C-sharded) buffer: reshaping to
        # (E*C) would merge an unsharded dim with a sharded one and force a
        # full all-gather (70 GB/step measured); the direct gather lowers
        # to a local partial gather + one bf16 all-reduce of (g, nk, d)
        per_slot = jax.vmap(lambda oe, e_i, p_i: oe[e_i, p_i])(out_e, se, pos_c)
    else:
        # TP-fallback (unsharded E and C): flat take_along_axis stays
        # local (reshape of fully-unsharded dims is free)
        back = out_e.reshape(g, num_experts * cap, d)
        flat_idx = se * cap + pos_c
        per_slot = jnp.take_along_axis(back, flat_idx[..., None], axis=1)
    per_slot = per_slot * jnp.where(keep, sg, jnp.zeros((), x.dtype))[..., None]

    def combine_group(slot_vals, tok_idx):
        return jnp.zeros((n, d), slot_vals.dtype).at[tok_idx].add(slot_vals)

    out = jax.vmap(combine_group)(per_slot, stok)                   # (g, n, d)
    out = out.reshape(b, s, d)
    return logical_constraint(out, "batch", "seq", "embed"), aux


def moe_decode(p: Dict, x: jnp.ndarray, *, num_experts: int, top_k: int,
               capacity_factor: float = 2.0,
               activation: str = "silu") -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Decode path: same sort-based dispatch, one group (T = B tokens).

    Per-token weight gathers would materialize (B·k·D·F) expert weights —
    30 GB for mixtral at batch 128 — so decode reuses the capacity path
    with a generous factor (token counts are tiny at decode)."""
    return moe_apply(
        p, x, num_experts=num_experts, top_k=top_k,
        capacity_factor=capacity_factor, groups=1, activation=activation,
    )
