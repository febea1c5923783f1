"""Mixture-of-Experts: top-k token-choice routing with capacity, sort-based
dispatch (no (T, E, C) one-hot blow-up), expert-parallel shardable — the
training path — and the dropless serving layer :func:`moe_serve`, which
computes the share of the experts this chip holds.

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

from repro.core.packing import BSRPlanes
from repro.distributed.sharding import _ambient_mesh, logical_constraint
from repro.kernels.moe_experts import ACTIVATIONS
from repro.kernels.ops import Epilogue
from repro.kernels.ops import moe_experts as _moe_experts_op
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

__all__ = ["moe_init", "moe_apply", "moe_serve"]


def moe_init(
    key,
    d_model: int,
    d_ff: int,
    num_experts: int,
    *,
    gated: bool = True,
    dtype=jnp.float32,
    held: Optional[int] = None,
) -> Dict:
    """Router over ``num_experts``; expert weights for the ``held`` of
    them this chip holds (all by default)."""
    held = num_experts if held is None else held
    ks = jax.random.split(key, 4)
    std_in = 1.0 / math.sqrt(d_model)
    std_out = 1.0 / math.sqrt(d_ff)
    p = {
        "router": {"kernel": truncated_normal_init(ks[0], (d_model, num_experts), std_in, jnp.float32)},
        "experts_up": truncated_normal_init(ks[1], (held, d_model, d_ff), std_in, dtype),
        "experts_down": truncated_normal_init(ks[2], (held, d_ff, d_model), std_out, dtype),
    }
    if gated:
        p["experts_gate"] = truncated_normal_init(ks[3], (held, d_model, d_ff), std_in, dtype)
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


def _row_tile(pairs: int, num_experts: int) -> int:
    """Rows per expert tile of the grouped kernel: the power of two nearest
    above the rows a held expert expects under uniform routing, within
    [16, 128] (16 fills a bf16 sublane tile; 128 an MXU pass)."""
    want = max(1, -(-pairs // num_experts))
    return int(min(128, max(16, 1 << (want - 1).bit_length())))


def _held_ffn_grouped(xt, p, local, mine, held: int, tm: int,
                      activation: str):
    """(t, k, d) fp32 expert outputs of every routed pair that is held
    here (zero elsewhere), through the grouped ``moe_experts`` kernel:
    pairs laid out by held expert, each group padded to whole tiles."""
    t, k = local.shape
    d = xt.shape[-1]
    flat = (local.reshape(t * k, 1) == jnp.arange(held)) & mine.reshape(
        t * k, 1)                                            # (t·k, held)
    counts = jnp.sum(flat, axis=0, dtype=jnp.int32)
    tiles = (counts + tm - 1) // tm
    tile_end = jnp.cumsum(tiles)
    live = tile_end[-1]
    # static bound on the tiles: every token sends at most min(k, held)
    # pairs here, and each group wastes less than a tile
    n_tiles = max(1, (t * min(k, held) + held * (tm - 1)) // tm)
    e_of = jnp.clip(local.reshape(t * k), 0, held - 1)
    rank = jnp.take_along_axis(jnp.cumsum(flat, axis=0, dtype=jnp.int32),
                               e_of[:, None], axis=1)[:, 0] - 1
    pos = (tile_end - tiles)[e_of] * tm + rank
    rows = n_tiles * tm
    pos = jnp.where(mine.reshape(t * k), pos, rows)          # out of range
    src = jnp.full((rows,), t, jnp.int32).at[pos].set(
        jnp.arange(t * k, dtype=jnp.int32) // k, mode="drop")
    xs = jnp.where((src < t)[:, None], xt[jnp.minimum(src, t - 1)], 0)
    tile = jnp.minimum(jnp.arange(n_tiles), jnp.maximum(live - 1, 0))
    tile_expert = jnp.minimum(
        jnp.searchsorted(tile_end, tile, side="right"), held - 1
    ).astype(jnp.int32)
    ys = _moe_experts_op(xs, p["experts_gate"], p["experts_up"],
                         p["experts_down"], tile_expert,
                         live.reshape(1).astype(jnp.int32), block_rows=tm,
                         activation=activation)
    got = ys[jnp.minimum(pos, rows - 1)].reshape(t, k, d)
    return jnp.where(mine[..., None], got, 0.0), counts


def _held_ffn_planes(xt, p, local, mine, held: int, activation: str):
    """The same for packed (``BSRPlanes``) expert weights: each held
    expert runs over a buffer of all ``t`` token rows (a capacity of every
    token, so nothing drops), through ``expert_matmul``."""
    t, k = local.shape
    d = xt.shape[-1]
    tok = jnp.broadcast_to(jnp.arange(t)[:, None], (t, k))
    buf = jnp.zeros((1, held, t, d), xt.dtype).at[
        0, jnp.where(mine, local, held), tok].set(
            jnp.broadcast_to(xt[:, None], (t, k, d)), mode="drop")
    up = expert_matmul(buf, p["experts_up"], accum=jnp.float32)
    h = expert_matmul(buf, p["experts_gate"], accum=jnp.float32,
                      epilogue=Epilogue(activation=activation, multiplier=up))
    out = expert_matmul(h.astype(xt.dtype), p["experts_down"],
                        accum=jnp.float32)[0]                # (held, t, d)
    got = out[jnp.clip(local, 0, held - 1), tok]             # (t, k, d)
    counts = jnp.sum((local[..., None] == jnp.arange(held)) & mine[..., None],
                     axis=(0, 1), dtype=jnp.int32)
    return jnp.where(mine[..., None], got, 0.0), counts


def moe_serve(p: Dict, x: jnp.ndarray, *, num_experts: int, top_k: int,
              held: Optional[int] = None, offset: int = 0,
              activation: str = "silu") -> Tuple[jnp.ndarray, Dict]:
    """Dropless MoE for serving (gated experts), computing this chip's
    share of the experts.  The router's softmax (fp32) and top-``top_k``
    run over all ``num_experts``; the ``top_k`` weights are renormalised
    to sum to 1.
    Each token's output is the sum, over its routed experts that are held
    here (``[offset, offset + held)``), of weight × the expert's gated
    FFN; experts not held add nothing.  No capacity, nothing dropped:
    every row's result depends on that row alone, so a request's tokens
    are the same solo and co-batched.

    Returns (output (B, S, D), stats): ``pairs`` routed (token, held
    expert) pairs and ``touched`` held experts with at least one, int32."""
    if activation not in ACTIVATIONS:
        raise ValueError(f"unsupported expert activation {activation!r}")
    held = num_experts if held is None else held
    b, s, d = x.shape
    t = b * s
    xt = x.reshape(t, d)
    logits = matmul(xt, p["router"]["kernel"], accum=jnp.float32)
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    gate, expert = jax.lax.top_k(probs, top_k)                # (t, k)
    gate = gate / jnp.sum(gate, axis=-1, keepdims=True)
    local = expert - offset
    mine = (local >= 0) & (local < held)
    names = ("experts_gate", "experts_up", "experts_down")
    if any(isinstance(p[n], BSRPlanes) for n in names):
        got, counts = _held_ffn_planes(xt, p, local, mine, held, activation)
    else:
        got, counts = _held_ffn_grouped(
            xt, p, local, mine, held,
            _row_tile(t * top_k, num_experts), activation)
    y = jnp.sum(gate[..., None] * got, axis=1)                # (t, d) fp32
    stats = {"pairs": jnp.sum(counts), "touched": jnp.sum(counts > 0,
                                                           dtype=jnp.int32)}
    return y.astype(x.dtype).reshape(b, s, d), stats
