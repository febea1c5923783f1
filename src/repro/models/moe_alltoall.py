"""Expert-parallel MoE with explicit all-to-all dispatch (shard_map).

The GSPMD-sharded sort/scatter dispatch (moe.py) lets the partitioner
lower data-dependent gathers over the expert-sharded buffer to
replicate+mask+all-reduce — measured at ~300 GB wire/step on
granite/train_4k (EXPERIMENTS.md §Perf).  The production pattern is
explicit: tokens travel to their experts' shards via all_to_all and come
back the same way; wire per layer ≈ 2·tokens·d·bf16·cf — a ~50×
reduction.

Topology: tokens sharded over the DP axes, experts over "model"
(E_local = E / model_size).  Two-stage routing per shard:
  1. sort token-choices by destination shard; fixed per-dest send buffers
     (capacity_factor-bounded, drops beyond),
  2. all_to_all payload + expert-ids to the owning shard,
  3. local per-expert capacity sort + batched FFN,
  4. inverse gather + all_to_all back + gate-weighted combine at source.

Everything inside is shard-local jnp (differentiable; all_to_all's
transpose is all_to_all).  Requires E % model_size == 0 (mixtral's E=8 on
a 16-way axis keeps the GSPMD fallback).

Sparse execution (DESIGN.md §8): packed ``BSRPlanes`` expert weights run
the shard-local FFN through the fused zero-skipping plane kernel with the
activation/SwiGLU gate in the matmul epilogue; ``transform.planes_pspec``
supplies the matching shard_map specs so the packed tree needs no
densify and no special casing at the call site.
"""
from __future__ import annotations

import math
from functools import partial
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.core.packing import BSRPlanes
from repro.distributed.sharding import _ambient_mesh, current_rules
from repro.kernels.ops import Epilogue, apply_epilogue, bsr_planes_matmul
from repro.sparse.transform import planes_pspec

__all__ = ["moe_alltoall_apply", "alltoall_available"]


def _expert_mm(h: jnp.ndarray, w, *, epilogue=None) -> jnp.ndarray:
    """Shard-local expert matmul (E_loc, C, d) @ (E_loc, d, f) -> fp32.

    ``BSRPlanes`` leaves (the shard's E_loc planes of the packed expert
    stack) run the fused zero-skipping kernel with the epilogue applied
    in-kernel; dense 3-D weights take the batched einsum with the same
    fp32 epilogue math."""
    if isinstance(w, BSRPlanes):
        return bsr_planes_matmul(h, w, epilogue=epilogue).astype(jnp.float32)
    y = jnp.einsum("ecd,edf->ecf", h, w, preferred_element_type=jnp.float32)
    return apply_epilogue(y, epilogue)


def alltoall_available(num_experts: int) -> bool:
    mesh = _ambient_mesh()
    rules = current_rules()
    if mesh is None or rules is None or "model" not in mesh.axis_names:
        return False
    return num_experts % mesh.shape["model"] == 0


def _local_moe(x_loc, p, *, num_experts, top_k, capacity_factor, activation,
               model_axis, model_size, dp_axes):
    """Per-shard body. x_loc (T, d) local tokens."""
    t, d = x_loc.shape
    # static axis size threaded from the caller's mesh (jax.lax.axis_size
    # is post-0.4.x, and the value feeds python-level shape math anyway)
    m = model_size
    e_loc = num_experts // m
    c_send = max(int(math.ceil(t * top_k * capacity_factor / m)), top_k)
    c_exp = max(int(math.ceil(m * c_send / e_loc)), 1)

    # --- routing ------------------------------------------------------------
    logits = jnp.einsum("td,de->te", x_loc, p["router"]["kernel"],
                        preferred_element_type=jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)
    gate, eid = jax.lax.top_k(probs, top_k)                  # (T, k)
    gate = (gate / jnp.maximum(gate.sum(-1, keepdims=True), 1e-9)).astype(x_loc.dtype)

    # Switch aux loss, globally averaged over the token shards
    me = jnp.mean(probs, axis=0)
    ce = jnp.mean(jax.nn.one_hot(eid[..., 0], num_experts), axis=0)
    for ax in dp_axes:
        me = jax.lax.pmean(me, ax)
        ce = jax.lax.pmean(ce, ax)
    aux = num_experts * jnp.sum(me * ce)

    # --- stage 1: sort by destination shard ----------------------------------
    ef = eid.reshape(-1)                                     # (T*k,)
    gf = gate.reshape(-1)
    tokf = jnp.arange(t * top_k) // top_k
    dest = ef // e_loc
    order = jnp.argsort(dest, stable=True)
    sd, se_, sg, stok = dest[order], ef[order], gf[order], tokf[order]
    starts = jnp.searchsorted(sd, jnp.arange(m))
    pos = jnp.arange(t * top_k) - starts[sd]
    keep = pos < c_send
    pos_c = jnp.where(keep, pos, 0)

    send_x = jnp.zeros((m, c_send, d), x_loc.dtype)
    send_x = send_x.at[sd, pos_c].add(
        jnp.where(keep[:, None], x_loc[stok], 0), mode="drop")
    send_id = jnp.full((m, c_send), -1, jnp.int32)
    send_id = send_id.at[sd, pos_c].max(
        jnp.where(keep, se_, -1).astype(jnp.int32), mode="drop")

    # --- stage 2: to the expert shards ---------------------------------------
    recv_x = jax.lax.all_to_all(send_x, model_axis, 0, 0, tiled=False)
    recv_id = jax.lax.all_to_all(send_id, model_axis, 0, 0, tiled=False)
    rx = recv_x.reshape(m * c_send, d)
    rid = recv_id.reshape(m * c_send)

    # --- stage 3: local per-expert buffers -----------------------------------
    le = rid % e_loc
    valid = rid >= 0
    le_sort = jnp.where(valid, le, e_loc)                    # invalid last
    order2 = jnp.argsort(le_sort, stable=True)
    le2, valid2 = le_sort[order2], valid[order2]
    starts2 = jnp.searchsorted(le2, jnp.arange(e_loc))
    pos2 = jnp.arange(m * c_send) - starts2[jnp.clip(le2, 0, e_loc - 1)]
    keep2 = valid2 & (pos2 < c_exp)
    pos2c = jnp.where(keep2, pos2, 0)
    le2c = jnp.where(keep2, le2, 0)

    ebuf = jnp.zeros((e_loc, c_exp, d), x_loc.dtype)
    ebuf = ebuf.at[le2c, pos2c].add(
        jnp.where(keep2[:, None], rx[order2], 0), mode="drop")

    # packed (BSRPlanes) or dense expert FFN, activation/gate fused into
    # the matmul epilogue either way (DESIGN.md §8)
    if "experts_gate" in p:
        up = _expert_mm(ebuf, p["experts_up"])
        h = _expert_mm(ebuf, p["experts_gate"],
                       epilogue=Epilogue(activation=activation, multiplier=up))
    else:
        h = _expert_mm(ebuf, p["experts_up"],
                       epilogue=Epilogue(activation=activation))
    out_e = _expert_mm(h.astype(x_loc.dtype),
                       p["experts_down"]).astype(x_loc.dtype)

    # --- stage 4: inverse route back ------------------------------------------
    y_sorted = jnp.where(keep2[:, None], out_e[le2c, pos2c], 0)
    inv2 = jnp.zeros_like(order2).at[order2].set(jnp.arange(order2.shape[0]))
    y_recv = y_sorted[inv2].reshape(m, c_send, d)
    y_send = jax.lax.all_to_all(y_recv, model_axis, 0, 0, tiled=False)

    y_slot = jnp.where(keep[:, None], y_send[sd, pos_c], 0) * sg[:, None]
    out = jnp.zeros((t, d), x_loc.dtype).at[stok].add(y_slot)
    return out, aux


def moe_alltoall_apply(
    p: Dict,
    x: jnp.ndarray,               # (B, S, D)
    *,
    num_experts: int,
    top_k: int,
    capacity_factor: float = 1.25,
    activation: str = "silu",
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    mesh = _ambient_mesh()
    rules = current_rules()
    dp = rules.get("batch") or ()
    dp_axes = (dp,) if isinstance(dp, str) else tuple(dp)
    b, s, d = x.shape

    body = partial(
        _local_moe, num_experts=num_experts, top_k=top_k,
        capacity_factor=capacity_factor, activation=activation,
        model_axis="model", model_size=int(mesh.shape["model"]),
        dp_axes=dp_axes,
    )

    def wrapped(xs, params):
        t_loc = xs.shape[0] * xs.shape[1]
        y, aux = body(xs.reshape(t_loc, d), params)
        return y.reshape(xs.shape), aux

    # per-leaf specs: dense expert stacks shard the plane (E) dim on the
    # model axis; packed BSRPlanes leaves shard the plane dim of every
    # component array (transform.planes_pspec), so the packed tree flows
    # through the same shard_map unchanged
    pspec = {
        "router": {"kernel": P()},
        "experts_up": planes_pspec(p["experts_up"], "model"),
        "experts_down": planes_pspec(p["experts_down"], "model"),
    }
    if "experts_gate" in p:
        pspec["experts_gate"] = planes_pspec(p["experts_gate"], "model")
    xspec = P(dp_axes if dp_axes else None, None, None)

    fn = jax.shard_map(
        wrapped, mesh=mesh,
        in_specs=(xspec, pspec),
        out_specs=(xspec, P()),
        check_vma=False,
    )
    return fn(x, p)
