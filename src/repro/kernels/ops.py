"""Jit'd public wrappers around the Pallas kernels.

Mode dispatch (``mode=``):
* ``auto``      — ref path off-TPU, compiled Pallas on TPU (serving default)
* ``ref``       — pure-jnp zero-skipping oracle (kernels/ref.py)
* ``pallas``    — compiled Pallas (TPU only)
* ``interpret`` — the Pallas kernel under the interpreter, any backend —
  this is how CI exercises the real kernel body on CPU hosts

The ref path is itself zero-skipping (it contracts the flat live-tile
store only, no densify — see kernels/ref.py), so CPU serving gets the
same work-scales-with-density contract as the TPU kernel.

Both wrappers accept a fused ``Epilogue`` (kernels/epilogue.py): bias,
activation, SwiGLU gate multiply and residual are applied to the fp32
accumulator inside the kernel (or on the ref accumulator before the
final cast) — identical math on every path, no (M, N) intermediate
round-trips.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.core.packing import BSRPlanes, BSRWeight
from .block_sparse_matmul import bsr_matmul_pallas, bsr_planes_matmul_pallas
from .epilogue import Epilogue, apply_epilogue, make_epilogue
from .moe_experts import moe_experts_pallas, moe_experts_ref
from .paged_attention import (
    paged_attention_decode_pallas,
    paged_attention_decode_ref,
    paged_attention_prefill_pallas,
    paged_attention_prefill_ref,
    paged_kv_write_pallas,
    paged_kv_write_ref,
)
from .structure_norms import structure_norms_pallas
from . import ref as _ref

__all__ = [
    "Epilogue", "apply_epilogue", "make_epilogue",
    "bsr_matmul", "bsr_planes_matmul", "structure_norms", "on_tpu",
    "paged_attention_decode", "paged_attention_prefill", "paged_kv_write",
    "moe_experts",
]


def on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def _use_ref(mode: str) -> bool:
    if mode not in ("auto", "ref", "pallas", "interpret"):
        raise ValueError(f"unknown kernel mode {mode!r}")
    return mode == "ref" or (mode == "auto" and not on_tpu())


@functools.partial(jax.jit, static_argnames=("bm", "mode"))
def bsr_matmul(
    x: jnp.ndarray,
    bsr: BSRWeight,
    *,
    bm: int = 128,
    mode: str = "auto",          # auto | pallas | interpret | ref
    epilogue: Optional[Epilogue] = None,
) -> jnp.ndarray:
    """y = epilogue(x @ W_bsr) for x (..., K); skips pruned tiles on
    every path.  Epilogue operands broadcast over the leading dims of x
    (i.e. multiplier/residual are shaped (..., N) like the output)."""
    lead = x.shape[:-1]
    k = x.shape[-1]
    x2 = x.reshape(-1, k)
    epi = None if epilogue is None else epilogue.map_operands(
        lambda a: a.reshape(-1, a.shape[-1]))
    if _use_ref(mode):
        y = _ref.bsr_matmul_ref(x2, bsr, epilogue=epi)
    else:
        y = bsr_matmul_pallas(
            x2, bsr, bm=bm, epilogue=epi, interpret=(mode == "interpret"),
        )
    return y.reshape(*lead, bsr.shape[1])


@functools.partial(jax.jit, static_argnames=("bm", "mode"))
def bsr_planes_matmul(
    x: jnp.ndarray,              # (E, ..., K)
    planes: BSRPlanes,
    *,
    bm: int = 128,
    mode: str = "auto",
    epilogue: Optional[Epilogue] = None,
) -> jnp.ndarray:
    """Fused gather-free per-plane matmul: y[e] = epilogue(x[e] @ W_bsr[e]).

    One call for the whole plane stack (the MoE expert dimension) —
    no python loop over planes, no per-expert stack.  Epilogue
    multiplier/residual are shaped (E, ..., n) like the output."""
    e = x.shape[0]
    lead = x.shape[1:-1]
    k = x.shape[-1]
    n = planes.shape[-1]
    x3 = x.reshape(e, -1, k)
    epi = None if epilogue is None else epilogue.map_operands(
        lambda a: a.reshape(e, -1, a.shape[-1]))
    if _use_ref(mode):
        y = _ref.bsr_planes_matmul_ref(x3, planes, epilogue=epi)
    else:
        y = bsr_planes_matmul_pallas(
            x3, planes, bm=bm, epilogue=epi, interpret=(mode == "interpret")
        )
    return y.reshape(e, *lead, n)


@functools.partial(jax.jit,
                   static_argnames=("mode", "pages_per_step", "window"))
def paged_attention_decode(
    q: jnp.ndarray,            # (B, H, dh) — rotated query, new token
    k_new: jnp.ndarray,        # (B, K, dh) — rotated K, new token (in-register)
    v_new: jnp.ndarray,        # (B, K, dh)
    k_pool: jnp.ndarray,       # (P, K, page_size, dh) physical pages
    v_pool: jnp.ndarray,
    page_table: jnp.ndarray,   # (B, max_pages) int32 pool ids
    cache_len: jnp.ndarray,    # (B,) int32 — #prior tokens
    *,
    mode: str = "auto",
    pages_per_step: int = 8,   # ref-path segment width (perf only)
    window: Optional[int] = None,  # sliding window: keys > cache_len - W
) -> jnp.ndarray:
    """Fused paged decode attention: walks ``page_table`` with an online
    softmax, O(cache_len) work/traffic, no logical-view gather.  The new
    token's K/V never round-trips through the pool — it seeds the
    accumulator in-register.  With ``window`` only the pages the window
    sees are walked.  Returns (B, H, dh) fp32."""
    if _use_ref(mode):
        return paged_attention_decode_ref(
            q, k_new, v_new, k_pool, v_pool, page_table, cache_len,
            pages_per_step=pages_per_step, window=window)
    return paged_attention_decode_pallas(
        q, k_new, v_new, k_pool, v_pool, page_table, cache_len,
        interpret=(mode == "interpret"), window=window)


@functools.partial(jax.jit, static_argnames=("mode",))
def paged_kv_write(
    k_new: jnp.ndarray,        # (B, K, dh) — rotated K, new token
    v_new: jnp.ndarray,        # (B, K, dh)
    k_pool: jnp.ndarray,       # (P, K, page_size, dh) physical pages
    v_pool: jnp.ndarray,
    page_table: jnp.ndarray,   # (B, max_pages) int32 pool ids
    cache_len: jnp.ndarray,    # (B,) int32 — the new token's position
    *,
    mode: str = "auto",
):
    """The new token's K/V into each row's page at ``cache_len``; returns
    (k_pool, v_pool).  The ref path is an XLA scatter; the Pallas kernel
    aliases the pools and moves one page per row, so a decode loop that
    carries the pools updates them in place."""
    if _use_ref(mode):
        return paged_kv_write_ref(k_new, v_new, k_pool, v_pool, page_table,
                                  cache_len)
    return paged_kv_write_pallas(k_new, v_new, k_pool, v_pool, page_table,
                                 cache_len, interpret=(mode == "interpret"))


@functools.partial(
    jax.jit,
    static_argnames=("bm", "mode", "pages_per_step", "q_offset", "window"))
def paged_attention_prefill(
    q: jnp.ndarray,            # (B, S, H, dh) — rotated, pos [q_offset, q_offset+S)
    k_pool: jnp.ndarray,       # (P, K, page_size, dh) — context K/V already
    v_pool: jnp.ndarray,       #   scattered into the rows' pages
    page_table: jnp.ndarray,   # (B, max_pages) int32
    lengths: jnp.ndarray,      # (B,) int32 per-row TOTAL length (<= q_offset+S)
    *,
    bm: int = 64,              # Pallas query-tile rows
    mode: str = "auto",
    pages_per_step: int = 8,
    q_offset: int = 0,         # static logical position of q row 0
    window: Optional[int] = None,  # sliding window: keys > qpos - W
) -> jnp.ndarray:
    """Causal paged prefill attention over the same page walk (bm-tiled
    query blocks in the Pallas kernel).  ``q_offset > 0`` is the
    tail-only prefill of a prefix-cache hit: queries sit at logical
    positions ``[q_offset, q_offset+S)`` and attend over every earlier
    page in the table, including shared prefix pages this request never
    computed (DESIGN.md §12).  Rows past ``lengths`` produce zeros.
    Returns (B, S, H, dh) fp32."""
    if _use_ref(mode):
        return paged_attention_prefill_ref(
            q, k_pool, v_pool, page_table, lengths,
            pages_per_step=pages_per_step, q_offset=q_offset, window=window)
    return paged_attention_prefill_pallas(
        q, k_pool, v_pool, page_table, lengths, bm=bm,
        interpret=(mode == "interpret"), q_offset=q_offset, window=window)


@functools.partial(jax.jit,
                   static_argnames=("block_rows", "activation", "mode"))
def moe_experts(
    x: jnp.ndarray,            # (R, D) rows grouped by held expert
    w_gate: jnp.ndarray,       # (E, D, F) held experts
    w_up: jnp.ndarray,         # (E, D, F)
    w_down: jnp.ndarray,       # (E, F, D)
    tile_expert: jnp.ndarray,  # (R // block_rows,) int32 held expert per tile
    live_tiles: jnp.ndarray,   # (1,) int32 tiles holding rows
    *,
    block_rows: int,
    activation: str = "silu",
    mode: str = "auto",
) -> jnp.ndarray:
    """Held experts' gated FFN over rows grouped by expert (dropless MoE,
    kernels/moe_experts.py): row r gets ``down(act(x_r gate) * x_r up)``
    of its tile's expert, fp32.  Rows of dead tiles are zero on the ref
    path and unwritten on the kernel's; callers read live rows only."""
    if _use_ref(mode):
        return moe_experts_ref(x, w_gate, w_up, w_down, tile_expert,
                               live_tiles, block_rows=block_rows,
                               activation=activation)
    return moe_experts_pallas(x, w_gate, w_up, w_down, tile_expert,
                              live_tiles, block_rows=block_rows,
                              activation=activation,
                              interpret=(mode == "interpret"))


@functools.partial(jax.jit, static_argnames=("bk", "bn", "mode"))
def structure_norms(
    w: jnp.ndarray, *, bk: int = 128, bn: int = 128, mode: str = "auto"
) -> jnp.ndarray:
    """Tile L2 norms (grid_k, grid_n) fp32 for a (K, N) weight."""
    if _use_ref(mode):
        return _ref.structure_norms_ref(w, bk, bn)
    return structure_norms_pallas(w, bk=bk, bn=bn, interpret=(mode == "interpret"))
