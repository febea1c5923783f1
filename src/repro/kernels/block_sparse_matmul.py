"""Block-sparse (BSR) matmul Pallas TPU kernels — the paper's §III-C codegen.

The paper's HLS generator emits RTL that skips multiplications by pruned
structures.  The TPU equivalent: the grid iterates only over *surviving*
weight tiles; the per-column block-row indices and flat-store slots are
scalar-prefetched (SMEM) so each grid step DMAs exactly one live (bk, bn)
weight tile and the matching (bm, bk) activation tile HBM->VMEM.  Pruned
tiles cost neither MXU passes nor HBM traffic — the "DSP and BRAM
removal" of the paper, in roofline terms: compute term x (1 - structure
sparsity), memory term likewise.

Layout (from core/packing.py — the flat store + per-column map):
    indices (grid_n, max_nnz) int32, -1-padded per block-column
    slots   (grid_n, max_nnz) int32 into the flat store, 0-padded
    blocks  (nnz, bk, bn) flat store, column-major, single weight copy

Grid: (m_tiles, grid_n, max_nnz) — the ``bm``-tiled leading dimension
covers prefill-shaped (large-M) GEMMs; output tile (i, j) accumulates
over its column's live tiles with the Pallas pipeline double-buffering
the flat-store block DMAs across the innermost nnz loop (each step's
tile prefetches while the previous one multiplies).  Padding slots are
skipped with ``pl.when`` (they fetch flat slot 0, a benign redundant DMA
bounded by the per-column padding).

Epilogue fusion (DESIGN.md §8): bias add, activation, SwiGLU gate
multiply and residual add run on the fp32 accumulator in VMEM at the
last slot step of every output tile — the (M, N) intermediate never
round-trips to HBM.

MXU alignment: bm, bk, bn should be multiples of (8, 128) sublane/lane
tiles; fp32 accumulation in an output-resident VMEM tile.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.packing import BSRPlanes, BSRWeight
from .epilogue import Epilogue

__all__ = [
    "bsr_matmul_kernel", "bsr_matmul_pallas",
    "bsr_planes_matmul_kernel", "bsr_planes_matmul_pallas",
]


def _epi_flags(epi: Optional[Epilogue]):
    if epi is None:
        return False, None, False, False
    return (epi.bias is not None, epi.activation,
            epi.multiplier is not None, epi.residual is not None)


def _fused_tail(y, epi_refs, has_bias, act, has_mult, has_res):
    """The in-VMEM epilogue on the fp32 accumulator tile — static python
    branches, same op order as kernels/epilogue.apply_epilogue."""
    k = 0
    if has_bias:
        y = y + epi_refs[k][...].astype(jnp.float32)
        k += 1
    if act is not None:
        y = getattr(jax.nn, act)(y)
    if has_mult:
        y = y * epi_refs[k][...].astype(jnp.float32)
        k += 1
    if has_res:
        y = y + epi_refs[k][...].astype(jnp.float32)
        k += 1
    return y


def bsr_matmul_kernel(idx_ref, slot_ref, x_ref, w_ref, *rest,
                      nnz_steps, has_bias, act, has_mult, has_res):
    """One grid step: o[i, j] += x[i, idx[j, s]] @ blocks[slot[j, s]],
    with the fused epilogue applied at the column's last slot step."""
    j = pl.program_id(1)
    s = pl.program_id(2)
    o_ref = rest[-1]

    @pl.when(s == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    @pl.when(idx_ref[j, s] >= 0)
    def _accum():
        o_ref[...] += jnp.dot(
            x_ref[...], w_ref[0], preferred_element_type=jnp.float32
        )

    if has_bias or act is not None or has_mult or has_res:
        @pl.when(s == nnz_steps - 1)
        def _epilogue():
            o_ref[...] = _fused_tail(
                o_ref[...], rest[:-1], has_bias, act, has_mult, has_res)


def _pad_mn(a: jnp.ndarray, m_pad: int, n_pad: int) -> jnp.ndarray:
    pm, pn = m_pad - a.shape[0], n_pad - a.shape[1]
    if pm or pn:
        a = jnp.pad(a, ((0, pm), (0, pn)))
    return a


def bsr_matmul_pallas(
    x: jnp.ndarray,             # (M, K)
    bsr: BSRWeight,
    *,
    bm: int = 128,
    epilogue: Optional[Epilogue] = None,
    interpret: bool = False,
) -> jnp.ndarray:
    """y = epilogue(x @ W_bsr), fp32 accumulation, returns (M, n) in
    x.dtype.  Epilogue operands (multiplier/residual) are (M, n)."""
    m, k = x.shape
    n = bsr.shape[1]
    grid_n, max_nnz = bsr.indices.shape
    bk, bn = bsr.blocking.bk, bsr.blocking.bn
    if k % bk:
        x = jnp.pad(x, ((0, 0), (0, bk * ((k + bk - 1) // bk) - k)))
    bm = min(bm, m)
    pad_m = (-m) % bm
    if pad_m:
        x = jnp.pad(x, ((0, pad_m), (0, 0)))
    m_tiles = x.shape[0] // bm

    has_bias, act, has_mult, has_res = _epi_flags(epilogue)
    operands = [x, bsr.blocks]
    in_specs = [
        pl.BlockSpec(
            (bm, bk), lambda i, j, s, idx, slt: (i, jnp.maximum(idx[j, s], 0))
        ),
        pl.BlockSpec((1, bk, bn), lambda i, j, s, idx, slt: (slt[j, s], 0, 0)),
    ]
    if has_bias:
        operands.append(_pad_mn(
            epilogue.bias.astype(jnp.float32)[None], 1, grid_n * bn))
        in_specs.append(pl.BlockSpec((1, bn), lambda i, j, s, idx, slt: (0, j)))
    for operand in (epilogue.multiplier if has_mult else None,
                    epilogue.residual if has_res else None):
        if operand is not None:
            operands.append(_pad_mn(operand, m_tiles * bm, grid_n * bn))
            in_specs.append(
                pl.BlockSpec((bm, bn), lambda i, j, s, idx, slt: (i, j)))

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(m_tiles, grid_n, max_nnz),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, s, idx, slt: (i, j)),
    )
    kwargs = {}
    if not interpret:
        kwargs["compiler_params"] = pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        )
    kernel = functools.partial(
        bsr_matmul_kernel, nnz_steps=max_nnz, has_bias=has_bias, act=act,
        has_mult=has_mult, has_res=has_res)
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((m_tiles * bm, grid_n * bn), jnp.float32),
        interpret=interpret,
        name="bsr_matmul",
        **kwargs,
    )(bsr.indices, bsr.slots, *operands)
    return out[:m, :n].astype(x.dtype)


# ---------------------------------------------------------------------------
# Fused per-plane (expert) BSR matmul
# ---------------------------------------------------------------------------

def bsr_planes_matmul_kernel(idx_ref, slot_ref, x_ref, w_ref, *rest,
                             nnz_steps, has_bias, act, has_mult, has_res):
    """One grid step: o[e, i, j] += x[e, i, idx[e, j, s]] @
    blocks[e, slot[e, j, s]].

    Identical math to ``bsr_matmul_kernel`` with a *plane-offset* grid
    dimension in front: plane ``e`` selects which expert's activations,
    index map and flat store the step touches, so the whole per-plane
    stack is one kernel launch instead of a python loop of E launches."""
    e = pl.program_id(1)
    j = pl.program_id(2)
    s = pl.program_id(3)
    o_ref = rest[-1]

    @pl.when(s == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    @pl.when(idx_ref[e, j, s] >= 0)
    def _accum():
        o_ref[...] += jnp.dot(
            x_ref[0], w_ref[0, 0], preferred_element_type=jnp.float32
        )[None]

    if has_bias or act is not None or has_mult or has_res:
        @pl.when(s == nnz_steps - 1)
        def _epilogue():
            o_ref[...] = _fused_tail(
                o_ref[...], rest[:-1], has_bias, act, has_mult, has_res)


def _pad_emn(a: jnp.ndarray, m_pad: int, n_pad: int) -> jnp.ndarray:
    pm, pn = m_pad - a.shape[1], n_pad - a.shape[2]
    if pm or pn:
        a = jnp.pad(a, ((0, 0), (0, pm), (0, pn)))
    return a


def bsr_planes_matmul_pallas(
    x: jnp.ndarray,             # (E, M, K)
    planes: BSRPlanes,
    *,
    bm: int = 128,
    epilogue: Optional[Epilogue] = None,
    interpret: bool = False,
) -> jnp.ndarray:
    """y[e] = epilogue(x[e] @ W_bsr[e]) in one fused launch -> (E, M, n).

    Epilogue operands (multiplier/residual) are (E, M, n); bias (n,) is
    broadcast across planes."""
    e, m, k = x.shape
    n = planes.shape[-1]
    _, grid_n, max_nnz = planes.indices.shape
    bk, bn = planes.blocking.bk, planes.blocking.bn
    if k % bk:
        x = jnp.pad(x, ((0, 0), (0, 0), (0, bk * ((k + bk - 1) // bk) - k)))
    bm = min(bm, m)
    pad_m = (-m) % bm
    if pad_m:
        x = jnp.pad(x, ((0, 0), (0, pad_m), (0, 0)))
    m_tiles = x.shape[1] // bm

    has_bias, act, has_mult, has_res = _epi_flags(epilogue)
    operands = [x, planes.blocks]
    in_specs = [
        pl.BlockSpec(
            (1, bm, bk),
            lambda i, p, j, s, idx, slt: (p, i, jnp.maximum(idx[p, j, s], 0)),
        ),
        pl.BlockSpec(
            (1, 1, bk, bn), lambda i, p, j, s, idx, slt: (p, slt[p, j, s], 0, 0)
        ),
    ]
    if has_bias:
        operands.append(_pad_mn(
            epilogue.bias.astype(jnp.float32)[None], 1, grid_n * bn))
        in_specs.append(
            pl.BlockSpec((1, bn), lambda i, p, j, s, idx, slt: (0, j)))
    for operand in (epilogue.multiplier if has_mult else None,
                    epilogue.residual if has_res else None):
        if operand is not None:
            operands.append(_pad_emn(operand, m_tiles * bm, grid_n * bn))
            in_specs.append(pl.BlockSpec(
                (1, bm, bn), lambda i, p, j, s, idx, slt: (p, i, j)))

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(m_tiles, e, grid_n, max_nnz),
        in_specs=in_specs,
        out_specs=pl.BlockSpec(
            (1, bm, bn), lambda i, p, j, s, idx, slt: (p, i, j)),
    )
    kwargs = {}
    if not interpret:
        kwargs["compiler_params"] = pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel", "arbitrary"),
        )
    kernel = functools.partial(
        bsr_planes_matmul_kernel, nnz_steps=max_nnz, has_bias=has_bias,
        act=act, has_mult=has_mult, has_res=has_res)
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(
            (e, m_tiles * bm, grid_n * bn), jnp.float32),
        interpret=interpret,
        name="bsr_planes_matmul",
        **kwargs,
    )(planes.indices, planes.slots, *operands)
    return out[:, :m, :n].astype(x.dtype)
