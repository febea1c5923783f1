"""Held experts' SwiGLU FFN over rows grouped by expert: one grouped
matmul kernel for a dropless MoE layer.

The caller (``models/moe.moe_serve``) routes every token over all of the
router's experts and lays the (token, held expert) pairs out as rows of
``x`` (R, D), sorted by held expert, each expert's group padded to a
whole number of ``block_rows`` row tiles.  ``tile_expert`` (n_tiles,)
names the held expert of each row tile and ``live_tiles`` how many tiles
hold rows; tiles past it are dead.  Row ``r`` of the result is

    down_e( act(x_r @ gate_e) * (x_r @ up_e) )         e = its tile's expert

in float32, the hidden product cast to ``x``'s type before the down
projection (as the dense MLP does).  Every row depends on its own input
alone, so a token's result does not depend on which other tokens share
the call.

The kernel's grid is one step per row tile.  Each held expert's three
weight matrices are whole blocks indexed by the tile's expert: the tiles
of one expert are consecutive, so a repeated block index skips the DMA
and each held expert's weights are read once per call; an expert with no
rows is never read.  Dead tiles repeat the last live tile's blocks (no
DMA) and skip the compute; their output rows are left unwritten, and the
caller reads live rows only.

Two backends behind ``ops.moe_experts``:

* :func:`moe_experts_ref` — pure jnp, every held expert over every row,
  selected per row (CPU serving and tests);
* :func:`moe_experts_pallas` — the TPU kernel (``interpret=True`` runs
  its body on CPU).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["moe_experts_ref", "moe_experts_pallas", "ACTIVATIONS"]

ACTIVATIONS = {"silu": jax.nn.silu}
VMEM_LIMIT_CAP = 100 * 1024 * 1024      # of v5e's 128 MiB of VMEM


def _ffn(x, wg, wu, wd, activation: str):
    g = jnp.dot(x, wg, preferred_element_type=jnp.float32)
    u = jnp.dot(x, wu, preferred_element_type=jnp.float32)
    h = (ACTIVATIONS[activation](g) * u).astype(x.dtype)
    return jnp.dot(h, wd, preferred_element_type=jnp.float32)


def moe_experts_ref(
    x: jnp.ndarray,            # (R, D) rows grouped by held expert
    w_gate: jnp.ndarray,       # (E, D, F)
    w_up: jnp.ndarray,         # (E, D, F)
    w_down: jnp.ndarray,       # (E, F, D)
    tile_expert: jnp.ndarray,  # (n_tiles,) int32
    live_tiles: jnp.ndarray,   # (1,) int32
    *,
    block_rows: int,
    activation: str = "silu",
) -> jnp.ndarray:
    """(R, D) fp32; rows of dead tiles are zero."""
    row_tile = jnp.arange(x.shape[0]) // block_rows
    row_expert = tile_expert[row_tile]
    out = jnp.zeros((x.shape[0], w_down.shape[-1]), jnp.float32)
    for e in range(w_gate.shape[0]):
        y = _ffn(x, w_gate[e], w_up[e], w_down[e], activation)
        out = jnp.where((row_expert == e)[:, None], y, out)
    return jnp.where((row_tile < live_tiles[0])[:, None], out, 0.0)


def _kernel(te_ref, live_ref, x_ref, wg_ref, wu_ref, wd_ref, o_ref, *,
            activation: str):
    @pl.when(pl.program_id(0) < live_ref[0])
    def _tile():
        o_ref[...] = _ffn(x_ref[...], wg_ref[0], wu_ref[0], wd_ref[0],
                          activation)


def moe_experts_pallas(
    x: jnp.ndarray,
    w_gate: jnp.ndarray,
    w_up: jnp.ndarray,
    w_down: jnp.ndarray,
    tile_expert: jnp.ndarray,
    live_tiles: jnp.ndarray,
    *,
    block_rows: int,
    activation: str = "silu",
    interpret: bool = False,
) -> jnp.ndarray:
    """(R, D) fp32; rows of dead tiles are not written."""
    r, d = x.shape
    _, _, f = w_gate.shape
    tm = block_rows
    n_tiles = r // tm
    if n_tiles * tm != r:
        raise ValueError(f"{r} rows are not whole tiles of {tm}")

    # dead tiles (i >= live) repeat the last live tile's blocks: no DMA
    def row_map(i, te, live):
        return (jnp.minimum(i, jnp.maximum(live[0] - 1, 0)), 0)

    def w_map(i, te, live):
        return (te[jnp.minimum(i, jnp.maximum(live[0] - 1, 0))], 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(n_tiles,),
        in_specs=[
            pl.BlockSpec((tm, d), row_map),
            pl.BlockSpec((1, d, f), w_map),
            pl.BlockSpec((1, d, f), w_map),
            pl.BlockSpec((1, f, d), w_map),
        ],
        out_specs=pl.BlockSpec((tm, d), row_map),
    )
    kwargs = {}
    if not interpret:
        # double-buffered weights of one expert, row tiles in and out, and
        # the tile's (tm, F) products
        need = (2 * 3 * d * f * w_gate.dtype.itemsize
                + 2 * tm * d * (x.dtype.itemsize + 4) + 4 * tm * f * 4)
        kwargs["compiler_params"] = pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=min(need + 8 * 1024 * 1024, VMEM_LIMIT_CAP))
    return pl.pallas_call(
        functools.partial(_kernel, activation=activation),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((r, d), jnp.float32),
        interpret=interpret,
        name="moe_experts",
        **kwargs,
    )(tile_expert, live_tiles, x, w_gate, w_up, w_down)
