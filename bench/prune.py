"""The tile selection of a pruned configuration, made by the benchmark.

The rule is the optimum of the paper's knapsack (Eq. 4-8) when every
tile costs the same, which is the case for uniform (bk, bn) tiles: score
each tile by its L2 norm over the largest tile norm of its own matrix
(layer-normalized magnitude) and keep the ``1 - sparsity`` best tiles of
the whole model.  The benchmark makes the selection itself, so the
reference never takes a table from the program under test.
"""
from __future__ import annotations

from typing import Dict, Sequence

import jax
import jax.numpy as jnp


def _grid(w: jnp.ndarray, bk: int, bn: int):
    n_l, k, n = w.shape
    if k % bk or n % bn:
        raise ValueError(f"matmul {w.shape} is not a whole number of "
                         f"({bk}, {bn}) tiles")
    return w.reshape(n_l, k // bk, bk, n // bn, bn)


def tile_keep(w: Dict[str, jnp.ndarray], matmuls: Sequence[str],
              block: Sequence[int], sparsity: float) -> Dict[str, jnp.ndarray]:
    """{name: (layers, grid_k, grid_n) bool} tiles kept of each stacked
    (layers, in, out) matmul in ``matmuls``."""
    bk, bn = block
    scores, shapes = [], []
    for name in matmuls:
        t = _grid(w[name], bk, bn).astype(jnp.float32)
        norms = jnp.sqrt(jnp.sum(t * t, axis=(2, 4)))          # (L, gk, gn)
        norms = norms / jnp.max(norms, axis=(1, 2), keepdims=True)
        scores.append(norms.reshape(-1))
        shapes.append(norms.shape)
    flat = jnp.concatenate(scores)
    kept = int(flat.shape[0] * (1.0 - sparsity) + 1e-9)
    _, idx = jax.lax.top_k(flat, kept)
    keep = jnp.zeros(flat.shape, bool).at[idx].set(True)
    out, at = {}, 0
    for name, shape in zip(matmuls, shapes):
        size = shape[0] * shape[1] * shape[2]
        out[name] = keep[at:at + size].reshape(shape)
        at += size
    return out


def apply_keep(w: Dict[str, jnp.ndarray], keep: Dict[str, jnp.ndarray],
               block: Sequence[int]) -> Dict[str, jnp.ndarray]:
    """``w`` with every tile that ``keep`` drops set to exactly zero."""
    bk, bn = block
    out = dict(w)
    for name, k in keep.items():
        t = _grid(w[name], bk, bn)
        out[name] = jnp.where(k[:, :, None, :, None], t,
                              jnp.zeros((), t.dtype)).reshape(w[name].shape)
    return out
