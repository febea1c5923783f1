"""Primitive layers: dense, norms, embeddings, rotary (+M-RoPE).

Conventions
-----------
* Params are nested dicts of jnp arrays; leaf names: "kernel", "bias",
  "scale".  Matmul kernels are (in, out) so the pruning structures map
  directly onto (bk, bn) MXU tiles of the (K, N) matmul.
* Matmuls accumulate in fp32 (``preferred_element_type``) and cast back to
  the activation dtype — the TPU-native mixed-precision policy.
* ``matmul`` is the single sparse-execution dispatch point (DESIGN.md §6):
  a kernel leaf may be a dense array *or* a packed ``BSRWeight`` /
  ``BSRPlanes`` (from ``repro.sparse.pack_params``); packed leaves route
  to ``kernels.ops.bsr_matmul`` which skips pruned tiles outright.
* ``logical_constraint`` annotates logical axes; it is a no-op outside a
  mesh/rules context so the same code runs in CPU unit tests.
"""
from __future__ import annotations

import math
from typing import Any, Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.packing import BSRPlanes, BSRWeight
from repro.distributed.sharding import logical_constraint
from repro.kernels.ops import (
    Epilogue,
    apply_epilogue,
    bsr_matmul,
    bsr_planes_matmul,
    make_epilogue,
)

__all__ = [
    "matmul", "expert_matmul",
    "dense_init", "dense",
    "rmsnorm_init", "rmsnorm",
    "layernorm_init", "layernorm",
    "embed_init", "embed_lookup", "unembed_logits",
    "rope_frequencies", "apply_rope", "apply_mrope",
    "sinusoidal_positions", "truncated_normal_init",
]


def truncated_normal_init(key, shape, stddev: float, dtype) -> jnp.ndarray:
    x = jax.random.truncated_normal(key, -2.0, 2.0, shape, jnp.float32) * stddev
    return x.astype(dtype)


# ---------------------------------------------------------------------------
# Dense
# ---------------------------------------------------------------------------

def dense_init(
    key,
    in_dim: int,
    out_dim: int,
    *,
    use_bias: bool = False,
    dtype=jnp.float32,
    stddev: Optional[float] = None,
) -> Dict[str, jnp.ndarray]:
    stddev = stddev if stddev is not None else 1.0 / math.sqrt(in_dim)
    p = {"kernel": truncated_normal_init(key, (in_dim, out_dim), stddev, dtype)}
    if use_bias:
        p["bias"] = jnp.zeros((out_dim,), dtype)
    return p


def matmul(x: jnp.ndarray, w, *, accum=jnp.float32, epilogue=None) -> jnp.ndarray:
    """x (..., K) @ w (K, N) -> (..., N) in ``accum`` dtype.

    The sparse-execution dispatch point: a packed ``BSRWeight`` routes to
    the zero-skipping BSR kernel (ref on CPU, Pallas on TPU); dense arrays
    take the einsum path.  Everything above (dense/ffn/attention/moe and
    both the forward and decode stacks) is agnostic to which it gets.

    ``epilogue`` (kernels.Epilogue) fuses bias/activation/gate/residual
    into the kernel on the packed path; the dense path applies the same
    fp32 op order on the einsum output, so both paths stay bit-compatible
    with the unfused composition (DESIGN.md §8)."""
    if isinstance(w, BSRWeight):
        return bsr_matmul(x, w, epilogue=epilogue).astype(accum)
    y = jnp.einsum("...k,kn->...n", x, w, preferred_element_type=accum)
    return apply_epilogue(y, epilogue)


def expert_matmul(h: jnp.ndarray, w, *, accum=jnp.float32, epilogue=None) -> jnp.ndarray:
    """Batched expert matmul (g, E, C, d) @ (E, d, f) -> (g, E, C, f).

    ``BSRPlanes`` (flattened per-expert BSR) issue ONE fused zero-skipping
    kernel call over the whole plane stack — no python loop over experts,
    no per-expert output stack; a fully-pruned expert costs only its
    skipped padding slots.  Dense 3-D weights take the batched einsum.
    ``epilogue`` operands (multiplier/residual) are output-shaped
    (g, E, C, f); the packed path transposes them alongside ``h``."""
    if isinstance(w, BSRPlanes):
        he = jnp.moveaxis(h, 1, 0)                            # (E, g, C, d)
        epi = None if epilogue is None else epilogue.map_operands(
            lambda a: jnp.moveaxis(a, 1, 0))
        y = bsr_planes_matmul(he, w, epilogue=epi)
        return jnp.moveaxis(y, 0, 1).astype(accum)            # (g, E, C, f)
    y = jnp.einsum("gecd,edf->gecf", h, w, preferred_element_type=accum)
    return apply_epilogue(y, epilogue)


def dense(
    p: Dict[str, jnp.ndarray],
    x: jnp.ndarray,
    *,
    accum=jnp.float32,
    activation: Optional[str] = None,
    multiplier: Optional[jnp.ndarray] = None,
    residual: Optional[jnp.ndarray] = None,
) -> jnp.ndarray:
    """Matmul with selectable accumulation dtype and a fused epilogue.

    ``accum=bfloat16`` on *row-parallel* matmuls (wo, w_down) lets GSPMD
    all-reduce the partial sums in bf16 — halves the dominant TP collective
    bytes (EXPERIMENTS.md §Perf); the MXU still accumulates each partial in
    fp32 internally.

    ``activation``/``multiplier``/``residual`` (plus the layer bias) form
    the fused tail ``act(y + bias) * multiplier + residual`` — one kernel
    on the packed path instead of three (M, N) round-trips."""
    epi = make_epilogue(bias=p.get("bias"), activation=activation,
                        multiplier=multiplier, residual=residual)
    y = matmul(x, p["kernel"], accum=accum, epilogue=epi)
    return y.astype(x.dtype)


# ---------------------------------------------------------------------------
# Norms
# ---------------------------------------------------------------------------

def rmsnorm_init(dim: int, dtype=jnp.float32) -> Dict[str, jnp.ndarray]:
    return {"scale": jnp.ones((dim,), dtype)}


def rmsnorm(p, x: jnp.ndarray, *, eps: float = 1e-6) -> jnp.ndarray:
    xf = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
    y = xf * jax.lax.rsqrt(var + eps)
    return (y * p["scale"].astype(jnp.float32)).astype(x.dtype)


def layernorm_init(dim: int, dtype=jnp.float32) -> Dict[str, jnp.ndarray]:
    return {"scale": jnp.ones((dim,), dtype), "bias_vec": jnp.zeros((dim,), dtype)}


def layernorm(p, x: jnp.ndarray, *, eps: float = 1e-5) -> jnp.ndarray:
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.var(xf, axis=-1, keepdims=True)
    y = (xf - mu) * jax.lax.rsqrt(var + eps)
    y = y * p["scale"].astype(jnp.float32) + p["bias_vec"].astype(jnp.float32)
    return y.astype(x.dtype)


# ---------------------------------------------------------------------------
# Embedding / unembedding (vocab-parallel)
# ---------------------------------------------------------------------------

def embed_init(key, vocab: int, dim: int, dtype=jnp.float32) -> Dict[str, jnp.ndarray]:
    return {"embedding": truncated_normal_init(key, (vocab, dim), 1.0, dtype)}


def embed_lookup(p, tokens: jnp.ndarray, dtype=None) -> jnp.ndarray:
    """(B, S) int32 -> (B, S, D).  Table is vocab-sharded on the TP axis;
    GSPMD partitions the gather (partial gather + all-reduce)."""
    table = p["embedding"]
    out = jnp.take(table, tokens, axis=0)
    out = logical_constraint(out, "batch", "seq", "embed")
    return out.astype(dtype or table.dtype)


def unembed_logits(p, x: jnp.ndarray) -> jnp.ndarray:
    """(B, S, D) -> (B, S, V) fp32 logits, vocab-sharded."""
    table = p["embedding"]
    logits = jnp.einsum("bsd,vd->bsv", x, table, preferred_element_type=jnp.float32)
    return logical_constraint(logits, "batch", "seq", "vocab")


# ---------------------------------------------------------------------------
# Rotary embeddings (standard + multimodal M-RoPE)
# ---------------------------------------------------------------------------

def rope_frequencies(head_dim: int, theta: float = 10000.0) -> jnp.ndarray:
    half = head_dim // 2
    return 1.0 / (theta ** (jnp.arange(0, half, dtype=jnp.float32) / half))


def _rope_rotate(x: jnp.ndarray, sin: jnp.ndarray, cos: jnp.ndarray) -> jnp.ndarray:
    """x (..., dh); sin/cos broadcastable to (..., dh/2)."""
    half = x.shape[-1] // 2
    x1, x2 = x[..., :half], x[..., half:]
    xf1, xf2 = x1.astype(jnp.float32), x2.astype(jnp.float32)
    return jnp.concatenate(
        [xf1 * cos - xf2 * sin, xf2 * cos + xf1 * sin], axis=-1
    ).astype(x.dtype)


def yarn_frequencies(head_dim: int, rope) -> np.ndarray:
    """YaRN inverse frequencies (dh/2,) of a ``RopeSpec`` with
    ``yarn_factor`` set — HF transformers' ``_compute_yarn_parameters``
    (truncated correction range): dims rotating more than ``beta_fast``
    times over ``yarn_original_max`` positions keep their frequency,
    those under ``beta_slow`` are interpolated by ``1/factor``, with a
    linear ramp between."""
    dim, base = head_dim, rope.theta

    def correction_dim(rotations):
        return (dim * math.log(rope.yarn_original_max
                               / (rotations * 2 * math.pi))
                / (2 * math.log(base)))

    low = max(math.floor(correction_dim(rope.yarn_beta_fast)), 0)
    high = min(math.ceil(correction_dim(rope.yarn_beta_slow)), dim - 1)
    if low == high:
        high += 0.001
    pos_freqs = base ** (np.arange(0, dim, 2, dtype=np.float64) / dim)
    extrapolation = 1.0 / pos_freqs
    interpolation = 1.0 / (rope.yarn_factor * pos_freqs)
    ramp = np.clip((np.arange(dim // 2) - low) / (high - low), 0, 1)
    keep = 1.0 - ramp                      # share of the extrapolated freq
    return (interpolation * (1.0 - keep) + extrapolation * keep).astype(
        np.float32)


def apply_rope(x: jnp.ndarray, positions: jnp.ndarray, *,
               theta: float = 10000.0, rope=None) -> jnp.ndarray:
    """x (B, S, H, dh), positions (B, S) -> rotated x.  ``rope`` (a
    ``configs.base.RopeSpec``) overrides ``theta``; with YaRN its
    frequencies are :func:`yarn_frequencies` and cos/sin are scaled by
    its attention factor."""
    scale = 1.0
    if rope is not None and rope.yarn_factor is not None:
        inv = jnp.asarray(yarn_frequencies(x.shape[-1], rope))
        scale = rope.yarn_attention_factor
    else:
        inv = rope_frequencies(x.shape[-1],
                               rope.theta if rope is not None else theta)
    ang = positions.astype(jnp.float32)[..., None] * inv  # (B, S, dh/2)
    sin, cos = jnp.sin(ang)[:, :, None, :], jnp.cos(ang)[:, :, None, :]
    if scale != 1.0:
        sin, cos = sin * scale, cos * scale
    return _rope_rotate(x, sin, cos)


def apply_mrope(
    x: jnp.ndarray,
    positions: jnp.ndarray,
    sections: Sequence[int],
    *,
    theta: float = 10000.0,
) -> jnp.ndarray:
    """Qwen2-VL multimodal RoPE.

    positions (B, S, 3) = (temporal, height, width) ids; the dh/2 frequency
    slots are split into ``sections`` (e.g. [16, 24, 24]) and each section
    uses its own position component.
    """
    half = x.shape[-1] // 2
    assert sum(sections) == half, (sections, half)
    inv = rope_frequencies(x.shape[-1], theta)  # (half,)
    comp = np.concatenate(
        [np.full(s, i, dtype=np.int32) for i, s in enumerate(sections)]
    )
    pos_per_slot = jnp.take(positions.astype(jnp.float32), jnp.asarray(comp), axis=-1)
    ang = pos_per_slot * inv  # (B, S, half)
    sin, cos = jnp.sin(ang)[:, :, None, :], jnp.cos(ang)[:, :, None, :]
    return _rope_rotate(x, sin, cos)


def sinusoidal_positions(length: int, dim: int) -> jnp.ndarray:
    """Whisper-style fixed sinusoidal embeddings, (length, dim) fp32."""
    pos = np.arange(length)[:, None]
    idx = np.arange(dim // 2)[None, :]
    angle = pos / (10000.0 ** (2 * idx / dim))
    out = np.concatenate([np.sin(angle), np.cos(angle)], axis=-1)
    return jnp.asarray(out, dtype=jnp.float32)
