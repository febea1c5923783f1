"""Attention: GQA / MHA / sliding-window, chunked (flash-style) training
path, KV-cache decode path (flash-decode compatible sharding).

Training uses a *statically chunked* causal attention: an unrolled loop
over query chunks, each attending to keys `[lo, hi)` where the bounds are
python ints — so (i) peak memory is O(S·chunk) not O(S²), (ii) sliding
windows skip out-of-range KV chunks entirely (real FLOP savings, visible
in the roofline terms), (iii) XLA's cost analysis sees every chunk
(no while-loop undercount; see DESIGN.md §4).

Decode attends a single query over the whole cache with fp32 softmax.  For
``long_500k`` the cache's *sequence* dim is sharded ("kv_seq" logical
axis); the softmax over the sharded axis lowers to the flash-decode
partial-stats + all-reduce pattern under GSPMD.
"""
from __future__ import annotations

import math
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core.packing import BSRWeight
from repro.distributed.sharding import logical_constraint
from repro.kernels.ops import paged_attention_decode as _paged_decode_op
from repro.kernels.ops import paged_attention_prefill as _paged_prefill_op
from repro.kernels.ops import paged_kv_write as _paged_write_op
from .layers import apply_mrope, apply_rope, dense, dense_init

__all__ = [
    "attention_init",
    "attention_apply",
    "attention_prefill",
    "attention_decode",
    "cross_attention_prefill",
    "chunked_causal_attention",
    "full_attention",
    "init_kv_cache",
]

NEG_INF = -1e30


def attention_init(
    key,
    d_model: int,
    num_heads: int,
    kv_heads: int,
    head_dim: int,
    *,
    qkv_bias: bool = False,
    out_bias: bool = False,
    dtype=jnp.float32,
) -> Dict:
    ks = jax.random.split(key, 4)
    return {
        "wq": dense_init(ks[0], d_model, num_heads * head_dim, use_bias=qkv_bias, dtype=dtype),
        "wk": dense_init(ks[1], d_model, kv_heads * head_dim, use_bias=qkv_bias, dtype=dtype),
        "wv": dense_init(ks[2], d_model, kv_heads * head_dim, use_bias=qkv_bias, dtype=dtype),
        "wo": dense_init(
            ks[3], num_heads * head_dim, d_model, use_bias=out_bias, dtype=dtype,
            stddev=1.0 / math.sqrt(num_heads * head_dim),
        ),
    }


def _split_heads(x: jnp.ndarray, heads: int) -> jnp.ndarray:
    b, s, hd = x.shape
    return x.reshape(b, s, heads, hd // heads)


def _gqa_scores(q: jnp.ndarray, k: jnp.ndarray) -> jnp.ndarray:
    """q (B,Sq,K,G,dh), k (B,Sk,K,dh) -> (B,K,G,Sq,Sk) fp32."""
    return jnp.einsum("bqkgd,bskd->bkgqs", q, k, preferred_element_type=jnp.float32)


def _gqa_values(w: jnp.ndarray, v: jnp.ndarray) -> jnp.ndarray:
    """w (B,K,G,Sq,Sk) fp32, v (B,Sk,K,dh) -> (B,Sq,K,G,dh)."""
    return jnp.einsum("bkgqs,bskd->bqkgd", w, v.astype(jnp.float32))


def chunked_causal_attention(
    q: jnp.ndarray,           # (B, S, H, dh) — already rotated
    k: jnp.ndarray,           # (B, S, K, dh)
    v: jnp.ndarray,           # (B, S, K, dh)
    *,
    causal: bool = True,
    window: Optional[int] = None,
    chunk: int = 512,
    q_offset: int = 0,        # absolute position of q[0] (cross-chunk prefill)
) -> jnp.ndarray:
    b, s, h, dh = q.shape
    kv_heads = k.shape[2]
    g = h // kv_heads
    scale = 1.0 / math.sqrt(dh)
    qg = q.reshape(b, s, kv_heads, g, dh)
    sk = k.shape[1]
    chunk = min(chunk, s)
    out = []
    for qs in range(0, s, chunk):
        qe = min(qs + chunk, s)
        abs_qs, abs_qe = qs + q_offset, qe + q_offset
        hi = min(abs_qe, sk) if causal else sk
        lo = 0 if window is None else max(0, abs_qs - window + 1)
        if hi <= lo:
            out.append(jnp.zeros((b, qe - qs, kv_heads, g, dh), q.dtype))
            continue
        kc, vc = k[:, lo:hi], v[:, lo:hi]
        scores = _gqa_scores(qg[:, qs:qe], kc) * scale  # (B,K,G,q,kv)
        if causal or window is not None:
            qpos = jnp.arange(abs_qs, abs_qe)[:, None]
            kpos = jnp.arange(lo, hi)[None, :]
            mask = jnp.ones((qe - qs, hi - lo), bool)
            if causal:
                mask &= kpos <= qpos
            if window is not None:
                mask &= kpos > qpos - window
            scores = jnp.where(mask[None, None, None], scores, NEG_INF)
        w = jax.nn.softmax(scores, axis=-1)
        out.append(_gqa_values(w, vc).astype(q.dtype))
    return jnp.concatenate(out, axis=1).reshape(b, s, h, dh)


def full_attention(q, k, v, *, causal=True, window=None):
    """Unchunked oracle (tests)."""
    return chunked_causal_attention(q, k, v, causal=causal, window=window, chunk=q.shape[1])


def attention_apply(
    p: Dict,
    x: jnp.ndarray,                       # (B, S, D)
    *,
    num_heads: int,
    kv_heads: int,
    head_dim: int,
    positions: Optional[jnp.ndarray] = None,
    causal: bool = True,
    window: Optional[int] = None,
    chunk: int = 512,
    rope_theta: float = 10000.0,
    rope=None,                            # configs.base.RopeSpec
    mrope_sections: Optional[Tuple[int, ...]] = None,
    kv_input: Optional[jnp.ndarray] = None,   # cross-attention source
    use_rope: bool = True,
    accum=None,
    out_seq: str = "seq",
) -> jnp.ndarray:
    accum = accum or jnp.float32
    b, s, _ = x.shape
    src = kv_input if kv_input is not None else x
    q = _split_heads(dense(p["wq"], x), num_heads)
    k = _split_heads(dense(p["wk"], src), kv_heads)
    v = _split_heads(dense(p["wv"], src), kv_heads)
    q = logical_constraint(q, "batch", "seq", "heads", None)
    k = logical_constraint(k, "batch", "seq", "kv", None)
    v = logical_constraint(v, "batch", "seq", "kv", None)
    if use_rope:
        if positions is None:
            positions = jnp.broadcast_to(jnp.arange(s)[None], (b, s))
        if mrope_sections is not None:
            q = apply_mrope(q, positions, mrope_sections, theta=rope_theta)
            k = apply_mrope(k, positions, mrope_sections, theta=rope_theta)
        else:
            q = apply_rope(q, positions, theta=rope_theta, rope=rope)
            k = apply_rope(k, positions, theta=rope_theta, rope=rope)
    o = chunked_causal_attention(q, k, v, causal=causal, window=window, chunk=chunk)
    o = logical_constraint(o, "batch", "seq", "heads", None)
    out = _wo_project(p, o, num_heads, head_dim, accum, x.dtype)
    return logical_constraint(out, "batch", out_seq, "embed")


def _wo_project(p: Dict, o: jnp.ndarray, num_heads: int, head_dim: int,
                accum, dtype) -> jnp.ndarray:
    """Output projection for (B, S, H, dh) attention values."""
    b, s = o.shape[:2]
    if "bias" not in p["wo"] and not isinstance(p["wo"]["kernel"], BSRWeight):
        # contract (heads, dh) via a kernel-side reshape: reshaping the
        # *activation* (B,S,H,dh)->(B,S,H*dh) merges the heads-sharded dim
        # with dh and forces a full all-gather fwd+bwd (32 GB/step measured
        # on qwen/train_4k — EXPERIMENTS.md §Perf P5); the kernel reshape
        # is tile-aligned (whole heads per shard) and free.  A packed BSR
        # kernel has no dense (H*dh, D) view, so it takes the dispatch
        # path below — serving-only, where the all-gather concern is moot.
        w3 = p["wo"]["kernel"].reshape(num_heads, head_dim, -1)
        return jnp.einsum("bshd,hde->bse", o, w3,
                          preferred_element_type=accum).astype(dtype)
    return dense(p["wo"], o.reshape(b, s, num_heads * head_dim), accum=accum)


def _pages_view(pool: jnp.ndarray, page_table: jnp.ndarray) -> jnp.ndarray:
    """Gather a (P, K, ps, dh) pool through ``page_table`` (B, max_pages)
    into the contiguous logical view (B, max_pages · ps, K, dh)."""
    b, mp = page_table.shape
    _, kvh, ps, dh = pool.shape
    return pool[page_table].transpose(0, 1, 3, 2, 4).reshape(
        b, mp * ps, kvh, dh)


def _check_paged_window(fn: str, window, page_table) -> None:
    """A sliding window over a paged pool must see at least the query
    itself; the page walk is sized from it."""
    if page_table is not None and window is not None and window < 1:
        raise ValueError(f"{fn}: window={window} with page_table — a "
                         "sliding window must be >= 1 (None: full attention)")


def attention_prefill(
    p: Dict,
    x: jnp.ndarray,                       # (B, S, D)
    cache: Dict[str, jnp.ndarray],
    *,
    num_heads: int,
    kv_heads: int,
    head_dim: int,
    positions: Optional[jnp.ndarray] = None,
    window: Optional[int] = None,
    chunk: int = 512,
    rope_theta: float = 10000.0,
    rope=None,                            # configs.base.RopeSpec
    mrope_sections: Optional[Tuple[int, ...]] = None,
    use_rope: bool = True,
    accum=None,
    out_seq: str = "seq",
    page_table: Optional[jnp.ndarray] = None,   # (B, max_pages) -> pool ids
    paged_impl: str = "fused",                  # fused (page walk) | gather
    start_pos: int = 0,                         # static logical pos of x[:, 0]
) -> Tuple[jnp.ndarray, Dict[str, jnp.ndarray]]:
    """Batched causal prefill that also fills the KV cache.

    Runs the full-sequence attention (identical math to
    ``attention_apply``) and writes the (rotated) K/V for positions
    ``[0, S)`` into the cache so decode can continue at ``cache_len=S``.
    With a sliding-window ring cache (alloc <= window) only the last
    ``alloc`` tokens are kept, each at slot ``t % alloc`` — the same
    placement the per-token decode writes produce.

    With ``page_table`` the cache is a ``(num_pages, K, page_size, dh)``
    pool (DESIGN.md §9/§10): token ``t`` of row ``b`` is scattered
    straight into ``pool[table[b, t // ps], :, t % ps]``, then attention
    runs *over the pages themselves* with the fused bm-tiled page-walk
    kernel (kernels/paged_attention.py, DESIGN.md §11) — no contiguous
    logical view is ever materialized.  ``paged_impl="gather"`` keeps
    the legacy path for differential tests.  A sliding ``window`` over a
    pool masks keys at or before ``qpos - window`` and the fused kernel
    walks only the pages each query tile can see; every page stays
    allocated (there is no ring).

    ``start_pos`` (static, paged-only) runs a *tail-only* prefill: the
    tokens in ``x`` sit at logical positions ``[start_pos, start_pos+S)``
    and the first ``start_pos`` positions are already in the pool —
    shared prefix pages mapped into this row's table by the prefix cache
    (DESIGN.md §12).  K/V scatter at the offset slots and attention
    covers the full ``start_pos + S`` context."""
    accum = accum or jnp.float32
    _check_paged_window("attention_prefill", window, page_table)
    if paged_impl not in ("fused", "gather"):
        raise ValueError(f"unknown paged_impl {paged_impl!r}")
    if start_pos and page_table is None:
        raise ValueError(
            "attention_prefill: start_pos > 0 needs a page_table — the "
            "prefix lives in pool pages, a contiguous cache has no shared "
            "prefix to resume from (DESIGN.md §12)")
    b, s, _ = x.shape
    q = _split_heads(dense(p["wq"], x), num_heads)
    k = _split_heads(dense(p["wk"], x), kv_heads)
    v = _split_heads(dense(p["wv"], x), kv_heads)
    if use_rope:
        if positions is None:
            positions = jnp.broadcast_to(
                jnp.arange(start_pos, start_pos + s)[None], (b, s))
        if mrope_sections is not None:
            if positions.ndim == 2:
                positions = jnp.tile(positions[..., None], (1, 1, 3))
            q = apply_mrope(q, positions, mrope_sections, theta=rope_theta)
            k = apply_mrope(k, positions, mrope_sections, theta=rope_theta)
        else:
            q = apply_rope(q, positions, theta=rope_theta, rope=rope)
            k = apply_rope(k, positions, theta=rope_theta, rope=rope)

    alloc = cache["k"].shape[1]
    kc, vc = k.astype(cache["k"].dtype), v.astype(cache["v"].dtype)
    if page_table is not None:
        ps = cache["k"].shape[2]
        t = jnp.arange(start_pos, start_pos + s)
        pid = page_table[:, t // ps]                   # (B, S) pool pages
        off = jnp.broadcast_to(t % ps, (b, s))
        ck = cache["k"].at[pid, :, off].set(kc)
        cv = cache["v"].at[pid, :, off].set(vc)
        total = start_pos + s                          # full logical context
        if paged_impl == "fused":
            # attend straight over the pages: the fused kernel walks this
            # row's table from logical position 0 — covering shared
            # prefix pages this call never wrote — so other sequences'
            # pages (and unallocated ones) are never touched
            o = _paged_prefill_op(
                q, ck, cv, page_table, jnp.full((b,), total, jnp.int32),
                bm=min(chunk, s), q_offset=start_pos,
                window=window).astype(x.dtype)
        elif start_pos:
            # gather path with a prefix: materialize the logical view up
            # to the full context (every position < total is live), then
            # run the contiguous kernel with the query offset
            kv, vv = _pages_view(ck, page_table), _pages_view(cv, page_table)
            o = chunked_causal_attention(
                q, kv[:, :total].astype(q.dtype), vv[:, :total].astype(q.dtype),
                causal=True, window=window, chunk=chunk, q_offset=start_pos)
        else:
            o = chunked_causal_attention(q, k, v, causal=True, window=window,
                                         chunk=chunk)
    elif s <= alloc:
        ck = jax.lax.dynamic_update_slice(cache["k"], kc, (0, 0, 0, 0))
        cv = jax.lax.dynamic_update_slice(cache["v"], vc, (0, 0, 0, 0))
        o = chunked_causal_attention(q, k, v, causal=True, window=window,
                                     chunk=chunk)
    else:  # ring: keep the last `alloc` tokens at their decode slots
        slots = jnp.arange(s - alloc, s) % alloc
        ck = cache["k"].at[:, slots].set(kc[:, s - alloc:])
        cv = cache["v"].at[:, slots].set(vc[:, s - alloc:])
        o = chunked_causal_attention(q, k, v, causal=True, window=window,
                                     chunk=chunk)
    out = _wo_project(p, o, num_heads, head_dim, accum, x.dtype)
    out = logical_constraint(out, "batch", out_seq, "embed")
    return out, {**cache, "k": ck, "v": cv}


def cross_attention_prefill(
    p: Dict,
    x: jnp.ndarray,                       # (B, S, D) — normed decoder stream
    cache: Dict[str, jnp.ndarray],        # holds cross_k / cross_v
    *,
    num_heads: int,
    kv_heads: int,
    head_dim: int,
    chunk: int = 512,
) -> jnp.ndarray:
    """Full-sequence cross-attention over precomputed encoder K/V."""
    q = _split_heads(dense(p["wq"], x), num_heads)
    o = chunked_causal_attention(
        q, cache["cross_k"].astype(q.dtype), cache["cross_v"].astype(q.dtype),
        causal=False, window=None, chunk=chunk,
    )
    return _wo_project(p, o, num_heads, head_dim, jnp.float32, x.dtype)


# ---------------------------------------------------------------------------
# Decode path
# ---------------------------------------------------------------------------

def init_kv_cache(
    batch: int, max_len: int, kv_heads: int, head_dim: int, dtype=jnp.bfloat16
) -> Dict[str, jnp.ndarray]:
    return {
        "k": jnp.zeros((batch, max_len, kv_heads, head_dim), dtype),
        "v": jnp.zeros((batch, max_len, kv_heads, head_dim), dtype),
    }


def attention_decode(
    p: Dict,
    x: jnp.ndarray,                       # (B, 1, D)
    cache: Dict[str, jnp.ndarray],
    cache_len: jnp.ndarray,               # scalar or (B,) int32: #valid positions
    *,
    num_heads: int,
    kv_heads: int,
    head_dim: int,
    window: Optional[int] = None,
    rope_theta: float = 10000.0,
    rope=None,                            # configs.base.RopeSpec
    mrope_sections: Optional[Tuple[int, ...]] = None,
    use_rope: bool = True,
    update_cache: bool = True,
    page_table: Optional[jnp.ndarray] = None,   # (B, max_pages) -> pool ids
    paged_impl: str = "fused",                  # fused (page walk) | gather
) -> Tuple[jnp.ndarray, Dict[str, jnp.ndarray]]:
    """One-token decode over a (possibly seq-sharded) KV cache.

    ``cache_len`` may be a scalar (every row at the same position — the
    fixed-batch hot path) or a ``(B,)`` vector (ragged prompts /
    continuous batching): each row writes its new K/V at its own slot and
    masks scores past its own length, so right-padded rows never attend
    over garbage KV.

    With ``page_table`` the cache is a *pool*: ``k``/``v`` are
    ``(num_pages, K, page_size, dh)`` physical pages shared by all
    sequences, and row ``b`` reads/writes the logical slots named by
    ``page_table[b]`` (DESIGN.md §9).  The new token lands at page
    ``cache_len // page_size``, offset ``cache_len % page_size`` of its
    own table.  The default ``paged_impl="fused"`` attends by *walking*
    the table with an online softmax (kernels/paged_attention.py,
    DESIGN.md §11) — O(cache_len) traffic, the new token's K/V stays
    in-register; ``"gather"`` keeps the legacy logical-view gather
    (O(max_pages · page_size) traffic) for differential tests and
    benchmarks.  A sliding ``window`` over a pool sees keys after
    ``cache_len - window``; the fused walk starts at the page of the
    first of them (every page stays allocated: there is no ring).

    With the cache's seq dim sharded ("kv_seq"), GSPMD lowers the softmax
    to partial stats + all-reduce — the flash-decode pattern.
    """
    b = x.shape[0]
    # normalize to a per-row length vector; scalar == every row equal
    cache_len = jnp.broadcast_to(
        jnp.asarray(cache_len, jnp.int32).reshape(-1), (b,))
    paged = page_table is not None
    _check_paged_window("attention_decode", window, page_table)
    if paged and not update_cache:
        raise ValueError("paged KV caches do not support cross-attention "
                         "reads")
    if paged_impl not in ("fused", "gather"):
        raise ValueError(f"unknown paged_impl {paged_impl!r}")
    page_size = cache["k"].shape[2] if paged else None
    max_len = page_table.shape[1] * page_size if paged else cache["k"].shape[1]
    ring = (not paged) and window is not None and max_len <= window
    q = _split_heads(dense(p["wq"], x), num_heads)          # (B,1,H,dh)
    pos = cache_len[:, None]                                # (B, 1)
    if update_cache:
        write_pos = cache_len % max_len if ring else cache_len
        knew = _split_heads(dense(p["wk"], x), kv_heads)
        vnew = _split_heads(dense(p["wv"], x), kv_heads)
        if use_rope and mrope_sections is not None:
            pos3 = jnp.tile(pos[..., None], (1, 1, 3))
            q = apply_mrope(q, pos3, mrope_sections, theta=rope_theta)
            knew = apply_mrope(knew, pos3, mrope_sections, theta=rope_theta)
        elif use_rope:
            q = apply_rope(q, pos, theta=rope_theta, rope=rope)
            knew = apply_rope(knew, pos, theta=rope_theta, rope=rope)
        if paged:
            # this row's next token lands in its own page table entry at
            # logical page cache_len // page_size: in place on TPU
            ck, cv = _paged_write_op(knew[:, 0], vnew[:, 0], cache["k"],
                                     cache["v"], page_table, cache_len)
        else:
            rows = jnp.arange(b)
            ck = cache["k"].at[rows, write_pos].set(
                knew[:, 0].astype(cache["k"].dtype))
            cv = cache["v"].at[rows, write_pos].set(
                vnew[:, 0].astype(cache["v"].dtype))
        cache = {"k": ck, "v": cv}
    else:  # cross-attention: cache holds encoder K/V, no rope on q
        pass
    if paged and paged_impl == "fused":
        # walk the page table with an online softmax — no logical view,
        # O(cache_len) traffic; the rotated new-token K/V seeds the
        # accumulator in-register instead of round-tripping via the pool
        o32 = _paged_decode_op(
            q[:, 0], knew[:, 0], vnew[:, 0], cache["k"], cache["v"],
            page_table, cache_len, window=window)
        o = dense(p["wo"], o32.astype(x.dtype).reshape(
            b, 1, num_heads * head_dim))
        return o, cache
    if paged:
        ck = _pages_view(cache["k"], page_table)
        cv = _pages_view(cache["v"], page_table)
    else:
        ck = logical_constraint(cache["k"], "batch", "kv_seq", "kv", None)
        cv = logical_constraint(cache["v"], "batch", "kv_seq", "kv", None)

    g = num_heads // kv_heads
    qg = q.reshape(b, 1, kv_heads, g, head_dim)
    scores = _gqa_scores(qg, ck) / math.sqrt(head_dim)      # (B,K,G,1,S)
    kpos = jnp.arange(ck.shape[1])[None, :]                 # (1, S)
    clen = cache_len[:, None]                               # (B, 1)
    if not update_cache:
        valid = kpos < clen                         # cross-attn: encoder len
    elif ring:
        # ring slots hold the last min(cache_len+1, max_len) tokens — all
        # valid once full; before that, only slots [0, cache_len]
        valid = kpos <= clen
    else:
        valid = kpos <= clen
        if window is not None:
            valid &= kpos > clen - window
    scores = jnp.where(valid[:, None, None, None, :], scores, NEG_INF)
    if paged:
        # unallocated pages may hold anything (the null page is
        # NaN-poisoned in tests): a NEG_INF score zeroes the softmax
        # weight, but 0 * NaN = NaN in the value contraction — zero the
        # gathered V at dead positions too (a no-op for finite data)
        cv = jnp.where(valid[:, :, None, None], cv, 0)
    w = jax.nn.softmax(scores, axis=-1)
    o = _gqa_values(w, cv).astype(x.dtype)                  # (B,1,K,G,dh)
    o = dense(p["wo"], o.reshape(b, 1, num_heads * head_dim))
    return o, cache
