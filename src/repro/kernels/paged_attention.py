"""Fused paged-attention kernels: walk the page table, never gather.

The serving pool stores every sequence's KV in fixed-size pages
``(num_pages, K, page_size, dh)`` with a per-row table of page ids
(serving/pages.py, DESIGN.md §9).  The pool is page-major, so one page
across all KV heads is one contiguous ``(1, K, page_size, dh)`` block
whose trailing dims the TPU's Mosaic compiler can tile: ``page_size``
must be a multiple of the pool dtype's sublane count (8 for fp32, 16
for bf16).  The naive decode path materializes the logical view first —
a gather of ``pool[page_table]`` to ``(b, max_len, K, dh)`` — so every
token pays O(max_pages · page_size) memory traffic no matter how short
the row's real context is.  These kernels instead *walk* the table with
an online-softmax accumulator carried across pages, and the
just-computed current token's K/V kept in-register (it seeds the
accumulator and never round-trips through the pool).  HBM traffic
scales with the live ``cache_len``, not the allocation — the same
locality argument the paper makes for structured pruning: compression
only pays when the kernel respects the memory layout.

Decode's grid is (batch, page block): one step covers one slot, all of
its KV heads and ``pages_per_step`` consecutive logical pages, scored as
a per-head batched contraction.  ``pages_per_step`` is derived from the
shapes (:func:`decode_pages_per_step`).  The grid is statically sized
by the table width, so the *step count* scales with ``max_pages``, not
with ``cache_len``: a slot with a short context still walks every block,
and its dead blocks pay the fixed per-step cost without their DMA.
Prefill's grid is (batch, kv_head, query tile, page block): one step
scores a query tile against ``pages_per_step`` pages, as many as fill
one 128-lane row of keys (:func:`prefill_pages_per_step`).

**Sliding windows** (``window=W``, static): a query at position ``i``
sees keys ``j`` with ``i - W < j <= i`` (HF's sliding-window causal
mask).  Decode's walk then starts at the page holding the first visible
position, ``max(cache_len - W + 1, 0)``, so no grid step and no DMA goes
to a page wholly behind the window; the grid's block count is bounded by
the window, not by the table width, and the partial first page is
masked.  Prefill starts each query tile's page walk at the first page
its earliest query can see.  Window calls carry their own kernel names,
``paged_attention_decode_window`` / ``paged_attention_prefill_window``;
``window=None`` builds the full-attention kernels.

Online-softmax recurrence per page (all fp32):

    m2  = max(m, max_s(scores))          # running max
    r   = exp(m - m2)                    # rescale factor for old state
    p   = where(valid, exp(s - m2), 0)   # page probabilities (unnormed)
    l   = l·r + Σ_s p                    # running normalizer
    acc = acc·r + p @ V_page             # running weighted values
    out = acc / l                        # after the last page

Decode seeds the state with the in-register current token — ``m = s_new,
l = 1, acc = v_new`` — so every row has a non-empty softmax even at
``cache_len == 0`` (a free slot parked on the null page).

Two backends behind ``ops.paged_attention_decode`` / ``_prefill``:

* ``*_ref``    — pure-jnp, but still **non-gathering**: a
  ``fori_loop`` over page *segments* bounded by ``max(cache_len)``, so
  CPU serving gets the same work-scales-with-context contract as the
  TPU kernel (and stays bit-comparable to it at the same
  ``pages_per_step`` — the ref mirrors the kernel's op sequence).
* ``*_pallas`` — the TPU kernel; ``interpret=True`` runs the same body
  on CPU for CI.  Page ids are scalar-prefetched (SMEM) and each pool
  BlockSpec index map clamps page slots past the live context to the
  last live page, so a revisited block index skips the DMA — traffic is
  O(cache_len) even though the grid is statically sized by the table
  width.

Masked positions never touch values: scores get the finite ``NEG_INF``
sentinel *and* the value contribution is zeroed (``p`` is where-masked),
so NaN poison in unallocated pages (the null page, freed pages) cannot
leak through a ``0 · NaN`` in the value contraction.

**The decode write** (``paged_kv_write``) puts each row's new K/V at
its page ``page_table[b, cache_len // ps]``, offset ``cache_len % ps``,
before the walk reads the pool.  The ``*_ref`` is an XLA scatter; for it
XLA holds a scanned pool in another layout than the Mosaic walk takes,
and copies every pool each tick.  The ``*_pallas`` kernel instead
aliases the pools (``input_output_aliases``): grid (B,), the page id and
offset scalar-prefetched, one page per row read, its one position
replaced and written back — the pool is updated in place.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = [
    "decode_blocks",
    "decode_pages_per_step",
    "paged_attention_decode_ref",
    "paged_attention_decode_pallas",
    "paged_attention_prefill_ref",
    "paged_attention_prefill_pallas",
    "paged_kv_write_ref",
    "paged_kv_write_pallas",
    "prefill_pages_per_step",
]

NEG_INF = -1e30  # finite mask sentinel (matches models/attention.py)
DECODE_VMEM_BUDGET = 4 * 1024 * 1024  # double-buffered K+V page blocks
# query rows (tokens x GQA group) of one prefill tile: its fp32
# accumulator, running stats and score tiles must fit scoped VMEM
PREFILL_TILE_ROWS = 2048
PREFILL_KEYS = 128  # key positions one prefill grid step scores


def _cdiv(a: int, b: int) -> int:
    return -(-a // b)


def _segment(pool: jnp.ndarray, pid: jnp.ndarray) -> jnp.ndarray:
    """Pages ``pid`` (B, n) of a (P, K, ps, dh) pool as one fp32
    (B, K, n·ps, dh) run of logical positions."""
    _, kvh, ps, dh = pool.shape
    b, n = pid.shape
    return pool[pid].transpose(0, 2, 1, 3, 4).reshape(
        b, kvh, n * ps, dh).astype(jnp.float32)


def _check_page_tiling(pool: jnp.ndarray) -> None:
    """A (1, ·, page_size, dh) pool block is tiled by Mosaic only when
    page_size fills whole sublane tiles of the pool dtype."""
    sublanes = 8 * 4 // pool.dtype.itemsize
    if pool.shape[2] % sublanes:
        raise ValueError(
            f"page_size {pool.shape[2]} is not a multiple of {sublanes}, the "
            f"sublane tiling of a {pool.dtype} KV pool on TPU")


# ---------------------------------------------------------------------------
# Decode write: each row's new K/V into its page, in place
# ---------------------------------------------------------------------------

def _write_slot(page_table: jnp.ndarray, cache_len: jnp.ndarray,
                page_size: int):
    """(page id, offset) (B,) of each row's next token: its own table
    entry at logical page ``cache_len // page_size``.  A row whose
    position lies past its table (a frozen row at a full budget) writes
    the null page, page 0: a Pallas block index is not bounds-checked,
    and the scatter would drop that write."""
    idx = cache_len // page_size
    last = page_table.shape[1] - 1
    pid = jnp.take_along_axis(
        page_table, jnp.minimum(idx, last)[:, None], axis=1)[:, 0]
    return jnp.where(idx <= last, pid, 0), cache_len % page_size


def paged_kv_write_ref(
    k_new: jnp.ndarray,        # (B, K, dh) — rotated K of the new token
    v_new: jnp.ndarray,        # (B, K, dh)
    k_pool: jnp.ndarray,       # (P, K, page_size, dh) physical pages
    v_pool: jnp.ndarray,       # (P, K, page_size, dh)
    page_table: jnp.ndarray,   # (B, max_pages) int32
    cache_len: jnp.ndarray,    # (B,) int32 — the new token's position
):
    """The new token's K/V scattered into both pools; returns them."""
    pid, off = _write_slot(page_table, cache_len, k_pool.shape[2])
    return (k_pool.at[pid, :, off].set(k_new.astype(k_pool.dtype)),
            v_pool.at[pid, :, off].set(v_new.astype(v_pool.dtype)))


def _kv_write_kernel(pid_ref, off_ref, kn_ref, vn_ref, kp_ref, vp_ref,
                     ko_ref, vo_ref):
    """Grid (B,): row ``b``'s page of each pool with position ``off[b]``
    replaced by its new (1, K, 1, dh) row."""
    del pid_ref                 # consumed by the page index maps
    pos = jax.lax.broadcasted_iota(jnp.int32, kp_ref.shape, 2)
    hit = pos == off_ref[pl.program_id(0)]
    ko_ref[...] = jnp.where(hit, kn_ref[...], kp_ref[...])
    vo_ref[...] = jnp.where(hit, vn_ref[...], vp_ref[...])


def paged_kv_write_pallas(
    k_new: jnp.ndarray,        # (B, K, dh)
    v_new: jnp.ndarray,        # (B, K, dh)
    k_pool: jnp.ndarray,       # (P, K, page_size, dh)
    v_pool: jnp.ndarray,       # (P, K, page_size, dh)
    page_table: jnp.ndarray,   # (B, max_pages) int32
    cache_len: jnp.ndarray,    # (B,) int32
    *,
    interpret: bool = False,
):
    """The write of :func:`paged_kv_write_ref` with the pools aliased to
    the outputs: one page per row and pool moves.  Rows may share a page
    only where nothing reads what they wrote (free slots parked on the
    null page): of two such writes one may be lost."""
    b, kvh, dh = k_new.shape
    ps = k_pool.shape[2]
    if not interpret:
        _check_page_tiling(k_pool)
    pid, off = _write_slot(page_table, cache_len, ps)
    # a unit sublane dim keeps the row block's trailing dims whole
    kn = k_new.astype(k_pool.dtype).reshape(b, kvh, 1, dh)
    vn = v_new.astype(v_pool.dtype).reshape(b, kvh, 1, dh)
    row = pl.BlockSpec((1, kvh, 1, dh), lambda bb, pid, off: (bb, 0, 0, 0))
    page = pl.BlockSpec((1, kvh, ps, dh),
                        lambda bb, pid, off: (pid[bb], 0, 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b,),
        in_specs=[row, row, page, page],
        out_specs=[page, page],
    )
    kwargs = {}
    if not interpret:
        kwargs["compiler_params"] = pltpu.CompilerParams(
            dimension_semantics=("arbitrary",))
    return pl.pallas_call(
        _kv_write_kernel,
        grid_spec=grid_spec,
        out_shape=(jax.ShapeDtypeStruct(k_pool.shape, k_pool.dtype),
                   jax.ShapeDtypeStruct(v_pool.shape, v_pool.dtype)),
        # operands count the two scalar-prefetch arrays: pools are 4, 5
        input_output_aliases={4: 0, 5: 1},
        interpret=interpret,
        name="paged_kv_write",
        **kwargs,
    )(pid, off, kn, vn, k_pool, v_pool)


# ---------------------------------------------------------------------------
# Decode: one query token per row over [0, cache_len) pool positions
# ---------------------------------------------------------------------------

def paged_attention_decode_ref(
    q: jnp.ndarray,            # (B, H, dh) — rotated query for the new token
    k_new: jnp.ndarray,        # (B, K, dh) — rotated K of the new token
    v_new: jnp.ndarray,        # (B, K, dh)
    k_pool: jnp.ndarray,       # (P, K, page_size, dh) physical pages
    v_pool: jnp.ndarray,       # (P, K, page_size, dh)
    page_table: jnp.ndarray,   # (B, max_pages) int32 pool ids
    cache_len: jnp.ndarray,    # (B,) int32 — #prior tokens (new token excluded)
    *,
    pages_per_step: int = 8,
    window: int | None = None,
) -> jnp.ndarray:
    """Non-gathering reference: page-segment ``fori_loop`` bounded by
    ``max(cache_len)``, online softmax across segments.  Returns
    (B, H, dh) fp32.  At the kernel's ``pages_per_step`` it is
    bit-comparable to the Pallas kernel (same op order per block); other
    widths stay within float rounding of it.  With ``window`` each row's
    walk starts at the page of its first visible position, as the
    kernel's does."""
    b, h, dh = q.shape
    kvh = k_new.shape[1]
    g = h // kvh
    ps = k_pool.shape[2]
    max_pages = page_table.shape[1]
    scale = 1.0 / math.sqrt(dh)
    qg = q.reshape(b, kvh, g, dh).astype(jnp.float32)
    kn = k_new.astype(jnp.float32)
    vn = v_new.astype(jnp.float32)
    clen = jnp.broadcast_to(
        jnp.asarray(cache_len, jnp.int32).reshape(-1), (b,))

    # the in-register current token seeds the state (its own score is the
    # first max, so exp(s_new - m) = 1): m = s_new, l = 1, acc = v_new —
    # the same seed the Pallas kernel uses, keeping the two bit-comparable
    s_new = jnp.sum(qg * kn[:, :, None, :], axis=-1, keepdims=True) * scale
    m0 = s_new                                              # (B,K,G,1)
    l0 = jnp.ones_like(s_new)
    acc0 = jnp.broadcast_to(vn[:, :, None, :], (b, kvh, g, dh)).astype(
        jnp.float32)

    seg = pages_per_step * ps                               # positions / step
    offs = jnp.arange(ps, dtype=jnp.int32)
    page_idx = jnp.arange(pages_per_step, dtype=jnp.int32)
    lo = (jnp.zeros_like(clen) if window is None
          else jnp.maximum(clen - window + 1, 0))           # first visible
    lo_page = lo // ps

    def body(j, carry):
        m, l, acc = carry
        idx = lo_page[:, None] + j * pages_per_step + page_idx[None]  # (B, n)
        # clip the *lookup* (labels stay logical): positions past the
        # table are masked below, never mislabeled
        pid = jnp.take_along_axis(page_table, jnp.minimum(idx, max_pages - 1),
                                  axis=1)
        kp = _segment(k_pool, pid)                          # (B,K,seg,dh)
        vp = _segment(v_pool, pid)
        pos = (idx[:, :, None] * ps + offs).reshape(b, seg)
        valid = ((pos >= lo[:, None]) & (pos < clen[:, None])
                 & (pos < max_pages * ps))
        s = jnp.einsum("bkgd,bksd->bkgs", qg, kp,
                       preferred_element_type=jnp.float32) * scale
        s = jnp.where(valid[:, None, None, :], s, NEG_INF)
        # zero masked values too: unallocated pages may hold anything
        # (NaN-poisoned in tests) and 0 · NaN = NaN in the contraction
        vp = jnp.where(valid[:, None, :, None], vp, 0.0)
        m2 = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        r = jnp.exp(m - m2)
        p = jnp.where(valid[:, None, None, :], jnp.exp(s - m2), 0.0)
        l = l * r + jnp.sum(p, axis=-1, keepdims=True)
        acc = acc * r + jnp.einsum(
            "bkgs,bksd->bkgd", p, vp, preferred_element_type=jnp.float32)
        return m2, l, acc

    n_steps = jnp.max((clen - lo_page * ps + seg - 1) // seg)
    m, l, acc = jax.lax.fori_loop(0, n_steps, body, (m0, l0, acc0))
    return (acc / l).reshape(b, h, dh)


def decode_pages_per_step(kv_heads: int, page_size: int, head_dim: int,
                          dtype, max_pages: int) -> int:
    """Pages one decode grid step fetches: the largest power of two
    <= ``max_pages`` whose double-buffered K and V blocks — four
    ``(pages, K, page_size, dh)`` buffers, lanes padded to 128 — fit
    ``DECODE_VMEM_BUDGET``.  Derived from the shapes, never configured."""
    page = (kv_heads * page_size * _cdiv(head_dim, 128) * 128
            * jnp.dtype(dtype).itemsize)
    pps = 1
    while pps * 2 <= max_pages and 4 * pps * 2 * page <= DECODE_VMEM_BUDGET:
        pps *= 2
    return pps


def decode_blocks(max_pages: int, pages_per_step: int, page_size: int,
                  window: int | None = None) -> int:
    """Grid steps per slot of the decode walk: every block of the table,
    or with ``window`` only as many as can hold the pages it sees — the
    visible cached positions ``[cache_len - W + 1, cache_len)`` span at
    most ``cdiv(W - 1, ps) + 1`` pages from the first visible one."""
    blocks = _cdiv(max_pages, pages_per_step)
    if window is None:
        return blocks
    return min(blocks, _cdiv(_cdiv(window - 1, page_size) + 1, pages_per_step))


def _decode_kernel(tbl_ref, clen_ref, q_ref, kn_ref, vn_ref, *refs,
                   page_size: int, pages_per_step: int, scale: float,
                   window: int | None = None):
    """Grid (B, page blocks); one step covers one slot, all its KV heads
    and ``pages_per_step`` logical pages, each page one ``(1, K, ps, dh)``
    pool block of its own.  Scratch m/l/acc (K, G, ·) persists across
    the block dimension: j == 0 seeds from the in-register current
    token, blocks past the live context are skipped, the last step
    normalizes into the output block.  With ``window`` block 0 starts at
    the page of the first visible position and positions before it are
    masked."""
    pps = pages_per_step
    k_refs, v_refs = refs[:pps], refs[pps:2 * pps]
    o_ref, m_ref, l_ref, acc_ref = refs[2 * pps:]
    bb = pl.program_id(0)
    j = pl.program_id(1)
    clen = clen_ref[bb]
    seg = pps * page_size
    qg = q_ref[0].astype(jnp.float32)                       # (K, G, dh)

    def first_visible():
        return jnp.maximum(clen - window + 1, 0)

    def start():                # logical position of the block's first slot
        if window is None:
            return j * seg
        return (first_visible() // page_size) * page_size + j * seg

    @pl.when(j == 0)
    def _seed():
        kn = kn_ref[0].astype(jnp.float32)                  # (K, 1, dh)
        s_new = jnp.sum(qg * kn, axis=-1, keepdims=True) * scale
        m_ref[...] = s_new                                  # (K, G, 1)
        l_ref[...] = jnp.ones_like(s_new)
        acc_ref[...] = jnp.broadcast_to(
            vn_ref[0].astype(jnp.float32), acc_ref.shape)

    @pl.when(start() < clen)
    def _block():
        kp = jnp.concatenate([r[0].astype(jnp.float32) for r in k_refs],
                             axis=1)                        # (K, seg, dh)
        vp = jnp.concatenate([r[0].astype(jnp.float32) for r in v_refs],
                             axis=1)
        s = jax.lax.dot_general(
            qg, kp, (((2,), (2,)), ((0,), (0,))),
            preferred_element_type=jnp.float32) * scale     # (K, G, seg)
        pos = start() + jax.lax.broadcasted_iota(jnp.int32, (1, 1, seg), 2)
        valid = pos < clen
        if window is not None:
            valid = valid & (pos >= first_visible())
        s = jnp.where(valid, s, NEG_INF)
        kv_pos = start() + jax.lax.broadcasted_iota(jnp.int32, (1, seg, 1), 1)
        kv_live = kv_pos < clen
        if window is not None:
            kv_live = kv_live & (kv_pos >= first_visible())
        vp = jnp.where(kv_live, vp, 0.0)
        m = m_ref[...]
        m2 = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        r = jnp.exp(m - m2)
        p = jnp.where(valid, jnp.exp(s - m2), 0.0)
        l_ref[...] = l_ref[...] * r + jnp.sum(p, axis=-1, keepdims=True)
        acc_ref[...] = acc_ref[...] * r + jax.lax.dot_general(
            p, vp, (((2,), (1,)), ((0,), (0,))),
            preferred_element_type=jnp.float32)
        m_ref[...] = m2

    @pl.when(j == pl.num_programs(1) - 1)
    def _finalize():
        o_ref[0] = acc_ref[...] / l_ref[...]


def paged_attention_decode_pallas(
    q: jnp.ndarray,            # (B, H, dh)
    k_new: jnp.ndarray,        # (B, K, dh)
    v_new: jnp.ndarray,        # (B, K, dh)
    k_pool: jnp.ndarray,       # (P, K, page_size, dh)
    v_pool: jnp.ndarray,       # (P, K, page_size, dh)
    page_table: jnp.ndarray,   # (B, max_pages) int32
    cache_len: jnp.ndarray,    # (B,) int32
    *,
    pages_per_step: int | None = None,
    interpret: bool = False,
    window: int | None = None,
) -> jnp.ndarray:
    """Decode over the page walk; ``pages_per_step`` defaults to
    :func:`decode_pages_per_step` of the shapes (tests pin it).  With
    ``window`` the walk covers only the pages the window can see."""
    b, h, dh = q.shape
    kvh = k_new.shape[1]
    g = h // kvh
    ps = k_pool.shape[2]
    max_pages = page_table.shape[1]
    scale = 1.0 / math.sqrt(dh)
    if not interpret:
        _check_page_tiling(k_pool)
    pps = pages_per_step or decode_pages_per_step(
        kvh, ps, dh, k_pool.dtype, max_pages)
    qg = q.reshape(b, kvh, g, dh)
    # a unit sublane dim keeps every block's trailing dims whole
    kn = k_new.reshape(b, kvh, 1, dh)
    vn = v_new.reshape(b, kvh, 1, dh)
    clen = jnp.broadcast_to(
        jnp.asarray(cache_len, jnp.int32).reshape(-1), (b,))

    blocks = decode_blocks(max_pages, pps, ps, window)

    def page_map(i):
        def index(bb, j, tbl, cl):
            # clamp pages past the live context to the last live page: a
            # repeated block index skips the DMA, so traffic is
            # O(cache_len) though the grid walks every block
            live = (cl[bb] + ps - 1) // ps
            page = j * pps + i
            if window is not None:       # from the first visible page
                page = jnp.maximum(cl[bb] - window + 1, 0) // ps + page
            jj = jnp.minimum(page, jnp.maximum(live - 1, 0))
            return (tbl[bb, jj], 0, 0, 0)
        return index

    row_map = lambda bb, j, tbl, cl: (bb, 0, 0, 0)  # noqa: E731
    pages = [pl.BlockSpec((1, kvh, ps, dh), page_map(i)) for i in range(pps)]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, blocks),
        in_specs=[
            pl.BlockSpec((1, kvh, g, dh), row_map),
            pl.BlockSpec((1, kvh, 1, dh), row_map),
            pl.BlockSpec((1, kvh, 1, dh), row_map),
            *pages,                               # K, one per page slot
            *pages,                               # V
        ],
        out_specs=pl.BlockSpec((1, kvh, g, dh), row_map),
        scratch_shapes=[
            pltpu.VMEM((kvh, g, 1), jnp.float32),     # running max m
            pltpu.VMEM((kvh, g, 1), jnp.float32),     # running normalizer l
            pltpu.VMEM((kvh, g, dh), jnp.float32),    # fp32 accumulator
        ],
    )
    kwargs = {}
    if not interpret:
        kwargs["compiler_params"] = pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
        )
    out = pl.pallas_call(
        functools.partial(_decode_kernel, page_size=ps, pages_per_step=pps,
                          scale=scale, window=window),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, kvh, g, dh), jnp.float32),
        interpret=interpret,
        name=("paged_attention_decode" if window is None
              else "paged_attention_decode_window"),
        **kwargs,
    )(page_table, clen, qg, kn, vn, *(k_pool,) * pps, *(v_pool,) * pps)
    return out.reshape(b, h, dh)


# ---------------------------------------------------------------------------
# Prefill: bm-tiled query blocks over the same page walk, causal mask
# ---------------------------------------------------------------------------

def paged_attention_prefill_ref(
    q: jnp.ndarray,            # (B, S, H, dh) — rotated, pos [q_offset, q_offset+S)
    k_pool: jnp.ndarray,       # (P, K, page_size, dh) — prompt K/V scattered in
    v_pool: jnp.ndarray,
    page_table: jnp.ndarray,   # (B, max_pages) int32
    lengths: jnp.ndarray,      # (B,) int32 — per-row TOTAL length (<= q_offset+S)
    *,
    pages_per_step: int = 8,
    q_offset: int = 0,
    window: int | None = None,
) -> jnp.ndarray:
    """Causal paged prefill reference: same page-segment walk as decode,
    vectorized over all S query rows.  With ``q_offset`` (static) the
    queries sit at logical positions ``[q_offset, q_offset+S)`` and the
    walk covers every page from logical position 0 — the tail-only
    prefill of a request whose first ``q_offset`` tokens are already
    cached in shared prefix pages (DESIGN.md §12).  ``lengths`` is the
    per-row *total* context (prefix + tail); rows at/past their length
    get zero output.  ``window`` adds the sliding-window mask.  Returns
    (B, S, H, dh) fp32."""
    b, s, h, dh = q.shape
    kvh = k_pool.shape[1]
    g = h // kvh
    ps = k_pool.shape[2]
    max_pages = page_table.shape[1]
    scale = 1.0 / math.sqrt(dh)
    qg = q.reshape(b, s, kvh, g, dh).transpose(0, 2, 3, 1, 4).astype(
        jnp.float32)                                        # (B,K,G,S,dh)
    ln = jnp.broadcast_to(jnp.asarray(lengths, jnp.int32).reshape(-1), (b,))

    m0 = jnp.full((b, kvh, g, s, 1), NEG_INF, jnp.float32)
    l0 = jnp.zeros((b, kvh, g, s, 1), jnp.float32)
    acc0 = jnp.zeros((b, kvh, g, s, dh), jnp.float32)
    qpos = q_offset + jnp.arange(s, dtype=jnp.int32)
    seg = pages_per_step * ps
    offs = jnp.arange(ps, dtype=jnp.int32)
    page_idx = jnp.arange(pages_per_step, dtype=jnp.int32)

    def body(j, carry):
        m, l, acc = carry
        idx = j * pages_per_step + page_idx
        pid = jnp.take(page_table, jnp.minimum(idx, max_pages - 1), axis=1)
        kp = _segment(k_pool, pid)                          # (B,K,seg,dh)
        vp = _segment(v_pool, pid)
        kvpos = (idx[:, None] * ps + offs[None, :]).reshape(seg)
        # (B, S, seg): causal x per-row length, labels stay logical
        valid = ((kvpos[None, None, :] <= qpos[None, :, None])
                 & (kvpos[None, None, :] < ln[:, None, None])
                 & (qpos[None, :, None] < ln[:, None, None]))
        if window is not None:
            valid = valid & (kvpos[None, None, :]
                             > qpos[None, :, None] - window)
        kv_live = kvpos[None, :] < ln[:, None]              # (B, seg)
        sb = jnp.einsum("bkgqd,bksd->bkgqs", qg, kp,
                        preferred_element_type=jnp.float32) * scale
        sb = jnp.where(valid[:, None, None], sb, NEG_INF)
        vp = jnp.where(kv_live[:, None, :, None], vp, 0.0)
        m2 = jnp.maximum(m, jnp.max(sb, axis=-1, keepdims=True))
        r = jnp.exp(m - m2)
        p = jnp.where(valid[:, None, None], jnp.exp(sb - m2), 0.0)
        l = l * r + jnp.sum(p, axis=-1, keepdims=True)
        acc = acc * r + jnp.einsum("bkgqs,bksd->bkgqd", p, vp,
                                   preferred_element_type=jnp.float32)
        return m2, l, acc

    n_steps = _cdiv(_cdiv(q_offset + s, ps), pages_per_step)
    m, l, acc = jax.lax.fori_loop(0, n_steps, body, (m0, l0, acc0))
    out = acc / jnp.where(l == 0.0, 1.0, l)                 # dead rows -> 0
    return out.transpose(0, 3, 1, 2, 4).reshape(b, s, h, dh)


def prefill_pages_per_step(page_size: int, pages: int) -> int:
    """Pages one prefill grid step scores: as many as fill
    ``PREFILL_KEYS`` key positions (one 128-lane row of scores, the
    MXU's width), at most the ``pages`` a query tile walks.  Derived from
    the shapes, never configured."""
    return max(1, min(PREFILL_KEYS // page_size, pages))


def _first_prefill_page(i, block_q: int, page_size: int, q_offset: int,
                        window: int | None):
    """The first page query tile ``i`` walks: 0, or with a window the page
    of the first key its earliest query sees."""
    if window is None:
        return 0
    return jnp.maximum(q_offset + i * block_q - window + 1, 0) // page_size


def _prefill_kernel(tbl_ref, len_ref, q_ref, *refs, page_size: int,
                    pages_per_step: int, block_q: int, group: int,
                    scale: float, q_offset: int, window: int | None = None):
    """Grid (B, K, q_tiles, page blocks), blocks innermost.  One step
    scores a query tile against ``pages_per_step`` logical pages, each
    page one ``(1, 1, ps, dh)`` pool block of its own.  Query rows arrive
    laid out (bm·G, dh) so one dot covers the whole GQA group; the causal
    mask is built from 2D iotas (qpos = q_offset + row // G, kvpos =
    block offset) — ``q_offset`` shifts every query to its logical
    position for tail-only prefill over shared prefix pages (DESIGN.md
    §12).  With ``window`` the tile's walk starts at its first visible
    page and keys at or before qpos - window are masked."""
    pps = pages_per_step
    k_refs, v_refs = refs[:pps], refs[pps:2 * pps]
    o_ref, m_ref, l_ref, acc_ref = refs[2 * pps:]
    bb = pl.program_id(0)
    i = pl.program_id(2)
    j = pl.program_id(3)
    ln = len_ref[bb]
    seg = pps * page_size
    # the logical position of the block's first key
    start = (_first_prefill_page(i, block_q, page_size, q_offset, window)
             * page_size + j * seg)

    @pl.when(j == 0)
    def _seed():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # keys needed by this q tile: kvpos <= qpos < min(len, q_offset+(i+1)·bm)
    qhi = jnp.minimum(ln, q_offset + (i + 1) * block_q)

    @pl.when(start < qhi)
    def _block():
        qg = q_ref[0, 0].astype(jnp.float32)                # (bm·G, dh)
        kp = jnp.concatenate([r[0, 0].astype(jnp.float32) for r in k_refs],
                             axis=0)                        # (seg, dh)
        vp = jnp.concatenate([r[0, 0].astype(jnp.float32) for r in v_refs],
                             axis=0)
        sb = jax.lax.dot_general(
            qg, kp, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale     # (bm·G, seg)
        shp = (block_q * group, seg)
        row = jax.lax.broadcasted_iota(jnp.int32, shp, 0)
        qpos = q_offset + i * block_q + (row // group if group > 1 else row)
        kvpos = start + jax.lax.broadcasted_iota(jnp.int32, shp, 1)
        valid = (kvpos <= qpos) & (kvpos < ln) & (qpos < ln)
        if window is not None:
            valid = valid & (kvpos > qpos - window)
        sb = jnp.where(valid, sb, NEG_INF)
        kv_live = (start + jax.lax.broadcasted_iota(
            jnp.int32, (seg, 1), 0)) < ln
        vp = jnp.where(kv_live, vp, 0.0)
        m = m_ref[...]
        m2 = jnp.maximum(m, jnp.max(sb, axis=-1, keepdims=True))
        r = jnp.exp(m - m2)
        p = jnp.where(valid, jnp.exp(sb - m2), 0.0)
        l_ref[...] = l_ref[...] * r + jnp.sum(p, axis=-1, keepdims=True)
        acc_ref[...] = acc_ref[...] * r + jnp.dot(
            p, vp, preferred_element_type=jnp.float32)
        m_ref[...] = m2

    @pl.when(j == pl.num_programs(3) - 1)
    def _finalize():
        l = l_ref[...]
        o_ref[0, 0] = acc_ref[...] / jnp.where(l == 0.0, 1.0, l)


def paged_attention_prefill_pallas(
    q: jnp.ndarray,            # (B, S, H, dh)
    k_pool: jnp.ndarray,       # (P, K, page_size, dh)
    v_pool: jnp.ndarray,
    page_table: jnp.ndarray,   # (B, max_pages) int32
    lengths: jnp.ndarray,      # (B,) int32
    *,
    bm: int = 64,
    pages_per_step: int | None = None,
    interpret: bool = False,
    q_offset: int = 0,
    window: int | None = None,
) -> jnp.ndarray:
    """Prefill over the page walk; ``pages_per_step`` defaults to
    :func:`prefill_pages_per_step` of the shapes (tests pin it)."""
    b, s, h, dh = q.shape
    kvh = k_pool.shape[1]
    g = h // kvh
    ps = k_pool.shape[2]
    scale = 1.0 / math.sqrt(dh)
    if not interpret:
        _check_page_tiling(k_pool)
    bm = min(bm, s, max(8, PREFILL_TILE_ROWS // g))
    s_pad = _cdiv(s, bm) * bm
    n_qt = s_pad // bm
    n_pg = _cdiv(q_offset + s, ps)                          # context pages only
    if window is not None:
        # a tile's keys span [first query - W + 1, last query]
        n_pg = min(n_pg, _cdiv(bm + window - 1, ps) + 1)
    pps = pages_per_step or prefill_pages_per_step(ps, n_pg)
    ln = jnp.broadcast_to(jnp.asarray(lengths, jnp.int32).reshape(-1), (b,))

    qt = q.reshape(b, s, kvh, g, dh).transpose(0, 2, 1, 3, 4)  # (B,K,S,G,dh)
    if s_pad != s:
        qt = jnp.pad(qt, ((0, 0), (0, 0), (0, s_pad - s), (0, 0), (0, 0)))
    qt = qt.reshape(b, kvh, s_pad * g, dh)           # GQA group rows adjacent

    def pool_map(bb, k, i, j, tbl, cl, *, p):
        # page slot p of the block; pages past the tile's live keys clamp
        # to its last live page: a repeated block index skips the DMA
        live = (jnp.minimum(cl[bb], q_offset + (i + 1) * bm) + ps - 1) // ps
        page = _first_prefill_page(i, bm, ps, q_offset, window) + j * pps + p
        jj = jnp.minimum(page, jnp.maximum(live - 1, 0))
        return (tbl[bb, jj], k, 0, 0)

    q_map = lambda bb, k, i, j, tbl, cl: (bb, k, i, 0)  # noqa: E731
    pages = [pl.BlockSpec((1, 1, ps, dh), functools.partial(pool_map, p=p))
             for p in range(pps)]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, kvh, n_qt, _cdiv(n_pg, pps)),
        in_specs=[
            pl.BlockSpec((1, 1, bm * g, dh), q_map),
            *pages,                               # K, one per page slot
            *pages,                               # V
        ],
        out_specs=pl.BlockSpec((1, 1, bm * g, dh), q_map),
        scratch_shapes=[
            pltpu.VMEM((bm * g, 1), jnp.float32),
            pltpu.VMEM((bm * g, 1), jnp.float32),
            pltpu.VMEM((bm * g, dh), jnp.float32),
        ],
    )
    kwargs = {}
    if not interpret:
        kwargs["compiler_params"] = pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary"),
        )
    out = pl.pallas_call(
        functools.partial(_prefill_kernel, page_size=ps, pages_per_step=pps,
                          block_q=bm, group=g, scale=scale,
                          q_offset=q_offset, window=window),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, kvh, s_pad * g, dh), jnp.float32),
        interpret=interpret,
        name=("paged_attention_prefill" if window is None
              else "paged_attention_prefill_window"),
        **kwargs,
    )(page_table, ln, qt, *(k_pool,) * pps, *(v_pool,) * pps)
    out = out.reshape(b, kvh, s_pad, g, dh)[:, :, :s]
    return out.transpose(0, 2, 1, 3, 4).reshape(b, s, h, dh)
