"""Fused paged-attention kernels (kernels/paged_attention.py, DESIGN.md §11).

Three-way differential coverage: the Pallas kernel body (interpret mode,
decode M=1 and prefill bm-tiled grids) against the non-gathering ref,
the ref against a dense gather oracle, and the ``attention_decode`` /
``attention_prefill`` fused dispatch against the legacy gather path —
across ragged ``(B,)`` cache_len (including empty rows parked on the
null page), GQA ratios, and page sizes 4/8/16.

The ref mirrors the kernel's op sequence exactly at the same
``pages_per_step`` (same seed, same per-block update order), so
kernel-vs-ref agreement is at float32 rounding (1–2 ulp from einsum
batching), not accumulated drift.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import paged_attention_decode, paged_attention_prefill
from repro.kernels.paged_attention import (
    DECODE_VMEM_BUDGET,
    decode_blocks,
    decode_pages_per_step,
    paged_attention_decode_pallas,
    paged_attention_decode_ref,
    paged_attention_prefill_pallas,
    paged_attention_prefill_ref,
    prefill_pages_per_step,
)
from repro.models.attention import attention_decode, attention_init, full_attention

ATOL = 2e-6


def _mk_decode(rng, b, h, kvh, dh, ps, max_pages, clens, poison=False):
    """Random decode case: shuffled non-null page ids per live row; rows
    with cache_len 0 park their whole table on the null page.  With
    ``poison`` every slot not owned by a live row is NaN."""
    n_pages = b * max_pages + 1
    q = jnp.asarray(rng.normal(size=(b, h, dh)), jnp.float32)
    kn = jnp.asarray(rng.normal(size=(b, kvh, dh)), jnp.float32)
    vn = jnp.asarray(rng.normal(size=(b, kvh, dh)), jnp.float32)
    clens = np.asarray(clens)
    ids = rng.permutation(np.arange(1, n_pages))[: b * max_pages]
    tbl = np.where(clens[:, None] == 0, 0, ids.reshape(b, max_pages))
    if poison:
        kp = np.full((n_pages, kvh, ps, dh), np.nan, np.float32)
        vp = np.full((n_pages, kvh, ps, dh), np.nan, np.float32)
        for r in range(b):                    # only live positions are real
            for t in range(int(clens[r])):
                kp[tbl[r, t // ps], :, t % ps] = rng.normal(size=(kvh, dh))
                vp[tbl[r, t // ps], :, t % ps] = rng.normal(size=(kvh, dh))
        kp, vp = jnp.asarray(kp), jnp.asarray(vp)
    else:
        kp = jnp.asarray(rng.normal(size=(n_pages, kvh, ps, dh)), jnp.float32)
        vp = jnp.asarray(rng.normal(size=(n_pages, kvh, ps, dh)), jnp.float32)
    return (q, kn, vn, kp, vp, jnp.asarray(tbl, jnp.int32),
            jnp.asarray(clens, jnp.int32))


def _decode_oracle(q, kn, vn, kp, vp, tbl, clen, window=None):
    """Dense gather + monolithic softmax, the new token appended at its
    row's cache_len — the legacy view the kernel must reproduce.  With
    ``window`` a row at cache_len c sees positions after c - window."""
    b, h, dh = q.shape
    kvh = kn.shape[1]
    g = h // kvh
    ps = kp.shape[2]
    s_max = tbl.shape[1] * ps
    ck = np.array(kp[tbl].transpose(0, 1, 3, 2, 4).reshape(b, s_max, kvh, dh))
    cv = np.array(vp[tbl].transpose(0, 1, 3, 2, 4).reshape(b, s_max, kvh, dh))
    for r in range(b):
        c = int(clen[r])
        ck[r, c] = np.asarray(kn[r])
        cv[r, c] = np.asarray(vn[r])
    qg = np.asarray(q).reshape(b, kvh, g, dh)
    s = np.einsum("bkgd,bskd->bkgs", qg, ck) / np.sqrt(dh)
    valid = np.arange(s_max)[None] <= np.asarray(clen)[:, None]
    if window is not None:
        valid &= np.arange(s_max)[None] > np.asarray(clen)[:, None] - window
    s = np.where(valid[:, None, None], s, -1e30)
    w = np.exp(s - s.max(-1, keepdims=True))
    w /= w.sum(-1, keepdims=True)
    cv = np.where(valid[:, :, None, None], cv, 0)
    return np.einsum("bkgs,bskd->bkgd", w, cv).reshape(b, h, dh)


def _decode_cases():
    """(h, kvh, ps, pages_per_step, pool dtype): every pinned width and
    the derived one (None) on fp32 pools, the derived width and 2 on
    bf16 pools.  The derived-width fp32 cases keep their plain
    ``h-kvh-ps`` ids."""
    for h, kvh in [(4, 4), (8, 2), (4, 1)]:
        for ps in (4, 8, 16):
            for pps, dtype in [(None, "float32"), (1, "float32"),
                               (2, "float32"), (4, "float32"),
                               (None, "bfloat16"), (2, "bfloat16")]:
                tag = ("" if pps is None and dtype == "float32"
                       else f"-pps{pps or 'auto'}-{dtype}")
                yield pytest.param(h, kvh, ps, pps, dtype,
                                   id=f"{h}-{kvh}-{ps}{tag}")


@pytest.mark.parametrize("h,kvh,ps,pps,pool_dtype", _decode_cases())
def test_paged_decode_kernel_interpret_matches_ref(h, kvh, ps, pps,
                                                   pool_dtype):
    """Decode-grid kernel body under the interpreter vs the ref at the
    same page-block width: same op order, float-rounding agreement,
    across ragged cache_len including an empty row on the null page and
    a full table (max_pages = 5 is no multiple of 2 or 4)."""
    rng = np.random.default_rng(ps * 10 + h)
    b, dh, mp = 4, 32, 5
    clens = [0, 1, ps * mp - 1, int(rng.integers(1, ps * mp - 1))]
    q, kn, vn, kp, vp, tbl, clen = _mk_decode(rng, b, h, kvh, dh, ps, mp,
                                              clens)
    kp, vp = kp.astype(pool_dtype), vp.astype(pool_dtype)
    args = (q, kn, vn, kp, vp, tbl, clen)
    width = pps or decode_pages_per_step(kvh, ps, dh, kp.dtype, mp)
    ref = paged_attention_decode_ref(*args, pages_per_step=width)
    ker = paged_attention_decode_pallas(*args, pages_per_step=pps,
                                        interpret=True)
    np.testing.assert_allclose(np.asarray(ker), np.asarray(ref), atol=ATOL)
    orc = _decode_oracle(q, kn, vn, kp.astype(jnp.float32),
                         vp.astype(jnp.float32), tbl, clen)
    np.testing.assert_allclose(np.asarray(ref), orc, atol=1e-5)


def test_paged_decode_ref_segment_width_invariant():
    """The ref's pages_per_step is a CPU throughput knob, not semantics:
    any width agrees with the per-page walk to float rounding."""
    rng = np.random.default_rng(3)
    args = _mk_decode(rng, 3, 8, 4, 64, 8, 6, [0, 17, 47])
    base = paged_attention_decode_ref(*args, pages_per_step=1)
    for pps in (2, 4, 8):
        got = paged_attention_decode_ref(*args, pages_per_step=pps)
        np.testing.assert_allclose(np.asarray(got), np.asarray(base),
                                   atol=ATOL)


def test_paged_decode_never_reads_unallocated_pages():
    """NaN-poison every slot outside the live prefix of each row's own
    pages (including the whole null page): outputs must be finite and
    bit-identical to the clean-pool run on ref AND interpret kernel —
    the kernel at its derived page-block width (all 4 pages) and at 2,
    where a block's page slots past the context hold the last live page."""
    rng = np.random.default_rng(5)
    b, h, kvh, dh, ps, mp = 3, 8, 4, 32, 4, 4
    clens = [0, 5, 13]
    dirty = _mk_decode(np.random.default_rng(5), b, h, kvh, dh, ps, mp,
                       clens, poison=True)
    # clean pool: identical live data, zeros elsewhere
    clean = tuple(jnp.nan_to_num(a, nan=0.0) if a.ndim == 4 else a
                  for a in dirty)
    for fn in (lambda *a: paged_attention_decode_ref(*a, pages_per_step=2),
               lambda *a: paged_attention_decode_pallas(*a, interpret=True),
               lambda *a: paged_attention_decode_pallas(
                   *a, pages_per_step=2, interpret=True)):
        got = np.asarray(fn(*dirty))
        assert np.isfinite(got).all()
        np.testing.assert_array_equal(got, np.asarray(fn(*clean)))


@pytest.mark.parametrize("kvh,ps,dtype,mp,want", [
    (16, 16, "float32", 40, 8),      # the decode cells: qwen1.5-0.5b
    (16, 8, "float32", 128, 16),     # the described-v5e compile shapes
    (16, 16, "float32", 64, 8),
    (16, 16, "bfloat16", 64, 16),
    (8, 8, "float32", 128, 32),
    (8, 16, "float32", 64, 16),
    (8, 16, "bfloat16", 64, 32),
    (16, 16, "float32", 3, 2),       # capped by the table width
    (64, 64, "float32", 40, 1),      # one page overfills the budget
])
def test_decode_pages_per_step_fits_its_vmem_budget(kvh, ps, dtype, mp,
                                                    want):
    """The derived page-block width is the largest power of two within
    the table whose four double-buffered K/V blocks, lanes padded to
    128, fit the budget (dh 64 pads to 128)."""
    pps = decode_pages_per_step(kvh, ps, 64, dtype, mp)
    assert pps == want
    page = kvh * ps * 128 * jnp.dtype(dtype).itemsize
    assert pps & (pps - 1) == 0 and 1 <= pps <= max(mp, 1)
    assert pps == 1 or 4 * pps * page <= DECODE_VMEM_BUDGET
    assert 2 * pps > mp or 4 * 2 * pps * page > DECODE_VMEM_BUDGET


def test_paged_decode_ops_mode_dispatch():
    rng = np.random.default_rng(7)
    args = _mk_decode(rng, 2, 4, 2, 16, 8, 3, [0, 11])
    ref = paged_attention_decode(*args, mode="ref")
    itp = paged_attention_decode(*args, mode="interpret")
    auto = paged_attention_decode(*args, mode="auto")   # CPU host -> ref
    np.testing.assert_allclose(np.asarray(itp), np.asarray(ref), atol=ATOL)
    np.testing.assert_array_equal(np.asarray(auto), np.asarray(ref))
    with pytest.raises(ValueError, match="unknown kernel mode"):
        paged_attention_decode(*args, mode="bogus")


def test_attention_decode_fused_matches_gather_impl():
    """The dispatch-level contract: attention_decode with the fused page
    walk == the legacy gather view, per row, over ragged cache_len —
    including the cache writes (shared between impls)."""
    rng = np.random.default_rng(11)
    b, ps, mp, kvh, h, dh, d = 3, 4, 4, 2, 4, 16, 64
    key = jax.random.PRNGKey(0)
    p = attention_init(key, d, h, kvh, dh)
    x = jax.random.normal(jax.random.fold_in(key, 1), (b, 1, d))
    n_pages = b * mp + 1
    pool = {
        "k": jnp.asarray(rng.normal(size=(n_pages, kvh, ps, dh)), jnp.float32),
        "v": jnp.asarray(rng.normal(size=(n_pages, kvh, ps, dh)), jnp.float32),
    }
    tables = jnp.asarray(
        rng.permutation(np.arange(1, n_pages))[: b * mp].reshape(b, mp),
        jnp.int32)
    clen = jnp.asarray([0, 7, 14], jnp.int32)
    out_f, cf = attention_decode(p, x, dict(pool), clen, num_heads=h,
                                 kv_heads=kvh, head_dim=dh,
                                 page_table=tables, paged_impl="fused")
    out_g, cg = attention_decode(p, x, dict(pool), clen, num_heads=h,
                                 kv_heads=kvh, head_dim=dh,
                                 page_table=tables, paged_impl="gather")
    np.testing.assert_allclose(np.asarray(out_f), np.asarray(out_g),
                               atol=1e-5)
    np.testing.assert_array_equal(np.asarray(cf["k"]), np.asarray(cg["k"]))
    np.testing.assert_array_equal(np.asarray(cf["v"]), np.asarray(cg["v"]))


# ---------------------------------------------------------------------------
# Prefill
# ---------------------------------------------------------------------------

def _mk_prefill(rng, b, s, h, kvh, dh, ps):
    """Prompt K/V scattered into shuffled pages; everything the scatter
    didn't touch stays NaN, so any stray read is loud."""
    mp = -(-s // ps)
    n_pages = b * mp + 1
    q = jnp.asarray(rng.normal(size=(b, s, h, dh)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(b, s, kvh, dh)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(b, s, kvh, dh)), jnp.float32)
    tbl = jnp.asarray(
        rng.permutation(np.arange(1, n_pages)).reshape(b, mp), jnp.int32)
    kp = jnp.full((n_pages, kvh, ps, dh), jnp.nan, jnp.float32)
    vp = jnp.full((n_pages, kvh, ps, dh), jnp.nan, jnp.float32)
    t = jnp.arange(s)
    pid = tbl[:, t // ps]
    off = jnp.broadcast_to(t % ps, (b, s))
    kp = kp.at[pid, :, off].set(k)
    vp = vp.at[pid, :, off].set(v)
    return q, k, v, kp, vp, tbl


@pytest.mark.parametrize("ps", [4, 8, 16])
@pytest.mark.parametrize("h,kvh,bm", [(4, 4, 32), (8, 2, 64), (4, 1, 16)])
def test_paged_prefill_kernel_interpret_matches_ref_m64(ps, h, kvh, bm):
    """Prefill-grid kernel (bm-tiled query blocks, M=64) vs ref vs the
    unchunked causal oracle; the NaN pool padding proves the page walk
    stays inside the prompt's own pages."""
    rng = np.random.default_rng(ps + h + bm)
    b, s, dh = 2, 64, 32
    q, k, v, kp, vp, tbl = _mk_prefill(rng, b, s, h, kvh, dh, ps)
    lengths = jnp.full((b,), s, jnp.int32)
    ref = paged_attention_prefill_ref(q, kp, vp, tbl, lengths,
                                      pages_per_step=1)
    ker = paged_attention_prefill_pallas(q, kp, vp, tbl, lengths, bm=bm,
                                         interpret=True)
    assert np.isfinite(np.asarray(ker)).all()
    np.testing.assert_allclose(np.asarray(ker), np.asarray(ref), atol=ATOL)
    orc = full_attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(ref), np.asarray(orc), atol=1e-5)


def test_paged_prefill_ragged_lengths_and_odd_sizes():
    """Per-row lengths: rows at/past their length produce zeros, live
    rows match the oracle restricted to their prefix; S not divisible by
    bm or page_size exercises the padded tail tiles."""
    rng = np.random.default_rng(17)
    b, s, h, kvh, dh, ps = 3, 50, 4, 2, 16, 8
    q, k, v, kp, vp, tbl = _mk_prefill(rng, b, s, h, kvh, dh, ps)
    lengths = jnp.asarray([0, 23, 50], jnp.int32)
    ref = paged_attention_prefill_ref(q, kp, vp, tbl, lengths,
                                      pages_per_step=2)
    ker = paged_attention_prefill_pallas(q, kp, vp, tbl, lengths, bm=16,
                                         interpret=True)
    np.testing.assert_allclose(np.asarray(ker), np.asarray(ref), atol=ATOL)
    orc = np.asarray(full_attention(q, k, v, causal=True))
    got = np.asarray(ref)
    for r, ln in enumerate([0, 23, 50]):
        np.testing.assert_allclose(got[r, :ln], orc[r, :ln], atol=1e-5)
        np.testing.assert_array_equal(got[r, ln:],
                                      np.zeros_like(got[r, ln:]))


def test_paged_prefill_ops_mode_dispatch():
    rng = np.random.default_rng(19)
    q, k, v, kp, vp, tbl = _mk_prefill(rng, 2, 32, 4, 2, 8, 8)
    lengths = jnp.full((2,), 32, jnp.int32)
    ref = paged_attention_prefill(q, kp, vp, tbl, lengths, mode="ref")
    itp = paged_attention_prefill(q, kp, vp, tbl, lengths, mode="interpret",
                                  bm=16)
    np.testing.assert_allclose(np.asarray(itp), np.asarray(ref), atol=ATOL)


@pytest.mark.parametrize("ps,start", [(4, 4), (8, 24), (16, 16)])
def test_paged_prefill_q_offset_tail_matches_full(ps, start):
    """Tail-only prefill (DESIGN.md §12 prefix caching): queries for
    positions [start, s) against pages holding the FULL prompt's K/V
    must reproduce rows [start:] of the full-prompt prefill — the walk
    covers the cached-prefix pages the tail queries attend over, the
    causal mask uses absolute positions, and the NaN padding past the
    prompt stays unread."""
    rng = np.random.default_rng(23 + ps)
    b, s, h, kvh, dh = 2, 48, 4, 2, 16
    q, k, v, kp, vp, tbl = _mk_prefill(rng, b, s, h, kvh, dh, ps)
    lengths = jnp.full((b,), s, jnp.int32)   # total lengths incl. prefix
    full = paged_attention_prefill_ref(q, kp, vp, tbl, lengths,
                                       pages_per_step=2)
    tail = paged_attention_prefill_ref(q[:, start:], kp, vp, tbl, lengths,
                                       pages_per_step=2, q_offset=start)
    np.testing.assert_allclose(np.asarray(tail),
                               np.asarray(full)[:, start:], atol=ATOL)
    ker = paged_attention_prefill_pallas(q[:, start:], kp, vp, tbl, lengths,
                                         bm=16, interpret=True,
                                         q_offset=start)
    assert np.isfinite(np.asarray(ker)).all()
    np.testing.assert_allclose(np.asarray(ker), np.asarray(tail), atol=ATOL)
    orc = full_attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(tail),
                               np.asarray(orc)[:, start:], atol=1e-5)


def test_paged_prefill_q_offset_ragged_and_ops_dispatch():
    """q_offset composed with per-row lengths: a row whose total length
    ends mid-tail zeroes its out-of-range rows, and the ops-layer
    dispatch threads q_offset to both impls."""
    rng = np.random.default_rng(29)
    b, s, h, kvh, dh, ps, start = 2, 40, 4, 2, 16, 8, 16
    q, k, v, kp, vp, tbl = _mk_prefill(rng, b, s, h, kvh, dh, ps)
    lengths = jnp.asarray([40, 25], jnp.int32)
    full = paged_attention_prefill_ref(q, kp, vp, tbl, lengths,
                                       pages_per_step=1)
    ref = paged_attention_prefill(q[:, start:], kp, vp, tbl, lengths,
                                  mode="ref", q_offset=start)
    itp = paged_attention_prefill(q[:, start:], kp, vp, tbl, lengths,
                                  mode="interpret", bm=16, q_offset=start)
    got = np.asarray(ref)
    np.testing.assert_allclose(got, np.asarray(full)[:, start:], atol=ATOL)
    np.testing.assert_allclose(np.asarray(itp), got, atol=ATOL)
    assert np.isfinite(got).all()
    np.testing.assert_array_equal(got[1, 25 - start:],
                                  np.zeros_like(got[1, 25 - start:]))


# ---------------------------------------------------------------------------
# Sliding windows
# ---------------------------------------------------------------------------

# (ps, window, pages_per_step): a window smaller than a page, windows that
# are no multiple of pps * ps, and one spanning several blocks
WINDOW_CASES = [(8, 5, 2), (4, 13, 2), (4, 6, 4), (8, 20, 1), (16, 40, 2)]


@pytest.mark.parametrize("ps,window,pps", WINDOW_CASES,
                         ids=[f"ps{p}-w{w}-pps{n}" for p, w, n in WINDOW_CASES])
def test_paged_decode_window_kernel_matches_ref_and_oracle(ps, window, pps):
    """Windowed decode: kernel (interpret) vs ref at the same block width
    vs the dense oracle with the sliding mask, for contexts below, at and
    past the window (up to 3x), an empty row included.  Every position
    outside a row's window or context is NaN-poisoned: the partial first
    page is masked and no page behind the window leaks into the result."""
    rng = np.random.default_rng(ps * 100 + window)
    b, h, kvh, dh = 7, 8, 2, 16
    mp = -(-(3 * window + 1) // ps)
    clens = [0, window - 2 if window > 2 else 1, window - 1, window,
             window + 1, 2 * window + 3, 3 * window]
    q, kn, vn, kp, vp, tbl, clen = _mk_decode(rng, b, h, kvh, dh, ps, mp,
                                              clens)
    kp, vp = np.array(kp), np.array(vp)
    tb = np.asarray(tbl)
    for r, c in enumerate(clens):
        for t in range(max(c - window + 1, 0)):   # behind the window
            kp[tb[r, t // ps], :, t % ps] = np.nan
            vp[tb[r, t // ps], :, t % ps] = np.nan
    args = (q, kn, vn, jnp.asarray(kp), jnp.asarray(vp), tbl, clen)
    ref = paged_attention_decode_ref(*args, pages_per_step=pps, window=window)
    ker = paged_attention_decode_pallas(*args, pages_per_step=pps,
                                        interpret=True, window=window)
    assert np.isfinite(np.asarray(ker)).all()
    np.testing.assert_allclose(np.asarray(ker), np.asarray(ref), atol=ATOL)
    clean = [np.nan_to_num(a) for a in (kp, vp)]
    orc = _decode_oracle(q, kn, vn, jnp.asarray(clean[0]),
                         jnp.asarray(clean[1]), tbl, clen, window=window)
    np.testing.assert_allclose(np.asarray(ref), orc, atol=1e-5)


@pytest.mark.parametrize("mp,pps,ps,window,want", [
    (192, 32, 16, 1024, 3),      # the mellum2 cell: 65 pages, 3 blocks
    (192, 32, 16, None, 6),      # its full layers walk the whole table
    (40, 8, 16, None, 5),        # the qwen decode cells, unchanged
    (8, 2, 8, 5, 1),             # a window inside one page
    (8, 2, 4, 13, 2),            # 4 visible pages + a partial one
    (3, 4, 16, 1024, 1),         # capped by the table
])
def test_decode_blocks_bounded_by_the_window(mp, pps, ps, window, want):
    assert decode_blocks(mp, pps, ps, window) == want


@pytest.mark.parametrize("ps,window,bm,start", [
    (8, 5, 16, 0), (4, 13, 16, 0), (8, 20, 32, 0), (4, 13, 16, 24),
    (16, 24, 16, 16)])
def test_paged_prefill_window_kernel_matches_ref_and_oracle(ps, window, bm,
                                                            start):
    """Windowed prefill: kernel (interpret) vs ref vs the unchunked
    sliding-window oracle, prompt ~3x the window, whole and as a tail
    over cached pages (``q_offset``); a query tile starts its walk at the
    first page its earliest query sees."""
    rng = np.random.default_rng(ps + window + bm + start)
    b, s, h, kvh, dh = 2, 3 * window + 5, 8, 2, 16
    q, k, v, kp, vp, tbl = _mk_prefill(rng, b, s, h, kvh, dh, ps)
    lengths = jnp.full((b,), s, jnp.int32)
    ref = paged_attention_prefill_ref(q[:, start:], kp, vp, tbl, lengths,
                                      pages_per_step=2, q_offset=start,
                                      window=window)
    ker = paged_attention_prefill_pallas(q[:, start:], kp, vp, tbl, lengths,
                                         bm=bm, interpret=True,
                                         q_offset=start, window=window)
    assert np.isfinite(np.asarray(ker)).all()
    np.testing.assert_allclose(np.asarray(ker), np.asarray(ref), atol=ATOL)
    orc = full_attention(q, k, v, causal=True, window=window)
    np.testing.assert_allclose(np.asarray(ref), np.asarray(orc)[:, start:],
                               atol=1e-5)


def test_paged_window_wider_than_context_matches_full_bitwise():
    """A window wider than every context gives bit-identical results
    through the windowed kernels as the ``window=None`` full-attention
    kernels."""
    rng = np.random.default_rng(31)
    dec = _mk_decode(rng, 3, 8, 2, 16, 8, 4, [0, 9, 31])
    full = paged_attention_decode_pallas(*dec, pages_per_step=2,
                                         interpret=True)
    wide = paged_attention_decode_pallas(*dec, pages_per_step=2,
                                         interpret=True, window=64)
    np.testing.assert_array_equal(np.asarray(full), np.asarray(wide))
    q, k, v, kp, vp, tbl = _mk_prefill(rng, 1, 40, 8, 2, 16, 8)
    ln = jnp.full((1,), 40, jnp.int32)
    pf = paged_attention_prefill_pallas(q, kp, vp, tbl, ln, bm=16,
                                        interpret=True)
    pw = paged_attention_prefill_pallas(q, kp, vp, tbl, ln, bm=16,
                                        interpret=True, window=64)
    np.testing.assert_array_equal(np.asarray(pf), np.asarray(pw))


# ---------------------------------------------------------------------------
# Prefill page blocks
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("ps,pages,want", [
    (16, 160, 8),        # the mellum2 cell's full layers: 128 keys a step
    (16, 81, 8),         # its window layers (a 256-token tile + 1023)
    (16, 8, 8),          # the qwen cells' 128-token prompts: one step
    (16, 3, 3),          # a short prompt: no more pages than it has
    (4, 100, 32),
    (8, 69, 16),
    (128, 10, 1),        # a page already fills the lane row
    (256, 10, 1),
])
def test_prefill_pages_per_step_fills_one_lane_row(ps, pages, want):
    assert prefill_pages_per_step(ps, pages) == want


# (pps, window, q_offset): one page a step (the former grid), a width that
# divides no window, one wider than the tile's walk, each whole and as a
# tail over cached pages
PREFILL_BLOCK_CASES = [(1, None, 0), (3, None, 0), (3, None, 24), (5, 13, 0),
                       (2, 13, 24), (16, 13, 8), (16, None, 0)]


@pytest.mark.parametrize(
    "pps,window,start", PREFILL_BLOCK_CASES,
    ids=[f"pps{p}-w{w}-q{o}" for p, w, o in PREFILL_BLOCK_CASES])
def test_paged_prefill_page_blocks_match_ref_and_oracle(pps, window, start):
    """The prefill kernel (interpret) at pinned page-block widths against
    the ref and the unchunked oracle, on ragged lengths (an empty row, a
    row ending mid-page) and a prompt off the tile grid.  Every pool slot
    the prompts never wrote is NaN, so a block that reads past a row's
    pages, or a page slot clamped to the last live page, leaks loudly."""
    rng = np.random.default_rng(37 * pps + start)
    b, s, h, kvh, dh, ps = 3, 45, 8, 2, 16, 4
    q, k, v, kp, vp, tbl = _mk_prefill(rng, b, s, h, kvh, dh, ps)
    lens = [start, start + 11, s]
    lengths = jnp.asarray(lens, jnp.int32)
    ref = paged_attention_prefill_ref(q[:, start:], kp, vp, tbl, lengths,
                                      pages_per_step=2, q_offset=start,
                                      window=window)
    ker = paged_attention_prefill_pallas(q[:, start:], kp, vp, tbl, lengths,
                                         bm=16, pages_per_step=pps,
                                         interpret=True, q_offset=start,
                                         window=window)
    got = np.asarray(ker)
    assert np.isfinite(got).all()
    np.testing.assert_allclose(got, np.asarray(ref), atol=ATOL)
    orc = np.asarray(full_attention(q, k, v, causal=True, window=window))
    for r, ln in enumerate(lens):
        live = ln - start
        np.testing.assert_allclose(got[r, :live], orc[r, start:ln], atol=1e-5)
        np.testing.assert_array_equal(got[r, live:],
                                      np.zeros_like(got[r, live:]))

