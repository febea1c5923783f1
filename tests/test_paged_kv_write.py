"""The decode write of the new token's K/V into its page
(``paged_kv_write``, kernels/paged_attention.py).

The Pallas kernel (interpret mode) against the XLA scatter it replaces
on TPU, bit for bit, at both served models' pool shapes: qwen1.5-0.5b
(16 KV heads, 16-token pages, dh 64) and mellum2 (4 KV heads, dh 128).
Free slots park on the null page (page 0), so several rows may write it
in one call and any value may land there: page 0 is left out of every
comparison, and NaN poison in it must not reach a live page.
"""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import paged_kv_write
from repro.kernels.paged_attention import (
    _write_slot,
    paged_kv_write_pallas,
    paged_kv_write_ref,
)

# (KV heads, page size, head dim) of the served pools
POOLS = [pytest.param(16, 16, 64, id="qwen"),
         pytest.param(4, 16, 128, id="mellum2")]
MAX_PAGES = 4


def _case(rng, kvh, ps, dh, clens, *, poison_null=False):
    """One decode write: rows with ``cache_len`` None are free slots,
    their whole table and their position on the null page; live rows own
    shuffled pages.  New rows are bf16, as the model's activations are;
    the pools are fp32."""
    b = len(clens)
    n_pages = b * MAX_PAGES + 3            # and pages nobody owns
    ids = rng.permutation(np.arange(1, n_pages))[: b * MAX_PAGES]
    free = np.array([c is None for c in clens])
    tbl = np.where(free[:, None], 0, ids.reshape(b, MAX_PAGES))
    clen = np.array([0 if c is None else c for c in clens], np.int32)
    kp = rng.normal(size=(n_pages, kvh, ps, dh)).astype(np.float32)
    vp = rng.normal(size=(n_pages, kvh, ps, dh)).astype(np.float32)
    if poison_null:
        kp[0] = vp[0] = np.nan
    kn = jnp.asarray(rng.normal(size=(b, kvh, dh)), jnp.bfloat16)
    vn = jnp.asarray(rng.normal(size=(b, kvh, dh)), jnp.bfloat16)
    return (kn, vn, jnp.asarray(kp), jnp.asarray(vp),
            jnp.asarray(tbl, jnp.int32), jnp.asarray(clen))


def _page(tbl, r, c, ps):
    """The page row ``r`` writes at position ``c``: its table entry at
    ``c // ps``, or the null page where ``c`` lies past the table."""
    tbl = np.asarray(tbl)
    return int(tbl[r, c // ps]) if c // ps < tbl.shape[1] else 0


def _written(tbl, clen, ps):
    """Pages a live row writes, the null page aside."""
    return {_page(tbl, r, int(c), ps)
            for r, c in enumerate(np.asarray(clen))} - {0}


def _dropped(args):
    """The parent's scatter of each row at its own table entry, a row
    past its table dropped; numpy, independent of ``_write_slot``."""
    kn, vn, kp, vp, tbl, clen = (np.asarray(a) for a in args)
    kp, vp = kp.copy(), vp.copy()
    ps = kp.shape[2]
    for r, c in enumerate(clen):
        if c // ps < tbl.shape[1]:
            kp[tbl[r, c // ps], :, c % ps] = kn[r].astype(np.float32)
            vp[tbl[r, c // ps], :, c % ps] = vn[r].astype(np.float32)
    return kp, vp


@pytest.mark.parametrize("kvh,ps,dh", POOLS)
@pytest.mark.parametrize("clens", [
    pytest.param(lambda ps: [0, ps - 1, ps, 2 * ps + 5, 3 * ps + ps - 1],
                 id="offsets-0-last-and-page-boundary"),
    pytest.param(lambda ps: [None, 3, None, ps + 7, None, 0],
                 id="rows-parked-on-the-null-page"),
    pytest.param(lambda ps: [MAX_PAGES * ps, 3, None, MAX_PAGES * ps - 1,
                             MAX_PAGES * ps],
                 id="rows-past-their-table"),
])
def test_paged_kv_write_kernel_matches_scatter_bitwise(kvh, ps, dh, clens):
    """Offset 0, offset ps - 1 and a row exactly at a page boundary
    (logical page 1, offset 0), with and without free rows on the null
    page, and frozen rows whose position lies past their table (a full
    budget): every page but page 0 comes back bit-identical to the
    scatter's, which drops the write past the table, and each live
    row's new K/V sits at its position."""
    args = _case(np.random.default_rng(0), kvh, ps, dh, clens(ps))
    kn, vn, kp, vp, tbl, clen = args
    want = paged_kv_write_ref(*args)
    got = paged_kv_write_pallas(*args, interpret=True)
    for g, w, d in zip(got, want, _dropped(args)):
        np.testing.assert_array_equal(np.asarray(g)[1:], np.asarray(w)[1:])
        np.testing.assert_array_equal(np.asarray(w)[1:], d[1:])
    for r, c in enumerate(np.asarray(clen)):
        page = _page(tbl, r, c, ps)
        if page:
            np.testing.assert_array_equal(
                np.asarray(got[0])[page, :, c % ps],
                np.asarray(kn[r].astype(jnp.float32)))
            np.testing.assert_array_equal(
                np.asarray(got[1])[page, :, c % ps],
                np.asarray(vn[r].astype(jnp.float32)))


@pytest.mark.parametrize("kvh,ps,dh", POOLS)
def test_paged_kv_write_leaves_untouched_pages_unchanged(kvh, ps, dh):
    """A page no live row writes comes back as it went in; a written page
    changes at its one position only."""
    clens = [None, 1, ps - 1, None, 2 * ps, MAX_PAGES * ps]
    kn, vn, kp, vp, tbl, clen = _case(np.random.default_rng(1), kvh, ps, dh,
                                      clens)
    ko, vo = paged_kv_write_pallas(kn, vn, kp, vp, tbl, clen, interpret=True)
    written = _written(tbl, clen, ps)
    assert len(written) == 3
    for before, after in ((kp, ko), (vp, vo)):
        before, after = np.asarray(before), np.asarray(after)
        for page in range(1, before.shape[0]):
            if page not in written:
                np.testing.assert_array_equal(after[page], before[page])
        for r, c in enumerate(np.asarray(clen)):
            page = _page(tbl, r, c, ps)
            if page:
                keep = np.arange(ps) != c % ps
                np.testing.assert_array_equal(after[page][:, keep],
                                              before[page][:, keep])


@pytest.mark.parametrize("kvh,ps,dh", POOLS)
def test_paged_kv_write_null_page_poison_stays_there(kvh, ps, dh):
    """Three free rows write a NaN-poisoned null page in one call: no
    NaN reaches a live page, and every other page matches the scatter."""
    clens = [None, 5, None, ps, None]
    args = _case(np.random.default_rng(2), kvh, ps, dh, clens,
                 poison_null=True)
    got = paged_kv_write_pallas(*args, interpret=True)
    want = paged_kv_write_ref(*args)
    for g, w in zip(got, want):
        g = np.asarray(g)
        assert np.isfinite(g[1:]).all()
        np.testing.assert_array_equal(g[1:], np.asarray(w)[1:])


def test_paged_kv_write_slot_past_the_table_is_the_null_page():
    """A row at ``cache_len == max_pages * ps`` has no table entry: its
    write goes to page 0, never to the index a lookup past the table
    gives (interpret mode clamps block indices, so only this shows it)."""
    ps = 16
    tbl = jnp.asarray([[5, 6, 7, 8], [9, 10, 11, 12], [0, 0, 0, 0]],
                      jnp.int32)
    clen = jnp.asarray([MAX_PAGES * ps, MAX_PAGES * ps - 1, 0], jnp.int32)
    pid, off = _write_slot(tbl, clen, ps)
    np.testing.assert_array_equal(np.asarray(pid), [0, 12, 0])
    np.testing.assert_array_equal(np.asarray(off), [0, ps - 1, 0])


@pytest.mark.parametrize("mode", ["auto", "ref", "interpret"])
def test_paged_kv_write_ops_mode_dispatch(mode):
    """``ops.paged_kv_write``: off TPU ``auto`` is the scatter itself;
    ``interpret`` runs the kernel body and agrees with it off page 0."""
    args = _case(np.random.default_rng(3), 4, 8, 32, [None, 2, 9, None])
    want = paged_kv_write_ref(*args)
    got = paged_kv_write(*args, mode=mode)
    for g, w in zip(got, want):
        if mode == "interpret":
            g, w = g[1:], w[1:]
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))
