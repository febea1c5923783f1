"""Continuous batching + paged KV caches (DESIGN.md §9).

The load-bearing property: a sequence that joins the engine mid-stream —
sharing its decode batch with strangers, its KV scattered over pool
pages — must emit exactly the tokens it would emit decoded alone, for
dense AND packed-BSR params.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, make_smoke
from repro.core import BlockingSpec
from repro.models import (init_caches, init_params, lm_forward, lm_generate,
                          lm_prefill)
from repro.models.attention import (
    attention_decode,
    attention_init,
    attention_prefill,
)
from repro.serving import NULL_PAGE, PagePool, Request, Scheduler, ServingEngine
from repro.serving.engine import _decode_chunk
from repro.sparse import knapsack_prune, pack_params


# ---------------------------------------------------------------------------
# PagePool / Scheduler units
# ---------------------------------------------------------------------------

def test_page_pool_alloc_free_recycle():
    pool = PagePool(num_pages=6, page_size=4)
    assert pool.free_pages == 5            # page 0 reserved (null)
    a = pool.alloc(10)                     # ceil(10/4) = 3 pages
    assert len(a) == 3 and NULL_PAGE not in a
    assert pool.used_pages == 3
    b = pool.alloc(4)
    assert len(b) == 1 and set(a).isdisjoint(b)
    assert not pool.can_alloc(8)           # 1 page left, need 2
    pool.free(a)
    assert pool.can_alloc(8)
    c = pool.alloc(8)                      # LIFO: freed pages come back
    assert set(c) <= set(a)
    with pytest.raises(ValueError):
        pool.free([NULL_PAGE])
    with pytest.raises(ValueError):
        pool.free([b[0], b[0]])            # double free


def test_scheduler_fifo_admission_and_head_of_line():
    pool = PagePool(num_pages=5, page_size=4)    # 4 usable pages
    sched = Scheduler(pool)
    big = Request(rid=0, prompt=np.zeros(10, np.int32), max_new=6)   # 4 pages
    small = Request(rid=1, prompt=np.zeros(2, np.int32), max_new=2)  # 1 page
    late = Request(rid=2, prompt=np.zeros(2, np.int32), max_new=2,
                   arrival=5)
    sched.submit(big), sched.submit(small), sched.submit(late)

    got = sched.admit(tick=0, free_slots=4)
    assert [r.rid for r in got] == [0]     # big takes the whole pool
    pages = pool.alloc(big.budget_tokens)
    # head-of-line: small would fit zero pages now; late hasn't arrived
    assert sched.admit(tick=0, free_slots=3) == []
    sched.retire(big, pages, tick=3)
    got = sched.admit(tick=3, free_slots=3)
    assert [r.rid for r in got] == [1]     # FIFO order, late still future
    pool.alloc(small.budget_tokens)
    assert [r.rid for r in sched.admit(tick=5, free_slots=2)] == [2]


def test_scheduler_same_tick_admissions_reserve_against_each_other():
    """Full-budget admission must never over-reserve the pool: requests
    admitted on the SAME tick reserve pages against each other, before
    any page is physically allocated."""
    pool = PagePool(num_pages=5, page_size=4)            # 4 usable pages
    sched = Scheduler(pool)
    for rid in range(3):                                 # 3 pages each
        sched.submit(Request(rid=rid, prompt=np.zeros(8, np.int32),
                             max_new=4))
    got = sched.admit(tick=0, free_slots=3)
    assert [r.rid for r in got] == [0]                   # 3 + 3 > 4 blocks #1
    assert sum(pool.pages_for(r.budget_tokens) for r in got) \
        <= pool.free_pages


def test_scheduler_admission_and_retirement_invariants_fuzz():
    """Random submit/admit/retire traffic: admitted budgets always fit
    the pool at admission time, and retirement returns EXACTLY the page
    count that was reserved."""
    rng = np.random.default_rng(3)
    pool = PagePool(num_pages=9, page_size=4)
    sched = Scheduler(pool)
    live, rid = [], 0
    for tick in range(60):
        for _ in range(int(rng.integers(0, 3))):
            sched.submit(Request(
                rid=rid, prompt=np.zeros(int(rng.integers(1, 12)), np.int32),
                max_new=int(rng.integers(1, 8)), arrival=tick))
            rid += 1
        free_slots = 4 - len(live)
        got = sched.admit(tick, free_slots)
        assert len(got) <= free_slots
        # the whole same-tick batch fits the pool as it stands
        assert sum(pool.pages_for(r.budget_tokens) for r in got) \
            <= pool.free_pages
        for r in got:
            pages = pool.alloc(r.budget_tokens)          # cannot raise
            assert len(pages) == pool.pages_for(r.budget_tokens)
            live.append((r, pages))
        keep = []
        for r, pages in live:
            if rng.integers(2):
                before = pool.free_pages
                sched.retire(r, pages, tick)
                assert pool.free_pages == before + len(pages)
            else:
                keep.append((r, pages))
        live = keep
    for r, pages in live:
        sched.retire(r, pages, tick)
    assert pool.free_pages == pool.num_pages - 1


def test_scheduler_orders_queue_by_arrival_not_submit_order():
    """An early-arrival request submitted late must not wait behind an
    unarrived head — the queue keeps (arrival, submit) order."""
    pool = PagePool(num_pages=5, page_size=4)
    sched = Scheduler(pool)
    sched.submit(Request(rid=0, prompt=np.zeros(2, np.int32), max_new=2,
                         arrival=100))
    sched.submit(Request(rid=1, prompt=np.zeros(2, np.int32), max_new=2,
                         arrival=0))
    assert [r.rid for r in sched.admit(tick=0, free_slots=2)] == [1]


def test_scheduler_insort_matches_stable_sort_semantics():
    """Regression for the O(n log n)-total ordered-insert queue: random
    submit traffic (with duplicate arrivals) must leave the queue in
    EXACTLY the order the old per-submit stable re-sort produced —
    sorted by arrival, equal arrivals in submit order."""
    rng = np.random.default_rng(7)
    for trial in range(20):
        pool = PagePool(num_pages=64, page_size=4)
        sched = Scheduler(pool)
        reqs = []
        for rid in range(int(rng.integers(1, 40))):
            r = Request(rid=rid, prompt=np.zeros(2, np.int32), max_new=2,
                        arrival=int(rng.integers(0, 6)))  # heavy duplicates
            reqs.append(r)
            sched.submit(r)
        reference = sorted(reqs, key=lambda r: r.arrival)  # stable
        assert [r.rid for r in sched.waiting] == \
            [r.rid for r in reference], f"trial {trial}"


def test_scheduler_priority_key_insort_matches_stable_sort():
    """The DESIGN.md §15 queue key: random (priority, arrival) traffic
    must leave the queue stably sorted by (priority, arrival) — equal
    keys in submit order — exactly what a full re-sort would produce."""
    rng = np.random.default_rng(15)
    for trial in range(20):
        pool = PagePool(num_pages=64, page_size=4)
        sched = Scheduler(pool)
        reqs = []
        for rid in range(int(rng.integers(1, 40))):
            r = Request(rid=rid, prompt=np.zeros(2, np.int32), max_new=2,
                        arrival=int(rng.integers(0, 4)),
                        priority=int(rng.integers(0, 3)))
            reqs.append(r)
            sched.submit(r)
        reference = sorted(reqs, key=lambda r: (r.priority, r.arrival))
        assert [r.rid for r in sched.waiting] == \
            [r.rid for r in reference], f"trial {trial}"


# ---------------------------------------------------------------------------
# Paged attention_decode == contiguous attention_decode
# ---------------------------------------------------------------------------

def test_attention_decode_paged_matches_contiguous():
    """Same KV scattered over pool pages (in shuffled physical order)
    must attend identically to the contiguous cache, per row."""
    b, ps, npages_seq, kvh, h, dh, d = 2, 4, 3, 2, 4, 16, 64
    max_len = ps * npages_seq
    key = jax.random.PRNGKey(0)
    p = attention_init(key, d, h, kvh, dh)
    x = jax.random.normal(jax.random.fold_in(key, 1), (b, 1, d))
    k0 = jax.random.normal(jax.random.fold_in(key, 2), (b, max_len, kvh, dh))
    v0 = jax.random.normal(jax.random.fold_in(key, 3), (b, max_len, kvh, dh))
    cache_len = jnp.asarray([5, 9], jnp.int32)

    out_c, cc = attention_decode(
        p, x, {"k": k0, "v": v0}, cache_len,
        num_heads=h, kv_heads=kvh, head_dim=dh)

    # pool: rows own disjoint, deliberately non-contiguous page ids
    tables = jnp.asarray([[3, 1, 5], [2, 6, 4]], jnp.int32)
    pool_k = jnp.zeros((7, kvh, ps, dh))
    pool_v = jnp.zeros((7, kvh, ps, dh))
    for r in range(b):
        for j in range(npages_seq):
            pool_k = pool_k.at[tables[r, j]].set(
                k0[r, j * ps:(j + 1) * ps].transpose(1, 0, 2))
            pool_v = pool_v.at[tables[r, j]].set(
                v0[r, j * ps:(j + 1) * ps].transpose(1, 0, 2))

    out_p, cp = attention_decode(
        p, x, {"k": pool_k, "v": pool_v}, cache_len,
        num_heads=h, kv_heads=kvh, head_dim=dh, page_table=tables)
    np.testing.assert_allclose(np.asarray(out_p), np.asarray(out_c),
                               atol=1e-6)
    # the write landed in the right physical slot of each row's own page
    for r, L in enumerate([5, 9]):
        want = cc["k"][r, L]
        got = cp["k"][tables[r, L // ps], :, L % ps]
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=1e-6)


def test_attention_prefill_paged_writes_match_contiguous():
    """Paged prefill scatters the prompt K/V straight into pool pages:
    same attention output as the contiguous cache, and every logical
    slot lands at pool[table[t // ps], :, t % ps] of the row's own table."""
    b, ps, npages_seq, kvh, h, dh, d, s = 2, 4, 3, 2, 4, 16, 64, 10
    key = jax.random.PRNGKey(0)
    p = attention_init(key, d, h, kvh, dh)
    x = jax.random.normal(jax.random.fold_in(key, 1), (b, s, d))

    cache_c = {"k": jnp.zeros((b, ps * npages_seq, kvh, dh)),
               "v": jnp.zeros((b, ps * npages_seq, kvh, dh))}
    out_c, cc = attention_prefill(p, x, cache_c, num_heads=h, kv_heads=kvh,
                                  head_dim=dh)

    tables = jnp.asarray([[3, 1, 5], [2, 6, 4]], jnp.int32)
    pool = {"k": jnp.zeros((7, kvh, ps, dh)), "v": jnp.zeros((7, kvh, ps, dh))}
    out_p, cp = attention_prefill(p, x, pool, num_heads=h, kv_heads=kvh,
                                  head_dim=dh, page_table=tables)
    np.testing.assert_allclose(np.asarray(out_p), np.asarray(out_c),
                               atol=1e-6)
    for r in range(b):
        for t in range(s):
            np.testing.assert_allclose(
                np.asarray(cp["k"][tables[r, t // ps], :, t % ps]),
                np.asarray(cc["k"][r, t]), atol=1e-6)
            np.testing.assert_allclose(
                np.asarray(cp["v"][tables[r, t // ps], :, t % ps]),
                np.asarray(cc["v"][r, t]), atol=1e-6)


def test_attention_paged_rejects_windows_with_clear_error():
    """Sliding windows over a paged cache are served (the windowed page
    walk, tests/test_paged_attention.py); a window that could not see
    even the query itself is refused by both entry points with an error
    naming the combination, not mis-computed."""
    p = attention_init(jax.random.PRNGKey(0), 32, 2, 2, 16)
    cache = {"k": jnp.zeros((4, 2, 2, 16)), "v": jnp.zeros((4, 2, 2, 16))}
    table = jnp.zeros((1, 2), jnp.int32)
    with pytest.raises(ValueError, match="window=0.*page_table"):
        attention_decode(p, jnp.zeros((1, 1, 32)), cache,
                         jnp.zeros((1,), jnp.int32),
                         num_heads=2, kv_heads=2, head_dim=16, window=0,
                         page_table=table)
    with pytest.raises(ValueError, match="window=0.*page_table"):
        attention_prefill(p, jnp.zeros((1, 3, 32)), cache,
                          num_heads=2, kv_heads=2, head_dim=16, window=0,
                          page_table=table)


def test_attention_paged_rejects_unknown_impl():
    p = attention_init(jax.random.PRNGKey(0), 32, 2, 2, 16)
    cache = {"k": jnp.zeros((4, 2, 2, 16)), "v": jnp.zeros((4, 2, 2, 16))}
    with pytest.raises(ValueError, match="paged_impl"):
        attention_decode(p, jnp.zeros((1, 1, 32)), cache,
                         jnp.zeros((1,), jnp.int32),
                         num_heads=2, kv_heads=2, head_dim=16,
                         page_table=jnp.zeros((1, 2), jnp.int32),
                         paged_impl="bogus")


# ---------------------------------------------------------------------------
# Engine: mid-stream joins token-identical to solo decode
# ---------------------------------------------------------------------------

def _smoke_pair(arch="qwen1.5-0.5b", *, sparsity=0.5):
    cfg = make_smoke(get_config(arch), n_layers=2)
    params = init_params(jax.random.PRNGKey(0), cfg)
    sel = knapsack_prune(params, sparsity=sparsity,
                         blocking=BlockingSpec(bk=32, bn=32), min_size=1024)
    packed = pack_params(params, sel.masks, sel.structures)
    return cfg, params, packed


def _window_moe_smoke(window=8):
    """A mellum2-shaped smoke stack: sliding and full GQA layers (3:1),
    YaRN on the full one, every MLP a dropless MoE holding 4 of 8
    experts."""
    cfg = make_smoke(get_config("mellum2-12b-a2.5b"), n_layers=4,
                     window=window, kv_heads=2).replace(
        moe_experts=8, moe_top_k=2, moe_experts_held=4, moe_expert_offset=2)
    return cfg, init_params(jax.random.PRNGKey(0), cfg)


def _solo(cfg, params, prompt, gen, eos_id=None):
    toks = jnp.asarray(prompt[None])
    caches = init_caches(cfg, 1, toks.shape[1] + gen, jnp.float32)
    logits, caches = lm_prefill(params, caches, {"tokens": toks}, cfg)
    first = jnp.argmax(logits[:, -1], -1)[:, None].astype(jnp.int32)
    out, _ = lm_generate(params, caches, first,
                         jnp.asarray(toks.shape[1], jnp.int32), gen, cfg,
                         eos_id=eos_id)
    return np.asarray(out)[0]


def test_engine_midstream_join_token_identical_dense_and_packed():
    cfg, dense, packed = _smoke_pair()
    rng = np.random.default_rng(0)
    lens, gens = [5, 9, 7, 5], [6, 4, 6, 5]
    arrivals = [0, 0, 3, 5]            # requests 2/3 join mid-stream
    prompts = [rng.integers(0, cfg.vocab, size=l).astype(np.int32)
               for l in lens]
    for name, params in (("dense", dense), ("packed", packed)):
        eng = ServingEngine(params, cfg, num_slots=2, page_size=4,
                            max_seq_len=16)
        for p, g, a in zip(prompts, gens, arrivals):
            eng.submit(p, g, arrival=a)
        done = eng.run()
        assert len(done) == len(prompts)
        for i, (p, g) in enumerate(zip(prompts, gens)):
            assert done[i].admitted_at >= arrivals[i]
            np.testing.assert_array_equal(
                done[i].tokens, _solo(cfg, params, p, g),
                err_msg=f"{name}/request {i}")
        # joins really were interleaved: some request admitted after
        # another had already started decoding
        assert max(r.admitted_at for r in done.values()) > 0
        # the prefix index deliberately retains full prompt blocks after
        # retirement (readmit reuse); dropping it must drain the pool
        eng.release_prefix_cache()
        assert eng.pool.free_pages == eng.pool.num_pages - 1  # all freed


@pytest.mark.parametrize("kind,impl", [
    ("dense", "fused"), ("packed", "fused"), ("dense", "gather"),
])
def test_engine_null_page_poison_streams_bitmatch_solo(kind, impl):
    """Fill the null page (page 0) of every layer pool with NaN before
    serving: streamed tokens must stay bit-identical to solo decode.
    This proves the attention read path — fused page walk AND legacy
    gather — never takes a value from an unallocated page (a single NaN
    would poison the softmax and change the argmax)."""
    cfg, dense_p, packed_p = _smoke_pair()
    cfg = cfg.replace(paged_attn_impl=impl)
    params = dense_p if kind == "dense" else packed_p
    rng = np.random.default_rng(2)
    lens, gens, arrivals = [5, 9, 7], [6, 4, 5], [0, 0, 3]
    prompts = [rng.integers(0, cfg.vocab, size=l).astype(np.int32)
               for l in lens]
    eng = ServingEngine(params, cfg, num_slots=2, page_size=4,
                        max_seq_len=16)
    for i, c in enumerate(eng.caches):
        if "k" in c:
            eng.caches[i] = {**c,
                             "k": c["k"].at[NULL_PAGE].set(jnp.nan),
                             "v": c["v"].at[NULL_PAGE].set(jnp.nan)}
    for p, g, a in zip(prompts, gens, arrivals):
        eng.submit(p, g, arrival=a)
    done = eng.run()
    assert len(done) == len(prompts)
    for i, (p, g) in enumerate(zip(prompts, gens)):
        got = np.asarray(done[i].tokens)
        assert np.isfinite(got).all()
        np.testing.assert_array_equal(
            got, _solo(cfg, params, p, g),
            err_msg=f"{kind}/{impl}/request {i}")


def test_engine_eos_retires_slot_and_readmits():
    """EOS ends a stream early, frees its pages, and the freed slot picks
    up the next queued request; tokens still match the solo decode."""
    cfg, dense, _ = _smoke_pair()
    rng = np.random.default_rng(1)
    p0 = rng.integers(0, cfg.vocab, size=6).astype(np.int32)
    p1 = rng.integers(0, cfg.vocab, size=6).astype(np.int32)
    base = _solo(cfg, dense, p0, 6)
    eos = int(base[2])                 # a token p0 emits mid-stream
    eng = ServingEngine(dense, cfg, num_slots=1, page_size=4,
                        max_seq_len=16, eos_id=eos)
    eng.submit(p0, 6)
    eng.submit(p1, 3)                  # must wait for the only slot
    done = eng.run()
    want0 = _solo(cfg, dense, p0, 6, eos_id=eos)
    stop = int(np.argmax(want0 == eos)) + 1 if (want0 == eos).any() else 6
    np.testing.assert_array_equal(done[0].tokens, want0[:stop])
    assert done[0].tokens[-1] == eos and len(done[0].tokens) < 6
    np.testing.assert_array_equal(done[1].tokens,
                                  _solo(cfg, dense, p1, 3, eos_id=eos))
    assert done[1].admitted_at >= done[0].finished_at


def test_engine_priority_reorders_admission_not_tokens():
    """Priority classes (DESIGN.md §15) through the full engine: a
    same-tick submission burst admits urgent-first, eviction freedom
    holds per admission (every admitted stream runs to its last token),
    and every stream stays bit-identical to its solo decode."""
    cfg, dense, _ = _smoke_pair()
    rng = np.random.default_rng(21)
    prompts = [rng.integers(0, cfg.vocab, size=5).astype(np.int32)
               for _ in range(3)]
    eng = ServingEngine(dense, cfg, num_slots=1, page_size=4,
                        max_seq_len=16, ticks_per_sync=2)
    rids = [eng.submit(p, 3, priority=pr)
            for p, pr in zip(prompts, (2, 0, 1))]
    done = eng.run()
    order = sorted(rids, key=lambda r: done[r].admitted_at)
    assert order == [1, 2, 0]          # urgency order, not submit order
    for r, p in zip(rids, prompts):
        assert done[r].status.name == "FINISHED"
        np.testing.assert_array_equal(done[r].tokens,
                                      _solo(cfg, dense, p, 3),
                                      err_msg=f"request {r}")


# ---------------------------------------------------------------------------
# Prefix caching (DESIGN.md §12)
# ---------------------------------------------------------------------------

def _shared_prompts(rng, cfg, *, prefix_len, tails):
    """Prompts sharing their first ``prefix_len`` tokens, each with a
    unique random tail."""
    prefix = rng.integers(0, cfg.vocab, size=prefix_len)
    return [np.concatenate([prefix, rng.integers(0, cfg.vocab, size=t)])
            .astype(np.int32) for t in tails]


@pytest.mark.parametrize("kind", ["dense", "packed"])
def test_engine_shared_prefix_streams_bitmatch_solo(kind):
    """Requests sharing a 3-page prompt prefix, joining mid-burst: hit
    requests map the cached pages and prefill only their tails, yet every
    stream stays bit-identical to its solo decode — the load-bearing
    property of DESIGN.md §12, for dense AND packed params."""
    cfg, dense_p, packed_p = _smoke_pair()
    params = dense_p if kind == "dense" else packed_p
    rng = np.random.default_rng(13)
    prompts = _shared_prompts(rng, cfg, prefix_len=12, tails=[3, 5, 2, 4])
    gens = [5, 4, 6, 4]
    arrivals = [0, 1, 4, 6]            # later requests join mid-stream
    eng = ServingEngine(params, cfg, num_slots=2, page_size=4,
                        max_seq_len=24, ticks_per_sync=2)
    for p, g, a in zip(prompts, gens, arrivals):
        eng.submit(p, g, arrival=a)
    done = eng.run()
    for i, (p, g) in enumerate(zip(prompts, gens)):
        np.testing.assert_array_equal(
            done[i].tokens, _solo(cfg, params, p, g),
            err_msg=f"{kind}/request {i}")
    st = eng.prefix_stats
    assert st["enabled"] and st["hit_requests"] == 3
    assert st["pages_shared"] == 9     # 3 later requests x 3 prefix pages
    assert done[0].prefix_hit_pages == 0
    assert all(done[i].prefix_hit_pages == 3 for i in (1, 2, 3))
    # the index deliberately retains prompt blocks past retirement
    # (readmit reuse); dropping it must drain the pool completely
    eng.release_prefix_cache()
    assert eng.pool.free_pages == eng.pool.num_pages - 1


def test_engine_prefix_reuse_after_retirement():
    """EOS-retire-readmit reuse: the cached blocks survive the request
    that computed them, so the same prompt submitted long after the
    original finished maps its prefix instead of re-prefilling."""
    cfg, dense, _ = _smoke_pair()
    rng = np.random.default_rng(17)
    p0 = rng.integers(0, cfg.vocab, size=13).astype(np.int32)
    eng = ServingEngine(dense, cfg, num_slots=1, page_size=4,
                        max_seq_len=24)
    eng.submit(p0, 4)
    eng.submit(p0.copy(), 4, arrival=30)   # long after request 0 retired
    done = eng.run()
    want = _solo(cfg, dense, p0, 4)
    np.testing.assert_array_equal(done[0].tokens, want)
    np.testing.assert_array_equal(done[1].tokens, want)
    assert done[0].prefix_hit_pages == 0
    assert done[1].prefix_hit_pages == 3   # (13 - 1) // 4: proper prefix
    assert done[1].admitted_at >= done[0].finished_at
    assert eng.prefix_stats["hit_requests"] == 1


def test_engine_identical_sampled_prompts_keep_independent_streams():
    """Three byte-identical sampled prompts in one burst: every request
    keeps its own rid (dedupe-safe) and its own fold_in(base, rid) PRNG
    stream, so sharing the ENTIRE cached prefix never collapses the
    samples — each stream replays against its own solo decode."""
    cfg, dense, _ = _smoke_pair()
    rng = np.random.default_rng(19)
    p0 = rng.integers(0, cfg.vocab, size=13).astype(np.int32)
    base = jax.random.PRNGKey(5)
    eng = ServingEngine(dense, cfg, num_slots=3, page_size=4,
                        max_seq_len=24, seed=5, temperature=0.9, top_k=8)
    rids = [eng.submit(p0.copy(), 5) for _ in range(3)]
    assert len(set(rids)) == 3
    done = eng.run()
    for rid in rids:
        want = _solo_sampled(cfg, dense, p0, 5, 0.9, 8, None,
                             jax.random.fold_in(base, rid))
        np.testing.assert_array_equal(done[rid].tokens, want,
                                      err_msg=f"request {rid}")
    assert eng.prefix_stats["hit_requests"] == 2  # 2nd/3rd hit the 1st's


def test_engine_cow_guard_copies_shared_write_page():
    """COW backstop: the standard path never decodes into a shared page,
    but if an external holder maps a live tail page anyway, the guard
    must copy it to a fresh page before the chunk — the stream stays
    bit-identical and the sharer's page is never written."""
    cfg, dense, _ = _smoke_pair()
    rng = np.random.default_rng(23)
    p0 = rng.integers(0, cfg.vocab, size=6).astype(np.int32)
    want = _solo(cfg, dense, p0, 6)
    eng = ServingEngine(dense, cfg, num_slots=1, page_size=4,
                        max_seq_len=16, ticks_per_sync=2)
    eng.submit(p0, 6)
    eng.step()                               # admit + first decode chunk
    # an external reference on the page the NEXT chunk writes into
    idx = int(eng._cache_len[0]) // eng.pool.page_size
    pid = int(eng._tables[0, idx])
    eng.pool.share([pid])
    done = eng.run()
    assert eng.pool.cow_copies >= 1
    assert eng.prefix_stats["cow_copies"] >= 1
    np.testing.assert_array_equal(done[0].tokens, want)
    assert eng.pool.refcount(pid) == 1       # only the external ref left
    eng.pool.free([pid])
    eng.release_prefix_cache()
    assert eng.pool.free_pages == eng.pool.num_pages - 1


def test_engine_stalls_loudly_when_pool_too_small():
    """A pool that can never fit the head request must fail FAST — on the
    first drained tick, not after burning max_ticks — and the error must
    carry enough state (waiting queue, pool occupancy, page math) to
    diagnose the sizing mistake."""
    cfg, dense, _ = _smoke_pair()
    eng = ServingEngine(dense, cfg, num_slots=1, page_size=4,
                        max_seq_len=16, num_pages=2)   # 1 usable page
    eng.submit(np.zeros(6, np.int32), 4)               # needs 3 pages
    with pytest.raises(RuntimeError, match="admission stalled") as ei:
        eng.run(max_ticks=50_000)
    assert eng.tick <= 1, "stall must be detected immediately"
    msg = str(ei.value)
    assert "needs 3 pages" in msg
    assert "waiting" in msg and "pool=" in msg and "1/1 pages free" in msg


# ---------------------------------------------------------------------------
# Multi-tick on-device decode chunks (DESIGN.md §10)
# ---------------------------------------------------------------------------

_SAMPLING_PALETTE = [
    (0.0, None, None),             # greedy
    (0.8, 5, None),                # temperature + top-k
    (1.3, None, 0.9),              # temperature + nucleus
    (0.9, 8, 0.95),                # everything at once
]


def _solo_sampled(cfg, params, prompt, gen, t, k, p, key, eos_id=None):
    toks = jnp.asarray(prompt[None])
    caches = init_caches(cfg, 1, toks.shape[1] + gen, jnp.float32)
    logits, caches = lm_prefill(params, caches, {"tokens": toks}, cfg)
    first = jnp.argmax(logits[:, -1], -1)[:, None].astype(jnp.int32)
    out, _ = lm_generate(params, caches, first,
                         jnp.asarray(toks.shape[1], jnp.int32), gen, cfg,
                         temperature=t, top_k=k, top_p=p, key=key,
                         eos_id=eos_id)
    return np.asarray(out)[0]


@pytest.mark.parametrize("kind", ["dense", "packed"])
def test_engine_fuzz_streams_bitmatch_solo(kind):
    """Randomized arrival-trace differential fuzz: seeded random prompts,
    arrival ticks, budgets and PER-SLOT sampling params, streamed through
    the chunked engine at every ticks_per_sync — each request's stream
    must be bit-identical to its solo ``lm_generate`` run (same per-slot
    key derivation: fold_in(base, rid)).  Budget-exhausted rows freeze
    mid-chunk (gen < 16 while ticks_per_sync = 16), so the done-mask path
    is always exercised."""
    cfg, dense_p, packed_p = _smoke_pair()
    params = dense_p if kind == "dense" else packed_p
    seed = 7 if kind == "dense" else 11
    rng = np.random.default_rng(seed)
    n = 6
    lens = rng.integers(3, 10, size=n)
    gens = rng.integers(2, 8, size=n)
    arrivals = np.sort(rng.integers(0, 12, size=n))
    samp = [_SAMPLING_PALETTE[i]
            for i in rng.integers(0, len(_SAMPLING_PALETTE), size=n)]
    prompts = [rng.integers(0, cfg.vocab, size=int(l)).astype(np.int32)
               for l in lens]
    base = jax.random.PRNGKey(5)
    solos = {}
    for tps in (1, 4, 16):
        eng = ServingEngine(params, cfg, num_slots=2, page_size=4,
                            max_seq_len=24, ticks_per_sync=tps, seed=5)
        rids = [eng.submit(pr, int(g), arrival=int(a), temperature=t,
                           top_k=k, top_p=p)
                for pr, g, a, (t, k, p)
                in zip(prompts, gens, arrivals, samp)]
        done = eng.run()
        assert len(done) == n
        for i, rid in enumerate(rids):
            if rid not in solos:
                t, k, p = samp[i]
                solos[rid] = _solo_sampled(
                    cfg, params, prompts[i], int(gens[i]), t, k, p,
                    jax.random.fold_in(base, rid))
            assert len(done[rid].tokens) == gens[i]
            np.testing.assert_array_equal(
                done[rid].tokens, solos[rid],
                err_msg=f"{kind}/tps={tps}/request {rid}")
        eng.release_prefix_cache()   # index refs survive retirement
        assert eng.pool.free_pages == eng.pool.num_pages - 1


def test_engine_chunked_eos_freezes_midchunk_and_readmits():
    """EOS inside a chunk: the row freezes mid-scan (its remaining chunk
    ticks emit nothing), retires at the chunk boundary, and the freed
    slot re-admits the queue head — tokens still match the solo decode."""
    cfg, dense, _ = _smoke_pair()
    rng = np.random.default_rng(1)
    p0 = rng.integers(0, cfg.vocab, size=6).astype(np.int32)
    p1 = rng.integers(0, cfg.vocab, size=6).astype(np.int32)
    base = _solo(cfg, dense, p0, 6)
    eos = int(base[2])                 # fires mid-chunk at ticks_per_sync=4
    eng = ServingEngine(dense, cfg, num_slots=1, page_size=4,
                        max_seq_len=16, eos_id=eos, ticks_per_sync=4)
    eng.submit(p0, 6)
    eng.submit(p1, 3)
    done = eng.run()
    want0 = _solo(cfg, dense, p0, 6, eos_id=eos)
    stop = int(np.argmax(want0 == eos)) + 1 if (want0 == eos).any() else 6
    np.testing.assert_array_equal(done[0].tokens, want0[:stop])
    assert done[0].tokens[-1] == eos and len(done[0].tokens) < 6
    np.testing.assert_array_equal(done[1].tokens,
                                  _solo(cfg, dense, p1, 3, eos_id=eos))
    assert done[1].admitted_at >= done[0].finished_at


# ---------------------------------------------------------------------------
# Steady-state invariants (DESIGN.md §14): 0 recompiles, 1 transfer/chunk
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("kind", ["dense", "packed", "window-moe"])
def test_engine_steady_state_zero_recompiles_one_sync_per_chunk(kind):
    """After warm-up, N chunks of mixed admit/retire traffic must hit the
    jit cache every time (0 new compiles, engine-wide compile-event
    tripwire included) and perform exactly ONE declared host round-trip
    per decode chunk plus one per admission — proven with the
    analysis/runtime.py counters, and with stray-pull interception armed
    so any undeclared device->host pull raises."""
    from repro.analysis import runtime as analysis_runtime

    if kind == "window-moe":          # windowed walk + MoE counters too
        cfg, params = _window_moe_smoke(window=4)
    else:
        cfg, dense_p, packed_p = _smoke_pair()
        params = dense_p if kind == "dense" else packed_p
    rng = np.random.default_rng(7)
    PLEN, GEN = 6, 3                   # one shape bucket for every request

    def build():
        # prefix caching off so every admission prefills from start=0 —
        # a single static-start bucket for _paged_prefill_step
        return ServingEngine(params, cfg, num_slots=2, page_size=4,
                             max_seq_len=16, ticks_per_sync=2,
                             prefix_caching=False)

    def traffic(eng, n, spread):
        for i in range(n):
            eng.submit(rng.integers(0, cfg.vocab, size=PLEN).astype(np.int32),
                       GEN, arrival=i * spread)

    # warm-up: compile every (shape, static) combo the steady engine uses
    warm = build()
    traffic(warm, 3, spread=2)
    assert len(warm.run()) == 3

    eng = build()
    traffic(eng, 6, spread=2)          # staggered: retire/admit churn
    before = eng.analysis_stats()
    chunks = 0
    with analysis_runtime.no_host_sync(strict=True):
        while eng.scheduler.pending or any(s is not None for s in eng.slots):
            regions0 = dict(eng.sync_regions)
            admitted = eng.step()
            active = any(s is not None for s in eng.slots)
            d_chunk = eng.sync_regions["decode_chunk"] - regions0["decode_chunk"]
            d_admit = eng.sync_regions["admission"] - regions0["admission"]
            assert d_chunk <= 1, "more than one transfer boundary in a chunk"
            assert d_admit == admitted, "admission sync without an admission"
            chunks += d_chunk
            if not active and not eng.scheduler.pending and d_chunk == 0:
                break
    after = eng.analysis_stats()

    assert chunks >= 3                 # the loop really decoded in chunks
    assert after["compile_caches"] == before["compile_caches"], \
        "steady-state traffic recompiled a hot-path function"
    assert after["compile_events"] == before["compile_events"], \
        "something compiled during steady-state traffic"
    assert after["sync_regions"]["decode_chunk"] - \
        before["sync_regions"]["decode_chunk"] == chunks
    assert after["sync_regions"]["admission"] - \
        before["sync_regions"]["admission"] == 6
    assert all(r.status.name == "FINISHED" for r in eng.requests.values())


@pytest.mark.parametrize("kind", ["dense", "packed"])
def test_engine_prefill_logits_and_lowered_chunk(kind):
    """``prefill_logits`` runs the admission prefill itself: its
    first-token logits match ``lm_forward`` on the same params, and the
    pages it borrows go back to the pool.  ``lower_decode_chunk`` lowers
    the chunk ``step()`` runs without touching the engine's state, and
    the engine then serves normally."""
    cfg, dense_p, packed_p = _smoke_pair()
    params = dense_p if kind == "dense" else packed_p
    eng = ServingEngine(params, cfg, num_slots=2, page_size=4,
                        max_seq_len=16, ticks_per_sync=2)
    free0 = eng.pool.free_pages
    prompt = np.random.default_rng(3).integers(
        0, cfg.vocab, size=9).astype(np.int32)
    got = eng.prefill_logits(prompt)
    want, _ = lm_forward(params, {"tokens": jnp.asarray(prompt[None])}, cfg)
    np.testing.assert_allclose(got, np.asarray(want[0, -1]),
                               atol=1e-3, rtol=1e-4)
    assert eng.pool.free_pages == free0

    caches = eng.caches
    lowered = eng.lower_decode_chunk()
    toks_info = jax.tree.leaves(lowered.out_info)[0]
    assert toks_info.shape == (2, 2)            # (ticks, slots)
    assert eng.caches is caches and eng.tick == 0
    eng.submit(prompt, 3)
    done = eng.run()
    np.testing.assert_array_equal(done[0].tokens, _solo(cfg, params, prompt, 3))


def test_engine_attention_page_counters_hand_count():
    """``attn_live_page_ticks`` sums, over committed ticks and slots, the
    pages each slot's cache_len occupies; ``attn_walked_page_ticks``
    counts the decode grid's page steps.  Page size 4, 2 slots, 2-tick
    chunks.  A (prompt 5, 4 tokens) decodes 3 ticks at cache_len 5, 6,
    7, then rides the rest of chunk 2 frozen at 8: 2 pages each tick.
    B (prompt 3, 3 tokens) decodes 2 ticks at 3, 4 (1 page each) and
    retires; its free slot walks 0 pages in chunk 2.  Live: 4·2 + 2·1."""
    from repro.kernels.paged_attention import decode_pages_per_step

    cfg, dense, _ = _smoke_pair()
    rng = np.random.default_rng(13)
    eng = ServingEngine(dense, cfg, num_slots=2, page_size=4,
                        max_seq_len=16, ticks_per_sync=2)
    eng.submit(rng.integers(0, cfg.vocab, size=5).astype(np.int32), 4)
    eng.submit(rng.integers(0, cfg.vocab, size=3).astype(np.int32), 3)
    eng.run()
    assert eng.decode_ticks == 4
    assert eng.attn_live_page_ticks == {"full": 10}
    pps = decode_pages_per_step(cfg.kv_heads, 4, cfg.head_dim_(),
                                jnp.float32, eng.max_pages)
    walk = -(-eng.max_pages // pps) * pps
    assert eng.attn_walked_page_ticks == {"full": eng.decode_ticks * 2 * walk}
    assert eng.moe_routed_pairs == eng.moe_experts_touched == 0


def test_engine_window_and_moe_counters_hand_count():
    """The same traffic on a stack of sliding (window 4) and full layers.
    A sliding layer's live pages run from the page of the first visible
    position, ``max(ctx - 3, 0)``: A at ctx 5, 6, 7, 8 sees pages
    {0,1}, {0,1}, {1}, {1}; B at 3, 4 sees {0}, {0}, then its slot is
    free.  Live: 2+2+1+1 + 1+1.  The walk covers decode_blocks(...)
    blocks of the window.  Every MoE layer routes both rows each tick:
    at most min(top_k, held) pairs per row, and touches at most the 4
    held experts."""
    from repro.kernels.paged_attention import (decode_blocks,
                                               decode_pages_per_step)

    cfg, params = _window_moe_smoke(window=4)
    rng = np.random.default_rng(13)
    eng = ServingEngine(params, cfg, num_slots=2, page_size=4,
                        max_seq_len=16, ticks_per_sync=2)
    eng.submit(rng.integers(0, cfg.vocab, size=5).astype(np.int32), 4)
    eng.submit(rng.integers(0, cfg.vocab, size=3).astype(np.int32), 3)
    eng.run()
    assert eng.decode_ticks == 4
    assert eng.attn_live_page_ticks == {"sliding": 8, "full": 10}
    pps = decode_pages_per_step(cfg.kv_heads, 4, cfg.head_dim_(),
                                jnp.float32, eng.max_pages)
    for kind, window in (("sliding", 4), ("full", None)):
        walk = decode_blocks(eng.max_pages, pps, 4, window) * pps
        assert eng.attn_walked_page_ticks[kind] == eng.decode_ticks * 2 * walk
    layer_ticks = eng.decode_ticks * eng.moe_layers
    assert eng.moe_layers == 4
    assert 0 < eng.moe_routed_pairs <= layer_ticks * 2 * cfg.moe_top_k
    assert 0 < eng.moe_experts_touched <= layer_ticks * 4
    assert eng.moe_experts_touched <= eng.moe_routed_pairs


# ---------------------------------------------------------------------------
# The decode chunk's loop: the all-done skip is its exit
# ---------------------------------------------------------------------------

def _chunk_tick_by_tick(args, static):
    """``_decode_chunk`` as the scan of per-tick steps it is: ``ticks``
    one-tick chunks threading the state, each tick whose rows are all
    done emitting every row's token as not live.  Budgets alone end rows
    here (no eos, no NaN), so the host's ``left - counts`` carries
    ``done`` exactly."""
    params, caches, tok, clen, tables, rngs, temp, topk, topp, left = args
    toks, lives, bad, moe = [], [], None, 0
    for _ in range(static["ticks"]):
        t, c, b, tok, clen, rngs, m, caches = _decode_chunk(
            params, jax.tree.map(jnp.copy, caches), tok, clen, tables, rngs,
            temp, topk, topp, left, **{**static, "ticks": 1})
        toks.append(t[0])
        lives.append(c)
        left = left - c
        bad = b if bad is None else bad | b
        moe = moe + m
    counts = jnp.sum(jnp.stack(lives), axis=0)
    return (jnp.stack(toks), counts, bad, tok, clen, rngs, moe, caches)


@pytest.mark.parametrize("budget", [3, 0], ids=["done-at-tick-3", "all-done"])
@pytest.mark.parametrize("kind", ["dense", "window-moe"])
def test_decode_chunk_loop_matches_tick_by_tick(kind, budget):
    """An 8-tick chunk's whole return tuple — tokens, counts, bad flags,
    last token, cache_len, rngs, MoE counts and every pool — is
    bit-identical to eight one-tick chunks, on the CPU reference path.
    With budget 3 every row finishes at tick 3 of 8 and the loop exits
    there: the last 5 rows of the token block repeat each row's last
    token.  With budget 0 every row starts done and nothing runs."""
    if kind == "window-moe":
        cfg, params = _window_moe_smoke(window=4)
    else:
        cfg, params, _ = _smoke_pair()
    rng = np.random.default_rng(21)
    eng = ServingEngine(params, cfg, num_slots=3, page_size=4,
                        max_seq_len=24, ticks_per_sync=2)
    for n in (5, 7):
        eng.submit(rng.integers(0, cfg.vocab, size=n).astype(np.int32), 12)
    eng.step()                          # admitted, two ticks in; slot 2 free
    left = np.array([budget, budget, 0], np.int32)
    args, static = eng._chunk_call(left, 8)
    args = (args[0], jax.tree.map(jnp.copy, args[1]), *args[2:])
    static["sampled"] = True            # per-row rngs advance in the loop
    args = (*args[:6], jnp.asarray([0.8, 1.0, 0.0], jnp.float32), *args[7:])
    pools = jax.device_get(args[1])     # the chunk donates its own

    want = _chunk_tick_by_tick(args, static)
    got = _decode_chunk(*args, **static)
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))
    toks, counts, _, tok, clen, _, moe, _ = got
    np.testing.assert_array_equal(np.asarray(counts), [budget, budget, 0])
    np.testing.assert_array_equal(np.asarray(clen),
                                  np.asarray(args[3]) + counts)
    np.testing.assert_array_equal(
        np.asarray(toks)[budget:],
        np.broadcast_to(np.asarray(tok)[:, 0], (8 - budget, 3)))
    if budget == 0:
        for g, w in zip(jax.tree.leaves(got[7]), jax.tree.leaves(pools)):
            np.testing.assert_array_equal(np.asarray(g), np.asarray(w))
        assert not np.asarray(moe).any()


# ---------------------------------------------------------------------------
# Program spans on the profiler's trace
# ---------------------------------------------------------------------------

def _traced_spans(fn, log_dir):
    """``fn()`` under the JAX profiler; the ``repro.*`` host events it
    left in the trace as (name, start, end, stats), by start."""
    import glob
    import os

    from jax.profiler import ProfileData

    jax.profiler.start_trace(str(log_dir))
    try:
        fn()
    finally:
        jax.profiler.stop_trace()
    path, = glob.glob(os.path.join(str(log_dir), "**", "*.xplane.pb"),
                      recursive=True)
    spans = []
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            spans.extend((e.name, e.start_ns, e.end_ns, dict(e.stats))
                         for e in line.events if e.name.startswith("repro."))
    return sorted(spans, key=lambda s: (s[1], -s[2]))


@pytest.mark.parametrize("kind", ["dense", "packed"])
def test_engine_step_spans_on_the_profiler_trace(kind, tmp_path):
    """Every phase of ``step()`` is a ``repro.*`` span inside its
    ``repro.step``: one ``repro.sync.decode_chunk`` per committed chunk,
    one ``repro.dispatch`` before each, and one ``repro.prefill`` per
    admission carrying its request id and tail length."""
    cfg, dense_p, packed_p = _smoke_pair()
    params = dense_p if kind == "dense" else packed_p
    eng = ServingEngine(params, cfg, num_slots=2, page_size=4,
                        max_seq_len=16, ticks_per_sync=2)
    rng = np.random.default_rng(11)
    lens = [5, 9, 7, 6]
    rids = [eng.submit(rng.integers(0, cfg.vocab, size=n).astype(np.int32),
                       4, arrival=2 * i) for i, n in enumerate(lens)]
    spans = _traced_spans(eng.run, tmp_path)

    steps = [s for s in spans if s[0] == "repro.step"]
    inner = [s for s in spans if s[0] != "repro.step"]
    assert steps and inner
    assert [s[3]["tick"] for s in steps] == sorted(s[3]["tick"] for s in steps)
    for name, a, b, _ in inner:
        assert any(sa <= a and b <= sb for _, sa, sb, _ in steps), name

    def named(n):
        return [s for s in spans if s[0] == n]

    chunks = sum(eng.chunks_by_ticks.values())
    assert chunks >= 3
    assert len(named("repro.sync.decode_chunk")) == chunks
    assert len(named("repro.dispatch")) == chunks
    assert len(named("repro.commit")) == chunks
    assert all(d[3]["ticks"] == 2 and 1 <= d[3]["active"] <= 2
               for d in named("repro.dispatch"))
    assert len(named("repro.verify_index")) == len(steps)
    assert len(named("repro.admit")) == len(steps)
    prefills = named("repro.prefill")
    assert [p[3]["rid"] for p in prefills] == rids
    assert [p[3]["tokens"] for p in prefills] == lens
    assert all(p[3]["hit_pages"] == 0 for p in prefills)
    assert len(named("repro.sync.admission")) == len(rids)
    # each admission's round trip lies inside its own prefill
    for p, s in zip(prefills, named("repro.sync.admission")):
        assert p[1] <= s[1] and s[2] <= p[2]
