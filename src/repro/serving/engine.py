"""Continuous-batching serving engine over paged KV caches.

The engine owns a fixed number of *decode slots* (rows of the jitted
decode step) and one page pool per attention layer (DESIGN.md §9/§10).
Its host loop interleaves three things per scheduler event:

1. **admission** — the FIFO scheduler hands over requests whose whole
   token budget fits in the pool; each gets a free slot, freshly
   allocated pages, and a *paged prefill-on-join*: one jitted
   ``lm_prefill`` over its (unpadded) prompt whose attention K/V is
   scattered straight into the pages the request owns (no contiguous
   intermediate cache) and whose recurrent states (mamba/xLSTM) are
   written into the slot row.  The first token is the prefill argmax —
   identical to the static hot path in ``launch/serve.py``.

   With **prefix caching** (default on for attention-only stacks,
   DESIGN.md §12) admission first matches the prompt's longest
   page-aligned cached prefix in the ``PrefixIndex``: hit pages are
   *mapped* into the new table (refcount bump, zero prefill compute for
   the hit region) and ``lm_prefill`` runs only on the uncached tail at
   its logical ``start_pos``.  After prefill the request's own full
   prompt blocks are indexed, so identical or prefix-sharing later
   arrivals — including re-admissions after the original retired — skip
   that compute too.  The match is capped one token short of the prompt
   (the tail is never empty), so every position a request ever writes
   (tail prefill + decode) lands in privately allocated pages — COW is
   unreachable on this path, but a refcount guard before every decode
   chunk enforces it (``pool.cow``) as a backstop.
2. **decode** — ONE jitted ``_decode_chunk`` call loops over
   ``ticks_per_sync`` decode steps for all slots on device: per-row
   ``cache_len`` masks, per-row page-table reads/writes, per-slot
   *traced* sampling params, per-slot PRNG keys advancing in-loop, and
   per-slot ``done`` masks that freeze EOS'd / budget-exhausted rows
   mid-chunk.  One device->host transfer returns the whole token block
   plus per-row emitted counts — the per-token host sync of PR 4 is
   amortized over the chunk.
3. **retirement** — rows that hit EOS or their budget give their pages
   back to the pool, freeing the slot for the next admission.  Admission
   and retirement only ever happen at chunk boundaries.

**Fault tolerance (DESIGN.md §13).**  Every request walks an explicit
lifecycle (``RequestStatus``) and ends in exactly one terminal state.
The layer adds, at each chunk boundary:

* *backpressure* — the waiting queue is bounded (``max_queue``);
  over-capacity submits are REJECTED instead of queued, with
  queue-depth/reject counters in :attr:`fault_stats`;
* *cancellation* — :meth:`cancel` removes a waiting request immediately
  and aborts an active one at the next chunk boundary, releasing its
  pages refcount-correctly (prefix-index entries survive, active tables
  never leak);
* *deadlines* — ``submit(..., deadline_ticks=N)`` expires a request
  that has not finished by ``arrival + N`` ticks, waiting or active;
* *fault isolation* — a non-finite guard inside the decode chunk
  freezes any row whose logits go NaN/inf at that very tick; the host
  quarantines only that row (FAILED, pages freed and purged from the
  prefix index) while co-batched rows keep streaming bit-identically;
  a ``PrefixIndex.verify()`` self-check each step drops a corrupted
  cache (by its reference ledger — no leaks) and keeps serving;
* *crash consistency* — the host-mirrored slot state is snapshotted
  before each chunk; an exception mid-``step()`` restores the snapshot,
  counts the failure, and degrades to ``ticks_per_sync=1`` so the
  engine stays usable (after ``max_chunk_failures`` consecutive
  failures it gives up loudly).

A seeded :class:`~repro.serving.faults.FaultInjector` can be attached to
drive all of these deterministically (chaos tests, ``serve.py --chaos``).

Because every row's attention is masked to its own ``[0, cache_len)``
and its pages are exclusively owned, a sequence that joins mid-stream
computes exactly what it would compute decoded alone — the token-identity
property ``tests/test_serving_engine.py`` pins down for dense and
packed-BSR params at every ``ticks_per_sync``.  Sampling params are
per-request (``submit(..., temperature=, top_k=, top_p=)`` overriding
the engine defaults) and ride the scan as ``(B,)`` vectors with a
*per-slot* PRNG key seeded from the request id, so sampled streams are
also independent of co-batching.  MoE layers serve through the dropless
``models/moe.moe_serve`` — each token routed on its own, no capacity, no
drops — so MoE stacks carry the same guarantee.

Sliding-window layers (``LayerSpec.window``) share the page pools: their
kernels walk only the pages inside the window, and pages behind it stay
allocated until the request ends.
"""
from __future__ import annotations

import dataclasses
import functools
import time
from typing import Dict, List, Optional, Sequence, Set

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from repro.analysis import runtime as analysis_runtime
from repro.configs.base import ModelConfig
from repro.kernels.paged_attention import decode_blocks, decode_pages_per_step
from repro.models import init_caches, layer_specs, lm_decode, lm_prefill
from repro.models.transformer import _select_token_rows

from .pages import NULL_PAGE, PagePool, PrefixIndex
from .scheduler import Request, RequestStatus, Scheduler
from .slo import AdaptiveChunkPolicy, ChunkSignals, percentiles

__all__ = ["ServingEngine"]


@dataclasses.dataclass
class _Slot:
    req: Request
    pages: List[int]
    emitted: List[int]


# Module-level jitted steps with a *static* cfg (ModelConfig is a frozen,
# hashable dataclass): every ServingEngine instance in the process shares
# one compilation cache per (cfg, shapes) — a warm-up engine really warms
# the engine being measured.

@functools.partial(jax.jit, static_argnames=("cfg", "start", "guard"),
                   donate_argnames=("caches",))
def _paged_prefill_step(params, tokens, caches, table, slot, *, cfg,
                        start=0, guard=True):
    """Paged prefill-on-join: one cache-filling pass over a (1, L) prompt
    that writes attention K/V *directly* into the pool pages named by
    ``table`` (1, max_pages) — no contiguous intermediate cache, no
    page-wise copy afterwards.  Recurrent (SSM/xLSTM) layers prefill into
    a scratch single-row cache whose final state lands in row ``slot``
    of the per-slot pool.  ``start > 0`` (static) is the prefix-cache
    tail-only variant: ``tokens`` is the uncached suffix at logical
    positions ``[start, start+L)``, attending over the shared prefix
    pages already mapped into ``table`` (attention-only stacks; the
    engine gates this).  ``guard`` additionally reduces the first-token
    logits to an all-finite flag so admission can quarantine a poisoned
    prefill before it ever occupies a slot.  Returns
    (first_token (1,), ok scalar bool, first-token logits (1, V) fp32,
    new caches)."""
    specs = layer_specs(cfg)
    row_caches = init_caches(cfg, 1, tokens.shape[1], jnp.float32)
    pre = [pool if spec.mixer == "attn" else rc
           for spec, pool, rc in zip(specs, caches, row_caches)]
    logits, new = lm_prefill(
        params, pre, {"tokens": tokens, "page_tables": table}, cfg,
        start_pos=start)
    last = logits[:, -1]
    first = jnp.argmax(last, axis=-1).astype(jnp.int32)
    ok = jnp.all(jnp.isfinite(last)) if guard else jnp.asarray(True)
    out = []
    for spec, pool, nc in zip(specs, caches, new):
        if spec.mixer == "attn":
            out.append(nc)          # pool already holds the prompt pages
        elif nc:
            out.append(jax.tree_util.tree_map(
                lambda P, r: P.at[slot].set(r[0].astype(P.dtype)),
                pool, nc))
        else:
            out.append(pool)
    return first, ok, last, out


@functools.partial(
    jax.jit,
    static_argnames=("cfg", "ticks", "eos_id", "sampled", "guard"),
    donate_argnames=("caches",))
def _decode_chunk(params, caches, tok, cache_len, tables, rngs,
                  temperature, top_k, top_p, budget_left, *,
                  cfg, ticks, eos_id, sampled, guard):
    """Up to ``ticks`` batched decode steps in ONE ``lax.while_loop`` —
    the chunk between two scheduler events (DESIGN.md §10).

    Per-row ``done`` masks freeze rows mid-chunk the moment they emit
    ``eos_id`` or exhaust ``budget_left``: a frozen row keeps its token,
    ``cache_len`` and rng untouched for the rest of the chunk (its
    lockstep decode output is discarded), so the tokens it *did* emit are
    bit-identical to its solo decode no matter where in a chunk it
    finished.  Sampling params are traced ``(B,)`` vectors — co-batched
    requests keep independent temperature/top-k/top-p — and per-row rngs
    advance in-loop only on live sampled rows.  ``sampled=False`` (a
    static host decision: no live slot has temperature > 0) compiles the
    pure-argmax variant with none of the per-row filter argsorts.  Once
    every row is done the loop exits: the remaining ticks emit each row's
    last token as not live.  Each tick updates the KV pools in place (the
    aliased page write of ``ops.paged_kv_write`` on TPU), so no tick
    copies a pool.

    ``guard=True`` (static) adds the non-finite fault gate (DESIGN.md
    §13): a row whose logits contain NaN/inf at some tick is frozen AT
    that tick exactly like a done row — its poisoned token is never
    emitted, its state stops advancing — and flagged in the returned
    ``bad`` vector so the host can quarantine it.  Other rows are
    untouched: their attention never reads the bad row's pages, so their
    streams stay bit-identical.

    Returns (token block (ticks, B), per-row emitted counts (B,),
    per-row bad flags (B,), last tok (B, 1), cache_len (B,),
    rngs (B, 2), MoE counts (2,), caches) in a single host transfer.  The
    MoE counts sum, over the ticks that ran and the MoE layers, the
    routed (token, held expert) pairs and the held experts they touched
    — of every row the layer computed, frozen and free rows included
    (zeros for a stack without experts)."""
    b = tok.shape[0]
    done0 = budget_left <= 0          # free slots ride along frozen
    bad0 = jnp.zeros((b,), bool)

    def live_step(operand):
        tok, clen, rngs, done, bad, left, moe_sum, cs = operand
        logits, cs, moe = lm_decode(
            params, cs, {"tokens": tok, "page_tables": tables}, clen, cfg,
            moe_stats=True)
        last = logits[:, -1]
        if sampled:
            nxt, rngs2 = _select_token_rows(
                last, rngs, temperature, top_k, top_p)
        else:
            nxt, rngs2 = jnp.argmax(last, axis=-1).astype(jnp.int32), rngs
        live = ~done
        if guard:
            # quarantine gate: a poisoned row freezes at THIS tick —
            # nothing it would have emitted leaves the chunk
            finite = jnp.all(jnp.isfinite(last), axis=-1)
            bad = bad | (live & ~finite)
            live = live & finite
            done = done | bad
        # frozen rows: discard the lockstep output, keep all state.
        # (their page writes land at their frozen cache_len inside their
        # own — or the null — page, attended by nobody.)
        emit = jnp.where(live, nxt, tok[:, 0])
        left = jnp.where(live, left - 1, left)
        done = done | (left <= 0)
        if eos_id is not None:
            done = done | (live & (emit == eos_id))
        clen = jnp.where(live, clen + 1, clen)
        rngs = jnp.where(live[:, None], rngs2, rngs)
        tok = jnp.where(live[:, None], nxt[:, None], tok)
        moe_sum = moe_sum + jnp.stack([moe["pairs"], moe["touched"]])
        return (tok, clen, rngs, done, bad, left, moe_sum, cs), (emit, live)

    def running(state):
        i, carry, _, _ = state
        return (i < ticks) & ~jnp.all(carry[3])

    def tick(state):
        i, carry, toks, lives = state
        carry, (emit, live) = live_step(carry)
        return i + 1, carry, toks.at[i].set(emit), lives.at[i].set(live)

    # the all-done skip is the loop's exit, not a lax.cond: a cond arm
    # that returns the pools untouched makes XLA copy them in one of its
    # arms (the live one, in mellum2's chunk).  The MoE counts ride the
    # carry: as stacked scan outputs they changed XLA's buffer assignment
    # of the pools (the qwen chunk's temporaries 4.4 -> 12.5 GB, half its
    # throughput on the chip)
    carry0 = (tok, cache_len, rngs, done0, bad0, budget_left,
              jnp.zeros((2,), jnp.int32), caches)
    n, (tok, cache_len, rngs, _, bad, _, moe, caches), toks, lives = \
        jax.lax.while_loop(running, tick, (
            jnp.int32(0), carry0, jnp.zeros((ticks, b), tok.dtype),
            jnp.zeros((ticks, b), bool)))
    # ticks after every row is done emit each row's last token, not live
    toks = jnp.where(jnp.arange(ticks)[:, None] < n, toks, tok[:, 0])
    counts = jnp.sum(lives.astype(jnp.int32), axis=0)
    return toks, counts, bad, tok, cache_len, rngs, moe, caches


class ServingEngine:
    """Request-level serving: paged KV pool + continuous batching.

    Parameters
    ----------
    params : dense or BSR-packed model pytree (both serve identically
        through the ``layers.matmul`` dispatch).
    cfg : model config.  Paged caches do not support encoder-decoder
        (whisper) stacks.
    num_slots : decode-batch rows; the jitted step shape never changes.
    page_size : tokens per physical KV page.  The compiled TPU kernels
        need a multiple of 8 (the fp32 pool's sublane tiling).
    max_seq_len : longest prompt+generation budget a request may hold;
        fixes the page-table width.
    num_pages : physical pages per layer pool (page 0 is the null page).
        Defaults to every slot holding a full-length sequence.
    ticks_per_sync : decode steps batched into one on-device chunk
        between scheduler events.  1 reproduces the PR-4 tick-per-sync
        loop; larger chunks amortize the host round-trip at the cost of
        admissions/retirements only happening at chunk boundaries.
    chunk_policy : optional :class:`~repro.serving.slo.AdaptiveChunkPolicy`
        making the chunk length adaptive (DESIGN.md §15): each boundary
        picks the next length from the policy's declared level ladder —
        shrinking toward the next slot-free event when arrived waiters
        exist, shrinking under SLO pressure (close hard deadlines, soft
        ttft/tpot targets), growing back to the top level when calm.
        Signals come from host mirrors only (no extra syncs), the
        policy never changes *what* tokens a stream emits (bit-identity
        holds under every policy), and only ``policy.compile_levels``
        chunk variants ever compile.  When set, ``ticks_per_sync``
        serves only as the degraded-fallback baseline.
    aging_ticks : scheduler anti-starvation knob — queue wait promotes
        a request one priority level per this many ticks (None
        disables aging).  See :class:`~repro.serving.scheduler.Scheduler`.
    temperature / top_k / top_p : engine-wide sampling defaults; each
        request may override them at :meth:`submit`.
    prefix_caching : share page-aligned prompt-prefix KV across requests
        through a content-hash :class:`PrefixIndex` (DESIGN.md §12).
        Auto-disabled for stacks with recurrent mixers — their per-slot
        state cannot be resumed from pages alone.
    max_queue : bound on the waiting queue; a :meth:`submit` past it is
        REJECTED (terminal status, counted in :attr:`fault_stats`)
        instead of growing admission latency without limit.  None =
        unbounded (the pre-§13 behavior).
    nan_guard : compile the non-finite logit gate into the decode chunk
        and prefill (DESIGN.md §13), quarantining poisoned rows as
        FAILED.  Off reproduces the unguarded PR-7 hot path —
        ``bench_serving.py`` measures the guard's overhead against it.
    max_chunk_failures : consecutive decode-chunk exceptions tolerated
        (snapshot-restore + degraded single-tick retry) before the
        engine gives up with a RuntimeError.
    fault_injector : optional
        :class:`~repro.serving.faults.FaultInjector` consulted at the
        chunk-boundary hook points (chaos testing).
    """

    def __init__(
        self,
        params,
        cfg: ModelConfig,
        *,
        num_slots: int = 4,
        page_size: int = 8,
        max_seq_len: int = 64,
        num_pages: Optional[int] = None,
        ticks_per_sync: int = 1,
        chunk_policy: Optional[AdaptiveChunkPolicy] = None,
        aging_ticks: Optional[int] = 32,
        temperature: float = 0.0,
        top_k: Optional[int] = None,
        top_p: Optional[float] = None,
        eos_id: Optional[int] = None,
        seed: int = 0,
        prefix_caching: bool = True,
        max_queue: Optional[int] = None,
        nan_guard: bool = True,
        max_chunk_failures: int = 3,
        fault_injector=None,
    ):
        if cfg.enc_layers:
            raise ValueError("encoder-decoder archs are not paged-servable")
        if ticks_per_sync < 1:
            raise ValueError("ticks_per_sync must be >= 1")
        self.params, self.cfg = params, cfg
        self.num_slots = num_slots
        self.ticks_per_sync = ticks_per_sync
        self.configured_ticks_per_sync = ticks_per_sync
        self.chunk_policy = chunk_policy
        self.max_pages = -(-max_seq_len // page_size)
        if num_pages is None:
            num_pages = num_slots * self.max_pages + 1
        self.pool = PagePool(num_pages, page_size)
        self._specs = layer_specs(cfg)
        attn_only = all(spec.mixer == "attn" for spec in self._specs)
        self.prefix_caching = bool(prefix_caching) and attn_only
        self.prefix_index = (PrefixIndex(self.pool)
                             if self.prefix_caching else None)
        self.scheduler = Scheduler(self.pool, self.prefix_index,
                                   max_queue=max_queue,
                                   aging_ticks=aging_ticks)
        self.temperature, self.top_k, self.top_p = temperature, top_k, top_p
        self.eos_id = eos_id
        self.nan_guard = bool(nan_guard)
        self.max_chunk_failures = max_chunk_failures
        self.injector = fault_injector
        self._base_key = jax.random.PRNGKey(seed)
        # prefix-cache observability (see prefix_stats)
        self.prefix_lookups = 0       # admissions that consulted the index
        self.prefix_hit_requests = 0  # admissions with >= 1 block hit
        self.prefix_pages_shared = 0  # hit pages mapped instead of prefilled
        # fault-tolerance observability (see fault_stats)
        self.rejected = 0             # bounded-queue admission rejects
        self.cancelled = 0            # cancel() honored (waiting or active)
        self.expired = 0              # deadline expiries (waiting or active)
        self.failed = 0               # guard quarantines (prefill or decode)
        self.guard_trips = 0          # non-finite detections by the guard
        self.chunk_failures = 0       # decode-chunk exceptions recovered
        self.alloc_failures = 0       # admission allocs that failed + retried
        self.index_drops = 0          # verify() inconsistencies -> cache drop
        self.queue_high_water = 0     # deepest the waiting queue ever got
        self.degraded = False         # fell back to single-tick chunks
        # SLO / adaptive-chunking observability (see slo_stats)
        self.chunks_by_ticks: Dict[int, int] = {}  # committed chunk lengths
        self.chunk_shrinks = 0        # committed chunk shorter than previous
        self.chunk_grows = 0          # committed chunk longer than previous
        self._last_chunk_ticks: Optional[int] = None
        self.last_chunk_error: Optional[str] = None
        self._consec_chunk_failures = 0
        self._cancel_pending: Set[int] = set()
        self._step_progress = False   # terminal/retry event this step

        # device state: page-pool caches per layer; recurrent mixers keep
        # ordinary per-slot rows (their state is O(1) per sequence)
        kvh, hd = cfg.kv_heads, cfg.head_dim_()
        self.caches = []
        for spec, c in zip(self._specs, init_caches(cfg, num_slots, 1,
                                                    jnp.float32)):
            if spec.mixer == "attn":
                # (page, kv head) is one (page_size, dh) tile: the block
                # the paged-attention kernels DMA per grid step
                c = {"k": jnp.zeros((num_pages, kvh, page_size, hd),
                                    jnp.float32),
                     "v": jnp.zeros((num_pages, kvh, page_size, hd),
                                    jnp.float32)}
            self.caches.append(c)

        # host-mirrored per-slot state, pushed to device every chunk
        self._tok = np.zeros((num_slots, 1), np.int32)
        self._cache_len = np.zeros((num_slots,), np.int32)
        self._tables = np.full((num_slots, self.max_pages), NULL_PAGE,
                               np.int32)
        self._rngs = np.zeros((num_slots, 2), np.uint32)
        # per-slot sampling params, traced into the chunk as (B,) vectors
        self._temp = np.zeros((num_slots,), np.float32)
        self._topk = np.zeros((num_slots,), np.int32)      # 0: disabled
        self._topp = np.ones((num_slots,), np.float32)     # 1: disabled
        self.slots: List[Optional[_Slot]] = [None] * num_slots
        self.requests: Dict[int, Request] = {}
        self.tick = 0
        self._next_rid = 0
        self.active_slot_ticks = 0
        self.decode_ticks = 0
        # the TPU decode kernel's grid, per attention kind ("full",
        # "sliding"): per tick, every slot walks decode_blocks(...) blocks
        # of pps pages — all of the table, or those a window can hold;
        # only the pages with positions its layers see are live (all of
        # cache_len, or those inside the window).  Their ratio is the
        # grid's share of real work (host counters from the cache_len
        # mirror, per slot-tick of one layer of the kind)
        pps = decode_pages_per_step(kvh, page_size, hd, jnp.float32,
                                    self.max_pages)
        self._attn_kinds: Dict[str, Optional[int]] = {
            "sliding" if spec.window else "full": spec.window
            for spec in self._specs if spec.mixer == "attn"}
        self._attn_walk = {
            kind: decode_blocks(self.max_pages, pps, page_size, w) * pps
            for kind, w in self._attn_kinds.items()}
        self.attn_live_page_ticks = {k: 0 for k in self._attn_kinds}
        self.attn_walked_page_ticks = {k: 0 for k in self._attn_kinds}
        # MoE layers' routed (token, held expert) pairs and held experts
        # touched, summed over decode ticks and MoE layers (the chunk's
        # own counts, carried on its one pull)
        self.moe_layers = sum(spec.mlp == "moe" for spec in self._specs)
        self.moe_routed_pairs = 0
        self.moe_experts_touched = 0
        # declared host round-trips (analysis_stats / DESIGN.md §14):
        # one "decode_chunk" region per chunk, one "admission" region
        # per admitted request — everything else stays on device
        self.sync_regions: Dict[str, int] = {"admission": 0, "decode_chunk": 0}

    # -- request intake ----------------------------------------------------

    def submit(self, prompt, max_new: int, arrival: int = 0, *,
               temperature: Optional[float] = None,
               top_k: Optional[int] = None,
               top_p: Optional[float] = None,
               deadline_ticks: Optional[int] = None,
               priority: int = 0,
               ttft_target_ticks: Optional[int] = None,
               tpot_target_ticks: Optional[int] = None) -> int:
        """Queue a request and return its rid.  Per-request sampling
        params default to the engine-level settings; pass e.g.
        ``temperature=0.0`` to force a greedy stream inside a sampled
        engine (or vice versa).  ``deadline_ticks`` bounds the request's
        lifetime: unfinished by ``arrival + deadline_ticks`` engine
        ticks, it is EXPIRED (waiting or mid-stream).

        ``priority`` (lower = more urgent, default 0) orders admission
        through the scheduler's aging rule; ``ttft_target_ticks`` /
        ``tpot_target_ticks`` are *soft* SLO targets — the adaptive
        chunk policy steers boundaries to land inside them and
        :meth:`slo_stats` counts the misses, but missing one never
        terminates the request (use ``deadline_ticks`` for that).

        If the bounded waiting queue is full the request is REJECTED —
        terminal immediately, visible via ``engine.requests[rid].status``
        and the ``rejected`` counter — instead of queueing unboundedly;
        callers shed the load rather than hiding it in latency."""
        prompt = np.asarray(prompt, np.int32).reshape(-1)
        if max_new < 1 or prompt.size < 1:
            raise ValueError("need a non-empty prompt and max_new >= 1")
        oob = np.nonzero((prompt < 0) | (prompt >= self.cfg.vocab))[0]
        if oob.size:
            pos = int(oob[0])
            raise ValueError(
                f"prompt token id {int(prompt[pos])} at position {pos} is "
                f"outside [0, {self.cfg.vocab}); out-of-range ids would "
                f"silently gather garbage embedding rows")
        if deadline_ticks is not None and deadline_ticks < 1:
            raise ValueError("deadline_ticks must be >= 1 (or None)")
        if ttft_target_ticks is not None and ttft_target_ticks < 1:
            raise ValueError("ttft_target_ticks must be >= 1 (or None)")
        if tpot_target_ticks is not None and tpot_target_ticks < 1:
            raise ValueError("tpot_target_ticks must be >= 1 (or None)")
        req = Request(rid=self._next_rid, prompt=prompt, max_new=max_new,
                      arrival=arrival, temperature=temperature,
                      top_k=top_k, top_p=top_p,
                      deadline_ticks=deadline_ticks, priority=priority,
                      ttft_target_ticks=ttft_target_ticks,
                      tpot_target_ticks=tpot_target_ticks)
        if self.pool.pages_for(req.budget_tokens) > self.max_pages:
            raise ValueError(
                f"request needs {req.budget_tokens} tokens > "
                f"max_seq_len {self.max_pages * self.pool.page_size}")
        self._next_rid += 1
        self.requests[req.rid] = req
        if self.scheduler.submit(req):
            self.queue_high_water = max(self.queue_high_water,
                                        self.scheduler.pending)
        else:
            self.rejected += 1
        return req.rid

    def cancel(self, rid: int) -> RequestStatus:
        """Cancel a request.  Waiting requests leave the queue
        immediately (CANCELLED, no tokens).  Active requests are marked
        and released at the next chunk boundary — their pages return to
        the pool refcount-correctly (prefix-index entries survive on
        their own references) and the tokens emitted so far are kept.
        Cancelling a terminal request is a no-op.  Returns the request's
        status as of this call (CANCELLED once honored; ACTIVE means the
        cancel is pending the boundary)."""
        req = self.requests.get(rid)
        if req is None:
            raise KeyError(f"unknown request id {rid}")
        if req.terminal:
            return req.status
        waiting = self.scheduler.remove(rid)
        if waiting is not None:
            self.scheduler.finish_waiting(
                waiting, self.tick, RequestStatus.CANCELLED,
                reason="cancelled while queued")
            self.cancelled += 1
            return RequestStatus.CANCELLED
        self._cancel_pending.add(rid)
        return req.status

    def sampling_for(self, req: Request):
        """The effective (temperature, top_k, top_p) a request decodes
        with: its own overrides where set, engine defaults elsewhere.
        (Public so solo-decode verifiers can replicate the stream.)"""
        t = req.temperature if req.temperature is not None else self.temperature
        k = req.top_k if req.top_k is not None else self.top_k
        p = req.top_p if req.top_p is not None else self.top_p
        return (float(t or 0.0), k, p)

    # -- engine loop -------------------------------------------------------

    def _admit(self) -> int:
        free = [i for i, s in enumerate(self.slots) if s is None]
        admitted = self.scheduler.admit(self.tick, len(free))
        # pages promised to this batch's admissions: eviction below must
        # never reclaim a page a sibling's reservation counted on.  (A
        # sibling's hits can only *grow* between here and its own turn —
        # earlier admissions insert fresh blocks — so pinning the match
        # as of now is sufficient.)
        pins: Set[int] = set()
        if self.prefix_index is not None:
            for req in admitted:
                pins.update(self.prefix_index.match(req.prompt))
        count = 0
        for j, req in enumerate(admitted):
            slot = free[0]
            hits: List[int] = []
            if self.prefix_index is not None:
                self.prefix_lookups += 1
                hits = self.prefix_index.match(req.prompt)
            n_hit = len(hits)
            total = self.pool.pages_for(req.budget_tokens)
            need = total - n_hit
            if (self.prefix_index is not None
                    and need > self.pool.free_pages):
                self.prefix_index.evict(need - self.pool.free_pages,
                                        exclude=pins | set(hits))
            # prefill only the uncached tail; the match is capped one
            # token short of the prompt, so the tail is never empty and
            # every write lands past the shared region
            start = n_hit * self.pool.page_size
            with TraceAnnotation("repro.prefill", rid=req.rid,
                                 tokens=req.prompt_len - start,
                                 hit_pages=n_hit):
                try:
                    if self.injector is not None:
                        self.injector.on_alloc(self, need)
                    fresh = self.pool.alloc_pages(need)
                except RuntimeError:
                    # allocator failure (injected or real): nothing of this
                    # request is committed yet — requeue it and the rest of
                    # the batch in order and retry at a later boundary
                    self.alloc_failures += 1
                    self._step_progress = True
                    self.scheduler.requeue(admitted[j:])
                    break
                free.pop(0)
                self.pool.share(hits)                 # map, don't recompute
                pages = hits + fresh
                self._tables[slot] = NULL_PAGE
                self._tables[slot, :total] = pages
                first, ok, _, self.caches = _paged_prefill_step(
                    self.params, jnp.asarray(req.prompt[start:][None]),
                    self.caches, jnp.asarray(self._tables[slot][None]),
                    jnp.asarray(slot, jnp.int32), cfg=self.cfg, start=start,
                    guard=self.nan_guard)
                # ONE declared host round-trip per admission: first token,
                # guard verdict, and the request's decode key in a single
                # batched pull (was three separate syncs)
                with analysis_runtime.sync_region("admission"):
                    self.sync_regions["admission"] += 1
                    first_np, ok_np, rng_np = jax.device_get(
                        (first, ok,
                         jax.random.fold_in(self._base_key, req.rid)))
                if self.nan_guard and not bool(ok_np):
                    # poisoned prefill: quarantine before the request ever
                    # holds a slot — its pages (and any cached blocks that
                    # fed them) must never be mapped again
                    self.guard_trips += 1
                    self.failed += 1
                    self._step_progress = True
                    req.tokens = np.zeros((0,), np.int32)
                    if self.prefix_index is not None:
                        self.prefix_index.drop_pages(pages)
                    self._tables[slot] = NULL_PAGE
                    self.scheduler.retire(
                        req, pages, self.tick, status=RequestStatus.FAILED,
                        reason="non-finite prefill logits (quarantined)")
                    free.insert(0, slot)
                    continue
                self._cache_len[slot] = req.prompt_len
                tok = int(first_np[0])
                req.first_token_time = time.perf_counter()
            req.prefix_hit_pages = n_hit
            if self.prefix_index is not None:
                self.prefix_index.insert(req.prompt, pages)
                if n_hit:
                    self.prefix_hit_requests += 1
                self.prefix_pages_shared += n_hit
            self._tok[slot, 0] = tok
            self._rngs[slot] = np.asarray(rng_np, np.uint32)
            t, k, p = self.sampling_for(req)
            self._temp[slot] = t
            self._topk[slot] = k if k is not None else 0
            self._topp[slot] = p if p is not None else 1.0
            req.admitted_at = self.tick
            req.status = RequestStatus.ACTIVE
            self.slots[slot] = _Slot(req=req, pages=pages, emitted=[tok])
            count += 1
            self._maybe_finish(slot)
        return count

    def _cow_guard(self, active: List[int], ticks: int) -> None:
        """Enforce copy-on-write before a decode chunk: no row may write
        into a page it does not exclusively own.  The standard admission
        path makes this unreachable (decode always writes into a private
        tail page — see _admit), so any trigger means an external holder
        shared a live tail page; the write target is copied to a fresh
        page and the row's table repointed, never the sharer's data."""
        ps = self.pool.page_size
        for i in active:
            s = self.slots[i]
            lo = int(self._cache_len[i])
            hi = lo + ticks                # write positions this chunk
            for idx in range(lo // ps, (hi - 1) // ps + 1):
                if idx >= self.max_pages:
                    break
                pid = int(self._tables[i, idx])
                if pid == NULL_PAGE or self.pool.refcount(pid) == 1:
                    continue
                if (self.pool.free_pages == 0
                        and self.prefix_index is not None):
                    self.prefix_index.evict(1, exclude=set(s.pages))
                new = self.pool.cow(pid)
                for li, spec in enumerate(self._specs):
                    if spec.mixer != "attn":
                        continue
                    c = self.caches[li]
                    self.caches[li] = {
                        **c,
                        "k": c["k"].at[new].set(c["k"][pid]),
                        "v": c["v"].at[new].set(c["v"][pid]),
                    }
                self._tables[i, idx] = new
                s.pages[s.pages.index(pid)] = new

    # -- lifecycle transitions ---------------------------------------------

    def _release_slot(self, i: int, status: RequestStatus,
                      reason: Optional[str] = None) -> None:
        """Terminal transition for an active slot: record the tokens
        emitted so far, clear the slot's host mirrors (table to the null
        page, sampling params to engine-off defaults) and hand the pages
        back through the scheduler (a refcount decrement under sharing —
        prefix-index entries survive on their own references).  FAILED
        rows additionally purge every index entry touching their pages:
        quarantined K/V must never be mapped into a later table."""
        s = self.slots[i]
        s.req.tokens = np.asarray(s.emitted, np.int32)
        s.req.finished_time = time.perf_counter()
        if status is RequestStatus.FAILED and self.prefix_index is not None:
            self.prefix_index.drop_pages(s.pages)
        self.slots[i] = None
        self._tables[i] = NULL_PAGE
        self._cache_len[i] = 0
        self._tok[i, 0] = 0
        self._temp[i], self._topk[i], self._topp[i] = 0.0, 0, 1.0
        self.scheduler.retire(s.req, s.pages, self.tick, status=status,
                              reason=reason)

    def _maybe_finish(self, slot: int) -> None:
        s = self.slots[slot]
        if s is None:
            return
        if (len(s.emitted) >= s.req.max_new
                or (self.eos_id is not None
                    and s.emitted[-1] == self.eos_id)):
            self._release_slot(slot, RequestStatus.FINISHED)

    def _service_cancels(self) -> None:
        """Honor pending cancels at the chunk boundary (the only point
        where slot state is at rest on the host)."""
        if not self._cancel_pending:
            return
        for i, s in enumerate(self.slots):
            if s is not None and s.req.rid in self._cancel_pending:
                self._cancel_pending.discard(s.req.rid)
                self.cancelled += 1
                self._step_progress = True
                self._release_slot(
                    i, RequestStatus.CANCELLED,
                    reason="cancelled mid-stream at chunk boundary")
        # anything left finished on its own before the boundary: drop
        self._cancel_pending = {
            rid for rid in self._cancel_pending
            if not self.requests[rid].terminal}

    def _service_deadlines(self) -> None:
        """Expire overdue requests: waiting ones leave the queue with no
        tokens; active ones are aborted at this boundary keeping their
        partial stream."""
        for _ in self.scheduler.expire(self.tick):
            self.expired += 1
            self._step_progress = True
        for i, s in enumerate(self.slots):
            if s is None:
                continue
            dl = s.req.deadline
            if dl is not None and self.tick >= dl:
                self.expired += 1
                self._step_progress = True
                self._release_slot(
                    i, RequestStatus.EXPIRED,
                    reason=f"deadline (tick {dl}) passed mid-stream")

    def _verify_index(self) -> None:
        """Prefix-index self-check (DESIGN.md §13): on ANY inconsistency
        drop the whole cache — released by the reference ledger, so the
        pool stays exactly conserved even under entry corruption — and
        keep serving uncached.  Active tables are untouched (their pages
        live on the requests' own references), so in-flight streams stay
        bit-identical; only future admissions lose the shared-prefix
        shortcut until the index repopulates."""
        if self.prefix_index is None:
            return
        issues = self.prefix_index.verify()
        if issues:
            self.prefix_index.clear()
            self.index_drops += 1
            self._step_progress = True

    # -- crash-consistent stepping -----------------------------------------

    def _snapshot(self):
        """Copy of every host-mirrored slot vector, taken after the COW
        guard and before the decode chunk: the restore point that keeps
        engine invariants if the chunk raises mid-``step()``."""
        return (self._tok.copy(), self._cache_len.copy(),
                self._tables.copy(), self._rngs.copy(), self._temp.copy(),
                self._topk.copy(), self._topp.copy())

    def _restore(self, snap) -> None:
        (self._tok, self._cache_len, self._tables, self._rngs,
         self._temp, self._topk, self._topp) = (a.copy() for a in snap)

    def _caches_alive(self) -> bool:
        ok = True

        def chk(x):
            nonlocal ok
            if isinstance(x, jax.Array) and x.is_deleted():
                ok = False
        jax.tree_util.tree_map(chk, self.caches)
        return ok

    def _recover_chunk_failure(self, snap, err: Exception) -> None:
        """A decode chunk raised mid-``step()``: restore the snapshot so
        every host mirror matches the last committed chunk boundary,
        fall back to degraded single-tick chunks, and retry on the next
        step.  Page writes the aborted chunk may have landed sit at
        positions >= each row's (restored) cache_len — attended by
        nobody, overwritten by the retry.  If the failure outlived the
        donated cache buffers, or keeps repeating, the engine is
        unrecoverable and says so loudly."""
        self._restore(snap)
        if not self._caches_alive():
            raise RuntimeError(
                "decode chunk failed after its cache donation was "
                "consumed; engine state is unrecoverable") from err
        self.chunk_failures += 1
        self._consec_chunk_failures += 1
        self._step_progress = True
        self.last_chunk_error = repr(err)
        if not self.degraded:
            self.degraded = True
            self.ticks_per_sync = 1       # smallest replayable unit
        if self._consec_chunk_failures > self.max_chunk_failures:
            raise RuntimeError(
                f"{self._consec_chunk_failures} consecutive decode-chunk "
                f"failures (last: {self.last_chunk_error}); giving up: "
                f"{self._state()}") from err

    # -- adaptive chunk length (DESIGN.md §15) -------------------------------

    def _chunk_signals(self, active: List[int]) -> ChunkSignals:
        """Assemble the chunk policy's inputs from host mirrors only —
        scheduler queue, per-slot emitted counts, request targets.
        Nothing here touches the device, so consulting the policy adds
        zero host syncs (the steady-state sync test still counts exactly
        one declared transfer per chunk)."""
        tick = self.tick
        queue_depth = sum(
            1 for r in self.scheduler.waiting if r.arrival <= tick)
        slack = None
        headroom = None
        for i in active:
            s = self.slots[i]
            left = s.req.max_new - len(s.emitted)
            slack = left if slack is None else min(slack, left)
            dl = s.req.deadline
            if dl is not None:
                h = max(1, dl - tick)
                headroom = h if headroom is None else min(headroom, h)
            tp = s.req.tpot_target_ticks
            if tp is not None:
                # the stream flushes only at boundaries: a chunk longer
                # than the per-token target holds tokens past it
                headroom = tp if headroom is None else min(headroom, tp)
        next_arrival = None
        for r in self.scheduler.waiting:
            if r.arrival > tick:
                d = r.arrival - tick
                next_arrival = (d if next_arrival is None
                                else min(next_arrival, d))
                continue
            if r.ttft_target_ticks is not None:
                h = max(1, r.arrival + r.ttft_target_ticks - tick)
                headroom = h if headroom is None else min(headroom, h)
        return ChunkSignals(tick=tick, queue_depth=queue_depth,
                            free_slots=self.num_slots - len(active),
                            min_active_slack=slack, slo_headroom=headroom,
                            next_arrival_in=next_arrival)

    def _next_ticks(self, active: List[int]) -> int:
        """The next chunk's length.  Fixed ``ticks_per_sync`` without a
        policy (and in degraded mode, where recovery already forced the
        single-tick replayable unit); otherwise the policy's pick for
        the current signals — always a member of its declared
        ``compile_levels``, so the jitted ``_decode_chunk`` variants
        stay a small closed set."""
        if self.chunk_policy is None or self.degraded:
            return self.ticks_per_sync
        return self.chunk_policy.next_ticks(self._chunk_signals(active))

    def _count_chunk(self, ticks: int) -> None:
        """Record a COMMITTED chunk length (aborted chunks are restored,
        not counted) and the shrink/grow transition against the previous
        committed chunk — the bench and the check.sh smoke assert the
        adaptive policy actually exercised both directions."""
        self.chunks_by_ticks[ticks] = self.chunks_by_ticks.get(ticks, 0) + 1
        prev = self._last_chunk_ticks
        if prev is not None:
            if ticks < prev:
                self.chunk_shrinks += 1
            elif ticks > prev:
                self.chunk_grows += 1
        self._last_chunk_ticks = ticks

    def _count_attn_pages(self, ticks: int, counts: np.ndarray) -> None:
        """Add a committed chunk to the attention page counters.  A
        slot's context at tick ``t`` is its cache_len before the chunk
        plus the tokens it had emitted by then (``min(t, counts)``: a
        frozen row stays where it stopped).  A window layer's live pages
        run from the page of its first visible position."""
        if not self._attn_walk:
            return
        ps = self.pool.page_size
        ctx = self._cache_len[None, :] + np.minimum(
            np.arange(ticks)[:, None], np.asarray(counts)[None, :])
        for kind, window in self._attn_kinds.items():
            live = (ctx + ps - 1) // ps
            if window is not None:
                live = live - np.maximum(ctx - window + 1, 0) // ps
            self.attn_live_page_ticks[kind] += int(live.sum())
            self.attn_walked_page_ticks[kind] += (
                ticks * self.num_slots * self._attn_walk[kind])

    def _chunk_call(self, left: np.ndarray, ticks: int):
        """(args, static kwargs) of the ``_decode_chunk`` call for the
        current host mirrors and per-slot budgets ``left``."""
        args = (self.params, self.caches, jnp.asarray(self._tok),
                jnp.asarray(self._cache_len), jnp.asarray(self._tables),
                jnp.asarray(self._rngs), jnp.asarray(self._temp),
                jnp.asarray(self._topk), jnp.asarray(self._topp),
                jnp.asarray(left))
        static = dict(cfg=self.cfg, ticks=ticks, eos_id=self.eos_id,
                      sampled=bool(np.any(self._temp > 0.0)),
                      guard=self.nan_guard)
        return args, static

    def lower_decode_chunk(self):
        """The engine's decode chunk of ``ticks_per_sync`` steps lowered
        for its current state (``jax.stages.Lowered``) — what ``step()``
        would compile, for inspecting the program (e.g. that the Pallas
        kernels are in it) without running it."""
        args, static = self._chunk_call(
            np.zeros((self.num_slots,), np.int32), self.ticks_per_sync)
        return _decode_chunk.lower(*args, **static)

    def prefill_logits(self, prompt) -> np.ndarray:
        """First-token logits (V,) fp32 of ``prompt`` through the
        admission path itself — the jitted paged prefill, writing into
        freshly allocated pages of this engine's pool (and the recurrent
        rows of a free slot), which go straight back to the pool.  For
        checking the serving path against a reference forward."""
        free = [i for i, s in enumerate(self.slots) if s is None]
        if not free:
            raise RuntimeError("prefill_logits needs a free slot")
        prompt = np.asarray(prompt, np.int32).reshape(-1)
        pages = self.pool.alloc_pages(self.pool.pages_for(prompt.size))
        table = np.full((1, self.max_pages), NULL_PAGE, np.int32)
        table[0, :len(pages)] = pages
        try:
            _, _, logits, self.caches = _paged_prefill_step(
                self.params, jnp.asarray(prompt[None]), self.caches,
                jnp.asarray(table), jnp.asarray(free[0], jnp.int32),
                cfg=self.cfg, guard=self.nan_guard)
        finally:
            self.pool.free(pages)
        return np.asarray(jax.device_get(logits))[0]

    def step(self) -> int:
        """One scheduler event: fault/lifecycle servicing, admission,
        then ONE on-device chunk of ``ticks_per_sync`` decode steps
        (or the adaptive policy's pick, see ``_next_ticks``).
        Returns the number of requests admitted this event.

        Each phase is a ``repro.*`` span on the profiler's trace (a
        ``jax.profiler.TraceAnnotation``, free unless a profiler is
        recording): ``repro.step`` around all of it, then
        ``repro.verify_index``, ``repro.admit`` (one ``repro.prefill``
        per admitted request), ``repro.cow_guard``, ``repro.dispatch``
        (host->device copies and the chunk's enqueue),
        ``repro.sync.decode_chunk`` (``sync_region``) and
        ``repro.commit``."""
        with TraceAnnotation("repro.step", tick=self.tick):
            return self._step()

    def _step(self) -> int:
        self._step_progress = False
        if self.injector is not None:
            self.injector.on_step_start(self)
        with TraceAnnotation("repro.verify_index"):
            self._verify_index()
        self._service_cancels()
        self._service_deadlines()
        with TraceAnnotation("repro.admit", free=self.slots.count(None)):
            admitted = self._admit()
        active = [i for i, s in enumerate(self.slots) if s is not None]
        if not active:
            self.tick += 1
            return admitted
        ticks = self._next_ticks(active)
        with TraceAnnotation("repro.cow_guard"):
            self._cow_guard(active, ticks)
        left = np.zeros((self.num_slots,), np.int32)
        for i in active:
            left[i] = self.slots[i].req.max_new - len(self.slots[i].emitted)
        snap = self._snapshot()
        try:
            if self.injector is not None:
                self.injector.on_chunk_start(self, active, ticks)
            with TraceAnnotation("repro.dispatch", ticks=ticks,
                                 active=len(active)):
                args, static = self._chunk_call(left, ticks)
                toks, counts, bad, tok, clen, rngs, moe, caches = \
                    _decode_chunk(*args, **static)
        except Exception as err:
            self._recover_chunk_failure(snap, err)
            self.tick += 1
            return admitted
        self._consec_chunk_failures = 0
        self.caches = caches
        # ONE declared host round-trip per decode chunk: every per-slot
        # output in a single batched pull (device_get returns numpy)
        with analysis_runtime.sync_region("decode_chunk"):
            self.sync_regions["decode_chunk"] += 1
            toks, counts, bad, tok, clen, rngs, moe = jax.device_get(
                (toks, counts, bad, tok, clen, rngs, moe))
        with TraceAnnotation("repro.commit"):
            self._count_attn_pages(ticks, counts)
            self.moe_routed_pairs += int(moe[0])
            self.moe_experts_touched += int(moe[1])
            self._tok = np.array(tok)
            self._cache_len = np.array(clen)
            self._rngs = np.array(rngs)
            for i in active:
                self.slots[i].emitted.extend(
                    int(t) for t in toks[:int(counts[i]), i])
                if bad[i]:
                    self.guard_trips += 1
                    self.failed += 1
                    self._step_progress = True
                    self._release_slot(
                        i, RequestStatus.FAILED,
                        reason="non-finite decode logits (quarantined)")
                else:
                    self._maybe_finish(i)
            self.active_slot_ticks += int(counts.sum())
            self.decode_ticks += ticks
            self.tick += ticks
            self._count_chunk(ticks)
        return admitted

    @property
    def prefix_stats(self) -> Dict[str, int]:
        """Prefix-cache counters: lookups / hit requests / pages shared
        (mapped instead of prefilled), blocks currently indexed, COW
        copies served, index evictions, and the refcount high-water mark
        (most tables any single page ever appeared in)."""
        idx = self.prefix_index
        return {
            "enabled": int(self.prefix_caching),
            "lookups": self.prefix_lookups,
            "hit_requests": self.prefix_hit_requests,
            "pages_shared": self.prefix_pages_shared,
            "blocks_indexed": len(idx) if idx is not None else 0,
            "evictions": idx.evictions if idx is not None else 0,
            "cow_copies": self.pool.cow_copies,
            "ref_high_water": self.pool.ref_high_water,
        }

    @property
    def fault_stats(self) -> Dict[str, int]:
        """Fault-tolerance counters (DESIGN.md §13), exposed like
        :attr:`prefix_stats`: queue depth/bound/high-water plus one
        counter per lifecycle/fault event.  ``max_queue`` 0 means
        unbounded."""
        return {
            "nan_guard": int(self.nan_guard),
            "queue_depth": self.scheduler.pending,
            "queue_high_water": self.queue_high_water,
            "max_queue": self.scheduler.max_queue or 0,
            "rejected": self.rejected,
            "cancelled": self.cancelled,
            "expired": self.expired,
            "failed": self.failed,
            "guard_trips": self.guard_trips,
            "chunk_failures": self.chunk_failures,
            "alloc_failures": self.alloc_failures,
            "index_drops": self.index_drops,
            "degraded": int(self.degraded),
        }

    def slo_stats(self) -> Dict[str, object]:
        """SLO / adaptive-chunking observability (DESIGN.md §15),
        exposed like :attr:`prefix_stats` / :attr:`fault_stats`.

        Chunk side: whether a policy is attached, the declared compile
        set of chunk lengths, a histogram of committed chunk lengths,
        and shrink/grow transition counts.  Request side: soft-target
        miss counters plus per-priority-class latency aggregates over
        every terminal request that held a slot — TTFT p50/p99 in ticks
        (admission tick minus arrival; the first token lands at
        admission) and mean ticks-per-token after the first.  Computed
        lazily by scanning ``scheduler.finished`` — nothing here is on
        the hot path."""
        policy = self.chunk_policy
        ttft_miss = tpot_miss = 0
        by_prio: Dict[int, Dict[str, List[float]]] = {}
        for r in self.scheduler.finished:
            ttft_miss += int(r.ttft_missed)
            tpot_miss += int(r.tpot_missed)
            if r.admitted_at is None:
                continue
            cls = by_prio.setdefault(r.priority, {"ttft": [], "tpot": []})
            cls["ttft"].append(float(r.ttft_ticks))
            tpot = r.tpot_ticks
            if tpot is not None:
                cls["tpot"].append(float(tpot))
        classes = {}
        for prio in sorted(by_prio):
            cls = by_prio[prio]
            pct = percentiles(cls["ttft"])
            classes[prio] = {
                "requests": len(cls["ttft"]),
                "ttft_ticks_p50": pct["p50"],
                "ttft_ticks_p99": pct["p99"],
                "tpot_ticks_mean": (float(np.mean(cls["tpot"]))
                                    if cls["tpot"] else 0.0),
            }
        return {
            "adaptive": int(policy is not None),
            "chunk_levels": list(policy.compile_levels) if policy is not None
            else [self.configured_ticks_per_sync],
            "chunks_by_ticks": dict(sorted(self.chunks_by_ticks.items())),
            "chunk_shrinks": self.chunk_shrinks,
            "chunk_grows": self.chunk_grows,
            "aging_ticks": self.scheduler.aging_ticks or 0,
            "ttft_target_misses": ttft_miss,
            "tpot_target_misses": tpot_miss,
            "by_priority": classes,
        }

    def analysis_stats(self) -> Dict[str, object]:
        """Runtime counters backing the static analyzer's dynamic claims
        (DESIGN.md §14), exposed like :attr:`prefix_stats` /
        :attr:`fault_stats`: jit cache sizes for the two hot-path entry
        points (steady state must not grow them), the process-wide
        compile-event count, and this engine's declared host sync
        regions — one ``decode_chunk`` region per chunk, one
        ``admission`` region per admitted request.  Tests snapshot this
        before and after traffic to prove "0 recompiles, <=1 transfer
        per chunk"."""
        return {
            "compile_caches": {
                "_decode_chunk": analysis_runtime.cache_size(_decode_chunk),
                "_paged_prefill_step": analysis_runtime.cache_size(_paged_prefill_step),
            },
            "compile_events": analysis_runtime.compile_events(),
            "sync_regions": dict(self.sync_regions),
        }

    def release_prefix_cache(self) -> int:
        """Drop every cached prefix block (e.g. to fully drain the pool);
        pages still mapped by active requests survive through the
        requests' own references.  Returns entries released."""
        if self.prefix_index is None:
            return 0
        return self.prefix_index.clear()

    def _state(self) -> str:
        """One-line engine state for stall diagnostics."""
        waiting = [(r.rid, r.budget_tokens,
                    self.scheduler.pages_needed(r), r.arrival, r.priority)
                   for r in self.scheduler.waiting]
        active = [(s.req.rid, len(s.emitted), s.req.max_new)
                  for s in self.slots if s is not None]
        return (f"tick={self.tick} "
                f"waiting(rid,budget_tok,pages,arrival,prio)={waiting} "
                f"active(rid,emitted,max_new)={active} "
                f"pool={self.pool.free_pages}/{self.pool.num_pages - 1} "
                f"pages free (page_size={self.pool.page_size}, "
                f"max {self.max_pages} pages/request) "
                f"prefix_cache={self.prefix_stats} "
                f"faults={self.fault_stats}")

    def run(self, max_ticks: int = 100_000) -> Dict[int, Request]:
        """Drive chunks until every submitted request is terminal.
        Returns every terminal request by rid — FINISHED streams plus
        any CANCELLED / EXPIRED / FAILED / REJECTED ones (check
        ``.status``; partial tokens are kept where the request ever held
        a slot)."""
        while self.scheduler.pending or any(s is not None for s in self.slots):
            if self.tick >= max_ticks:
                raise RuntimeError(
                    f"engine stalled after {max_ticks} ticks: {self._state()}")
            # a tick that starts fully idle with a due request and admits
            # nothing can never make progress (no pages will ever free) —
            # unless this step made OTHER progress: a terminal transition
            # (cancel/expire/reject), a transient allocator failure being
            # retried, or a recovered chunk fault
            idle = all(s is None for s in self.slots)
            # priority order means the queue head may not be the earliest
            # arrival — "due" is ANY arrived waiter
            due = any(r.arrival <= self.tick
                      for r in self.scheduler.waiting)
            admitted = self.step()
            if idle and due and not admitted and not self._step_progress:
                head = self.scheduler.effective_head(self.tick)
                avail = self.pool.free_pages
                if self.prefix_index is not None:
                    avail += self.prefix_index.evictable_pages()
                raise RuntimeError(
                    "admission stalled: head request "
                    f"rid={head.rid} needs "
                    f"{self.scheduler.pages_needed(head)} pages "
                    f"({head.budget_tokens} tokens) but the drained pool "
                    f"only has {avail} (incl. evictable cache); "
                    f"{self._state()}")
        return {r.rid: r for r in self.scheduler.finished}

    @property
    def slot_utilization(self) -> float:
        if not self.decode_ticks:
            return 0.0
        return self.active_slot_ticks / (self.decode_ticks * self.num_slots)
