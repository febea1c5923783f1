"""Serving launcher: batched prefill + on-device greedy decode loop.

``python -m repro.launch.serve --arch qwen1.5-0.5b --smoke --batch 4
--prompt-len 16 --gen 32``

The hot path is two jitted calls (DESIGN.md §7):

1. **prefill** — one ``lm_prefill`` pass over the whole prompt fills every
   KV/SSM cache and yields the first generated token (argmax on device);
2. **decode** — one ``lm_generate`` call runs the entire greedy loop as a
   ``jax.lax.scan`` with the caches in the carry: N tokens, zero host
   round-trips, one device->host transfer at the end.

``--pruned <sparsity>`` turns on the sparse execution layer (DESIGN.md
§6/§7): the model is knapsack-pruned at ``--block bk,bn`` tile
granularity, packed to BSR, and every matmul on both calls skips pruned
tiles via the ``models/layers.matmul`` dispatch (zero-skipping ref path
on CPU, compiled Pallas on TPU; MoE experts go through the fused
flattened-planes kernel).  On a real fleet, add ``--mesh single|multi``
for the production placement.

``--stream`` switches to request-level serving (DESIGN.md §9/§10):
ragged prompts arrive every ``--arrive-every`` ticks and flow through
the continuous-batching engine — paged KV pool (prompt K/V written
straight into the request's pages at prefill), ``--ticks-per-sync``
decode steps scanned on device between scheduler events, EOS'd slots
re-admitted from the queue.  ``--request-temperatures`` cycles
per-request sampling temperatures through the stream (co-batched
requests sample independently).  Each finished stream is verified
token-identical against its solo decode — including sampled streams,
which are replicated with the engine's per-slot key derivation.
"""
import argparse
import sys
import time


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--gen", type=int, default=32)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--temperature", type=float, default=0.0,
                    help="sampling temperature; 0 = greedy argmax")
    ap.add_argument("--top-k", type=int, default=None,
                    help="sample from the k highest-probability tokens")
    ap.add_argument("--top-p", type=float, default=None,
                    help="nucleus sampling probability mass")
    ap.add_argument("--eos-id", type=int, default=None,
                    help="stop token: finished rows emit it and the scan "
                         "body early-exits once all rows are done")
    ap.add_argument("--pruned", type=float, default=None, metavar="SPARSITY",
                    help="knapsack-prune to this structure sparsity and "
                         "serve through the zero-skipping BSR path")
    ap.add_argument("--block", type=str, default="128,128", metavar="BK,BN",
                    help="pruning tile shape (MXU-aligned on TPU)")
    ap.add_argument("--min-size", type=int, default=4096,
                    help="smallest weight (elements) eligible for pruning")
    ap.add_argument("--stream", action="store_true",
                    help="continuous batching over a streamed request "
                         "arrival pattern (paged KV pool, prefill-on-join)")
    ap.add_argument("--requests", type=int, default=6,
                    help="[--stream] number of requests in the stream")
    ap.add_argument("--arrive-every", type=int, default=2,
                    help="[--stream] ticks between request arrivals")
    ap.add_argument("--page-size", type=int, default=8,
                    help="[--stream] tokens per physical KV page")
    ap.add_argument("--ticks-per-sync", type=int, default=4,
                    help="[--stream] decode steps batched into one "
                         "on-device chunk between scheduler events "
                         "(1 = host sync per token)")
    ap.add_argument("--adaptive", action="store_true",
                    help="[--stream] SLO-aware adaptive chunking "
                         "(DESIGN.md §15): the chunk length becomes a "
                         "policy pick from a geometric level ladder "
                         "topped at --ticks-per-sync — shrinking toward "
                         "slot-free events and SLO edges when the queue "
                         "is hot, growing back when calm.  Requests get "
                         "alternating priority classes with soft TTFT "
                         "targets on the interactive class; the run "
                         "fails unless at least one chunk-shrink event "
                         "fired and every stream still verifies "
                         "bit-identical to its solo decode")
    ap.add_argument("--request-temperatures", type=str, default=None,
                    metavar="T0,T1,...",
                    help="[--stream] per-request sampling temperatures, "
                         "cycled over the stream (overrides --temperature "
                         "per request; 0 = greedy)")
    ap.add_argument("--shared-prefix", action="store_true",
                    help="[--stream] all requests share a long common "
                         "prompt prefix (and the first two share the FULL "
                         "prompt) to exercise the prefix cache: hit "
                         "requests map the cached pages and prefill only "
                         "their tail (DESIGN.md §12); streams still "
                         "verify token-identical vs solo decode")
    ap.add_argument("--chaos", action="store_true",
                    help="seeded fault-injection smoke (DESIGN.md §13): "
                         "serve a stream under a deterministic plan of "
                         "NaN poisoning, allocator failure, index "
                         "corruption, a chunk crash, a cancel, a deadline "
                         "and queue-overflow rejects; verify every "
                         "request reaches a terminal status, non-faulted "
                         "streams stay bit-identical to solo decode, and "
                         "the page pool drains exactly")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.configs import get_config, make_smoke
    from repro.launch.compile_cache import enable_compile_cache
    from repro.models import init_caches, init_params, lm_generate, lm_prefill
    from repro.models.transformer import encode_kv_caches, encoder_forward

    enable_compile_cache()
    cfg = get_config(args.arch)
    if args.smoke:
        cfg = make_smoke(cfg)

    # independent streams for weights / benchmark inputs — reusing one key
    # would correlate the random prompt (and encoder frames) with the
    # weight draw and skew every benchmark number derived from them
    key_params, key_prompt, key_frames, key_sample = jax.random.split(
        jax.random.PRNGKey(args.seed), 4)
    params = init_params(key_params, cfg)

    if args.pruned is not None:
        from repro.core import BlockingSpec
        from repro.kernels.ops import on_tpu
        from repro.sparse import knapsack_prune, pack_params, sparsity_summary

        bk, bn = (int(t) for t in args.block.split(","))
        sel = knapsack_prune(
            params, sparsity=args.pruned,
            blocking=BlockingSpec(bk=bk, bn=bn), min_size=args.min_size,
        )
        params = pack_params(params, sel.masks, sel.structures)
        summ = sparsity_summary(params)
        path = "pallas" if on_tpu() else "ref (CPU)"
        print(f"pruned: kept {sel.kept}/{sel.total} structures "
              f"({sel.result.method}, feasible={sel.result.feasible}); "
              f"BSR density {summ['density']:.2f} "
              f"({summ['nnz_blocks']}/{summ['total_blocks']} blocks), "
              f"dispatch={path}")
        for p, d in sorted(summ["per_path"].items())[:4]:
            print(f"  {p}: density {d:.2f}")

    if args.chaos:
        return _run_chaos(args, cfg, params)
    if args.stream:
        return _run_stream(args, cfg, params)

    b, plen = args.batch, args.prompt_len
    max_len = max(plen + args.gen, 1)
    caches = init_caches(cfg, b, max_len, jnp.float32)

    prompt = jax.random.randint(key_prompt, (b, max(plen, 1)), 0, cfg.vocab)
    if cfg.enc_layers:
        frames = jax.random.normal(key_frames, (b, cfg.enc_frames, cfg.d_model))
        enc = encoder_forward(params, frames, cfg)
        caches = encode_kv_caches(params, enc, cfg, caches)

    # prefill: ONE lm_prefill call over the whole prompt fills the caches
    # and produces the first token — not prompt_len decode steps
    @jax.jit
    def prefill(p, c, toks):
        logits, c = lm_prefill(p, c, {"tokens": toks}, cfg)
        tok = jnp.argmax(logits[:, -1], axis=-1)[:, None].astype(jnp.int32)
        return tok, c

    # decode: ONE lm_generate call (lax.scan) emits every token on device;
    # sampling (temperature/top-k/top-p) and EOS early-exit run inside the
    # scan — still zero host round-trips per token
    sample_key = key_sample
    generate = jax.jit(
        lambda p, c, t, l: lm_generate(
            p, c, t, l, args.gen, cfg,
            temperature=args.temperature, top_k=args.top_k,
            top_p=args.top_p, eos_id=args.eos_id, key=sample_key))

    # warm both calls once (trace + XLA compile) so the printed numbers
    # measure steady-state serving, not compilation
    if plen > 0:
        wtok, wcaches = prefill(params, caches, prompt)
    else:
        wtok, wcaches = jnp.zeros((b, 1), jnp.int32), caches
    jax.block_until_ready(
        generate(params, wcaches, wtok, jnp.asarray(plen, jnp.int32)))

    t0 = time.time()
    if plen > 0:
        tok, caches = prefill(params, caches, prompt)
    else:
        # empty prompt: start generation from token 0 (a stand-in BOS)
        tok = jnp.zeros((b, 1), jnp.int32)
    jax.block_until_ready(tok)
    t_prefill = time.time() - t0

    t1 = time.time()
    tokens, caches = generate(params, caches, tok, jnp.asarray(plen, jnp.int32))
    gen = np.asarray(tokens)          # the single host transfer
    dt_dec = max(time.time() - t1, 1e-9)
    dt = max(time.time() - t0, 1e-9)

    print(f"generated {gen.shape} tokens in {dt:.2f}s "
          f"(prefill {t_prefill * 1e3:.1f}ms, decode "
          f"{args.gen * b / dt_dec:.1f} tok/s aggregate)")
    if gen.shape[1]:
        print("sample:", gen[0][:16])
    return 0


def _run_stream(args, cfg, params) -> int:
    """Continuous-batching demo: ragged prompts arrive over time, flow
    through the paged-KV engine in ``--ticks-per-sync`` on-device decode
    chunks, and every finished stream — greedy OR sampled — is checked
    token-identical against its solo decode (sampled streams are
    replicated with the engine's per-slot fold_in(base, rid) keys)."""
    import time

    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.models import init_caches, lm_generate, lm_prefill
    from repro.serving import AdaptiveChunkPolicy, ServingEngine

    plen, gen = max(args.prompt_len, 1), args.gen
    rng = np.random.default_rng(args.seed)
    if args.shared_prefix:
        # long common prefix + short unique tails; requests 0 and 1 get
        # the IDENTICAL full prompt — duplicate prompts must still get
        # unique rids and per-request fold_in keys (verified below)
        tail = max(plen // 4, 1)
        pre = max(plen - tail, 0)
        prefix = rng.integers(0, cfg.vocab, size=pre).astype(np.int32)
        prompts = [np.concatenate([
            prefix, rng.integers(0, cfg.vocab, size=tail).astype(np.int32)])
            for _ in range(args.requests)]
        if args.requests >= 2:
            prompts[1] = prompts[0].copy()
        lens = np.asarray([len(p) for p in prompts])
    else:
        lens = rng.integers(max(1, plen // 2), plen + 1, size=args.requests)
        prompts = [rng.integers(0, cfg.vocab, size=int(l)).astype(np.int32)
                   for l in lens]
    req_temps = None
    if args.request_temperatures:
        req_temps = [float(t) for t in args.request_temperatures.split(",")]

    # adaptive mode: geometric chunk-level ladder topped at the fixed
    # setting, alternating priority classes, soft TTFT targets on the
    # interactive (priority 0) class — the smoke must see a shrink
    policy = None
    if args.adaptive:
        levels = sorted({1, args.ticks_per_sync}
                        | {2 ** k for k in range(10)
                           if 2 ** k < args.ticks_per_sync})
        policy = AdaptiveChunkPolicy(levels=tuple(levels))

    def build():
        eng = ServingEngine(
            params, cfg, num_slots=args.batch, page_size=args.page_size,
            max_seq_len=plen + gen, ticks_per_sync=args.ticks_per_sync,
            chunk_policy=policy, temperature=args.temperature,
            top_k=args.top_k, top_p=args.top_p, eos_id=args.eos_id,
            seed=args.seed)
        for i, p in enumerate(prompts):
            kw = {}
            if req_temps is not None:
                kw["temperature"] = req_temps[i % len(req_temps)]
            if args.adaptive:
                kw["priority"] = i % 2
                if i % 2 == 0:
                    kw["ttft_target_ticks"] = 2 * args.ticks_per_sync
            eng.submit(p, gen, arrival=i * args.arrive_every, **kw)
        return eng

    # warm the jitted prefill/chunk shapes so the printed numbers are
    # steady-state (same discipline as the static path above)
    build().run()
    engine = build()

    t0 = time.time()
    done = engine.run()
    dt = max(time.time() - t0, 1e-9)
    emitted = sum(len(r.tokens) for r in done.values())
    print(f"streamed {len(done)} requests (ragged prompts "
          f"{int(lens.min())}..{int(lens.max())}, arrivals every "
          f"{args.arrive_every} ticks, {args.ticks_per_sync} ticks/sync) "
          f"in {dt:.2f}s: {emitted} tokens, "
          f"{emitted / dt:.1f} tok/s aggregate, slot utilization "
          f"{engine.slot_utilization:.2f}, "
          f"{engine.pool.num_pages}x{args.page_size}-token pages/layer")
    joins = [r.admitted_at for r in done.values()]
    print(f"  joins at ticks {sorted(joins)}; "
          f"pool free pages after drain: {engine.pool.free_pages}")
    st = engine.prefix_stats
    if st["enabled"]:
        print(f"  prefix cache: {st['hit_requests']}/{st['lookups']} "
              f"admissions hit, {st['pages_shared']} pages mapped instead "
              f"of prefilled, {st['blocks_indexed']} blocks resident, "
              f"{st['cow_copies']} COW copies, refcount high-water "
              f"{st['ref_high_water']}")
    if args.adaptive:
        slo = engine.slo_stats()
        print(f"  slo: chunks_by_ticks={slo['chunks_by_ticks']} "
              f"shrinks={slo['chunk_shrinks']} grows={slo['chunk_grows']} "
              f"ttft_misses={slo['ttft_target_misses']} "
              f"by_priority={slo['by_priority']}")
        if slo["chunk_shrinks"] < 1:
            print("stream verify FAILED: adaptive run never shrank a "
                  "chunk (policy inert)")
            return 1
        extra = set(slo["chunks_by_ticks"]) - set(slo["chunk_levels"])
        if extra:
            print(f"stream verify FAILED: undeclared chunk lengths "
                  f"{sorted(extra)} ran (compile set violated)")
            return 1
    if args.shared_prefix:
        # dedupe safety: N identical full prompts must still be distinct
        # requests — unique rids, and (for sampled runs) independent
        # fold_in(base, rid) keys; the per-rid solo replication below is
        # what proves each stream used its own key
        rids = sorted(done)
        assert len(set(rids)) == len(done), f"duplicate rids: {rids}"
        if st["enabled"] and st["hit_requests"] == 0:
            print("stream verify FAILED: shared-prefix run produced no "
                  "prefix-cache hits")
            return 1

    # token-identity vs solo decode through the static hot path.  Each
    # request replays with ITS effective sampling params and the engine's
    # per-slot key (fold_in(base, rid)) — so mixed greedy/sampled streams
    # verify too.  Retraces per distinct (prompt length, sampling combo).
    prefill = jax.jit(lambda p, c, t: lm_prefill(p, c, {"tokens": t}, cfg))
    base_key = jax.random.PRNGKey(args.seed)
    # sampling params are static (python-level branches in lm_generate):
    # jit's own cache keys one compilation per distinct combo
    generate = jax.jit(
        lambda pp, c, tok, l, key, t, k, p: lm_generate(
            pp, c, tok, l, gen, cfg, temperature=t, top_k=k, top_p=p,
            eos_id=args.eos_id, key=key),
        static_argnums=(5, 6, 7))

    bad = 0
    for rid, req in sorted(done.items()):
        toks = jnp.asarray(req.prompt[None])
        caches = init_caches(cfg, 1, req.prompt_len + gen, jnp.float32)
        logits, caches = prefill(params, caches, toks)
        first = jnp.argmax(logits[:, -1], -1)[:, None].astype(jnp.int32)
        t, k, p = engine.sampling_for(req)
        want, _ = generate(
            params, caches, first, jnp.asarray(req.prompt_len, jnp.int32),
            jax.random.fold_in(base_key, rid), t, k, p)
        # a stream may only be short of --gen if it legitimately hit EOS
        # — otherwise a prefix match would mask dropped trailing tokens
        short_ok = (args.eos_id is not None and len(req.tokens) >= 1
                    and req.tokens[-1] == args.eos_id)
        want = np.asarray(want)[0][:len(req.tokens)]
        if not np.array_equal(req.tokens, want) or (
                len(req.tokens) != gen and not short_ok):
            bad += 1
            print(f"  request {rid}: MISMATCH vs solo decode "
                  f"(got {len(req.tokens)} toks {req.tokens[:8]}.. "
                  f"want {gen} toks {want[:8]}..)")
    if bad:
        print(f"stream verify FAILED: {bad}/{len(done)} requests diverged")
        return 1
    n_sampled = sum(1 for r in done.values()
                    if engine.sampling_for(r)[0] > 0)
    print(f"  verify OK: all {len(done)} streams token-identical to "
          f"solo decode ({n_sampled} sampled, {len(done) - n_sampled} "
          "greedy)")
    return 0


def _run_chaos(args, cfg, params) -> int:
    """Seeded fault-injection smoke (DESIGN.md §13): a streamed workload
    plus a deterministic plan of every fault kind, a cancel, a deadline
    and queue-overflow rejects.  Verifies the engine's fault contract
    end-to-end: every request terminal, the faulted/cancelled/expired
    streams carrying correct solo-prefix partials, every NON-faulted
    stream bit-identical to its solo decode, all fault counters
    registering, and the page pool draining exactly."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.models import init_caches, lm_generate, lm_prefill
    from repro.serving import (FaultInjector, RequestStatus, ServingEngine,
                               alloc_failure, chunk_exception,
                               index_corruption, nan_logit)

    plen, gen = max(args.prompt_len, 2), max(args.gen, 12)
    rng = np.random.default_rng(args.seed)
    lens = rng.integers(max(2, plen // 2), plen + 1, size=args.requests)
    prompts = [rng.integers(0, cfg.vocab, size=int(l)).astype(np.int32)
               for l in lens]
    victim = 1 % args.requests          # rid the NaN fault targets

    def build(injector=None, max_queue=None):
        eng = ServingEngine(
            params, cfg, num_slots=args.batch, page_size=args.page_size,
            max_seq_len=plen + gen, ticks_per_sync=args.ticks_per_sync,
            eos_id=args.eos_id, seed=args.seed, max_queue=max_queue,
            fault_injector=injector)
        for i, p in enumerate(prompts):
            eng.submit(p, gen, arrival=i * args.arrive_every)
        return eng

    # warm the jitted shapes faults will replay through — including the
    # degraded ticks_per_sync=1 chunk the crash recovery falls back to
    build().run()
    if args.ticks_per_sync != 1:
        w = build()
        w.ticks_per_sync = 1
        w.run()

    plan = [
        alloc_failure(0),                 # admission unwound + retried
        index_corruption(3),              # caught by verify() -> cache drop
        nan_logit(6, rid=victim),         # quarantined, others untouched
        chunk_exception(9),               # snapshot restore + degraded mode
    ]
    inj = FaultInjector(plan, seed=args.seed)
    engine = build(injector=inj, max_queue=args.requests + 2)
    # lifecycle extras: one request cancelled while queued, one that
    # cannot finish inside its deadline, and two rejects past the bound
    rid_cancel = engine.submit(prompts[0], gen, arrival=10_000)
    rid_expire = engine.submit(
        prompts[-1], gen, arrival=0, deadline_ticks=max(3, gen // 2))
    rejected = [engine.submit(prompts[0], gen, arrival=0) for _ in range(3)]
    engine.cancel(rid_cancel)

    t0 = time.time()
    done = engine.run()
    dt = max(time.time() - t0, 1e-9)
    stats = engine.fault_stats
    print(f"chaos: {len(done)} requests terminal in {dt:.2f}s under "
          f"{len(plan)} injected faults + cancel/deadline/overflow")
    print(f"  statuses: "
          f"{sorted((r.rid, r.status.value) for r in done.values())}")
    print(f"  fault counters: {stats}")
    print(f"  injector fired: {[(k, t) for k, t, _ in inj.fired]}")

    failures = []

    def check(cond, msg):
        if not cond:
            failures.append(msg)

    # 1. totality: every submitted request reached a terminal status
    check(len(done) == len(engine.requests),
          f"{len(engine.requests) - len(done)} requests not terminal")
    check(all(r.terminal for r in engine.requests.values()),
          "non-terminal request status")
    # 2. the planned fates landed
    check(done[rid_cancel].status is RequestStatus.CANCELLED,
          f"cancel victim ended {done[rid_cancel].status}")
    check(done[rid_expire].status is RequestStatus.EXPIRED,
          f"deadline victim ended {done[rid_expire].status}")
    for r in rejected:
        check(done[r].status is RequestStatus.REJECTED,
              f"overflow submit {r} ended {done[r].status}")
    check(done[victim].status is RequestStatus.FAILED,
          f"NaN victim ended {done[victim].status}")
    # 3. every fault path actually exercised
    for counter in ("guard_trips", "chunk_failures", "alloc_failures",
                    "index_drops", "rejected", "cancelled", "expired",
                    "degraded"):
        check(stats[counter] >= 1, f"counter {counter} never tripped")
    check(not inj.pending, f"faults never fired: {inj.pending}")

    # 4. token correctness: non-faulted streams bit-identical to solo
    # decode; FAILED/EXPIRED partials are clean solo prefixes
    prefill = jax.jit(lambda p, c, t: lm_prefill(p, c, {"tokens": t}, cfg))
    generate = jax.jit(
        lambda pp, c, tok, l: lm_generate(
            pp, c, tok, l, gen, cfg, eos_id=args.eos_id))
    for rid, req in sorted(done.items()):
        if req.status is RequestStatus.REJECTED or len(req.tokens) == 0:
            continue
        toks = jnp.asarray(req.prompt[None])
        caches = init_caches(cfg, 1, req.prompt_len + gen, jnp.float32)
        logits, caches = prefill(params, caches, toks)
        first = jnp.argmax(logits[:, -1], -1)[:, None].astype(jnp.int32)
        want, _ = generate(params, caches, first,
                           jnp.asarray(req.prompt_len, jnp.int32))
        want = np.asarray(want)[0]
        if req.status is RequestStatus.FINISHED:
            check(np.array_equal(req.tokens, want),
                  f"rid {rid}: non-faulted stream diverged from solo")
        else:   # FAILED / EXPIRED / CANCELLED partials
            check(np.array_equal(req.tokens, want[:len(req.tokens)]),
                  f"rid {rid} ({req.status.value}): partial tokens are "
                  f"not a solo-decode prefix")
    # 5. no page leaked through any of it
    engine.release_prefix_cache()
    check(engine.pool.free_pages == engine.pool.num_pages - 1,
          f"pool did not drain: {engine.pool.free_pages}/"
          f"{engine.pool.num_pages - 1}")
    check(engine.pool.live_refs() == 0, "dangling page references")

    if failures:
        for f in failures:
            print(f"  chaos verify FAILED: {f}")
        return 1
    n_ok = sum(1 for r in done.values()
               if r.status is RequestStatus.FINISHED)
    print(f"  verify OK: {n_ok} streams bit-identical to solo decode, "
          f"faulted/cancelled/expired partials are clean prefixes, "
          f"pool drained exactly")
    return 0


if __name__ == "__main__":
    sys.exit(main())
