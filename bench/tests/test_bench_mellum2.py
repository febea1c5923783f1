"""The plain Mellum2 reference against the program at a small size on the
CPU: sliding and full GQA layers over the paged pool, YaRN on the full
ones, the dropless MoE holding 4 of 16 experts.

The size: 8 layers (two periods of sliding, sliding, sliding, full),
d 128, 8 query heads over 2 KV heads of 16, window 32 over 16-token
pages, 16 experts of width 64 routed top-4, 4 held; contexts up to ~3x
the window, so every sliding layer masks whole pages and partial ones.

Tolerances.  In float32 on the CPU, XLA computes matmuls in full float32
and the two sides differ only in the order of their sums -- ~1e-6
relative on logits of size ~10 -- so ``REL`` (1e-4 relative, per row)
holds that with a hundredfold margin, while a dropped norm scale, rotary
term, window or expert moves the logits by O(1).  A routing flip at a
near tie would move one row by O(0.1); none occurs at these seeds.  The
same comparison with the program in bfloat16 (the served type, 8 bits
of mantissa) reads ~1e-2: it must fail ``REL`` by at least 10x, which
shows the tolerance would catch a program computing below the float32
the comparison states.
"""
import json
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import check, flops_moe, model
from bench.references import mellum2
from bench.system import Server

ROOT = Path(__file__).resolve().parents[2]
FULL = json.loads(
    (ROOT / "bench" / "configs" / "mellum2-12b-a2.5b-ep8.json").read_text())
SMALL = dict(
    FULL, hidden_size=128, num_attention_heads=8, num_key_value_heads=2,
    head_dim=16, moe_intermediate_size=64, num_hidden_layers=8,
    num_experts=4, router_experts=16, expert_offset=4,
    num_experts_per_tok=4, sliding_window=32, vocab_size=256,
    initializer_range=0.125, torch_dtype="float32",
    serving=dict(FULL["serving"], page_size=16, ticks_per_sync=4))
SMALL_BF16 = dict(SMALL, torch_dtype="bfloat16")
MIX = {"slots": 3, "max_seq_len": 112}
REL = 1e-4


def _ref_logits(cfg, w, tokens):
    x = mellum2.hidden(w, jnp.asarray(tokens), cfg)
    return np.asarray(x @ mellum2.head(w, cfg))


def _rel_rows(got, want):
    return np.linalg.norm(got - want, axis=-1) / np.linalg.norm(want, axis=-1)


def test_engine_prefill_logits_match_the_reference():
    server = Server(SMALL, "small", MIX, seed=5)
    w = check.reference_weights(SMALL, 5)
    rng = np.random.default_rng(0)
    for n in (20, 60, 96):
        prompt = rng.integers(0, 256, size=n, dtype=np.int32)
        got = server.engine.prefill_logits(prompt)
        want = _ref_logits(SMALL, w, prompt)[-1]
        assert _rel_rows(got, want) < REL, n


def _decode_logits(cfg, seed, tokens, prompt_len):
    """Logits of every position from ``prompt_len`` on, computed the way
    the engine does: paged prefill of the prompt, then one paged decode
    step per token (teacher-forced with ``tokens``), through the
    engine's own pools and page allocator."""
    from repro.models import lm_decode, lm_prefill

    server = Server(cfg, "small", MIX, seed=seed)
    eng = server.engine
    pages = eng.pool.alloc_pages(eng.pool.pages_for(len(tokens)))
    table = np.zeros((1, eng.max_pages), np.int32)
    table[0, :len(pages)] = pages
    tb = jnp.asarray(table)
    logits, caches = lm_prefill(
        eng.params, eng.caches,
        {"tokens": jnp.asarray(tokens[None, :prompt_len]), "page_tables": tb},
        eng.cfg)
    out = [np.asarray(logits[0, -1])]
    for t in range(prompt_len, len(tokens) - 1):
        lg, caches = lm_decode(
            eng.params, caches,
            {"tokens": jnp.asarray(tokens[None, t:t + 1]), "page_tables": tb},
            jnp.asarray([t], jnp.int32), eng.cfg)
        out.append(np.asarray(lg[0, -1], np.float32))
    return np.stack(out)


@pytest.mark.parametrize("cfg,agrees", [(SMALL, True), (SMALL_BF16, False)],
                         ids=["float32", "bfloat16-fails"])
def test_decode_through_the_cache_matches_the_reference_forward(cfg, agrees):
    """Prefill 24 tokens, then decode through the pages to 96 (3x the
    window): each step's logits against the reference's full forward
    over the same tokens."""
    tokens = np.random.default_rng(1).integers(0, 256, size=96,
                                               dtype=np.int32)
    got = _decode_logits(cfg, 7, tokens, 24)
    want = _ref_logits(SMALL, check.reference_weights(cfg, 7), tokens)[23:95]
    rel = _rel_rows(got, want)
    if agrees:
        assert rel.max() < REL, rel.max()
    else:
        assert rel.max() > 10 * REL, rel.max()


def test_same_request_solo_and_cobatched_gives_identical_tokens():
    """Dropless MoE, windowed pages: a request's tokens do not depend on
    what shares its decode steps (bfloat16, as served)."""
    rng = np.random.default_rng(2)
    prompts = [rng.integers(0, 256, size=n, dtype=np.int32)
               for n in (40, 70, 9)]

    def serve(which):
        server = Server(SMALL_BF16, "small", MIX, seed=3)
        rids = [server.submit(prompts[i], 30) for i in which]
        while server.busy():
            server.step()
        return [server.request(r).tokens.tolist() for r in rids]

    together = serve([0, 1, 2])
    for i in range(3):
        assert serve([i]) == [together[i]]


def test_expert_shares_sum_to_the_uncut_reference_layer():
    """One chip's share, tied to the model (model-configs §4): the four
    shares of 4 experts each (0-3, 4-7, 8-11, 12-15), each computed by
    the program's dropless layer from its slice of the experts, add up to
    the uncut reference's MoE layer over all 16."""
    from repro.models.moe import moe_serve

    uncut = dict(SMALL, num_experts=16, expert_offset=0)
    w = check.reference_weights(uncut, 11)
    lw = {k: w[k][0] for k in ("router", "e_gate", "e_up", "e_down")}
    y = jax.random.normal(jax.random.PRNGKey(0), (40, 128))
    want = np.asarray(mellum2.moe(lw, y, uncut))
    total = 0.0
    for off in range(0, 16, 4):
        p = {"router": {"kernel": lw["router"]},
             "experts_gate": lw["e_gate"][off:off + 4],
             "experts_up": lw["e_up"][off:off + 4],
             "experts_down": lw["e_down"][off:off + 4]}
        part, _ = moe_serve(p, y[None], num_experts=16, top_k=4, held=4,
                            offset=off)
        total = total + np.asarray(part[0])
        share = dict(uncut, num_experts=4, expert_offset=off)
        slice_w = {**lw, **{k: lw[k][off:off + 4]
                            for k in ("e_gate", "e_up", "e_down")}}
        np.testing.assert_allclose(
            np.asarray(part[0]), np.asarray(mellum2.moe(slice_w, y, share)),
            atol=1e-5)
    np.testing.assert_allclose(total, want, atol=1e-5)
    assert np.abs(want).max() > 0.1


def test_reference_sees_a_window_yarn_and_an_expert():
    """Each mechanism moves the reference's logits by far more than REL:
    no window, default RoPE on the full layers, one held expert fewer."""
    w = model.weights(SMALL, 5)
    tokens = np.arange(96) % 256
    base = _ref_logits(SMALL, w, tokens)
    no_window = dict(SMALL, sliding_window=1000)
    rp = dict(SMALL["rope_parameters"])
    # YaRN at factor 1 and attention factor 1 is the default RoPE
    rp["full_attention"] = dict(rp["full_attention"], factor=1.0,
                                attention_factor=1.0)
    plain_rope = dict(SMALL, rope_parameters=rp)
    fewer = dict(w, e_down=w["e_down"].at[:, 0].set(0.0))
    for cfg, ww in ((no_window, w), (plain_rope, w), (SMALL, fewer)):
        assert _rel_rows(_ref_logits(cfg, ww, tokens), base).max() > 100 * REL


def test_reckoning_by_hand():
    """flops_moe on the served configuration: expert bytes per tick at
    16 rows, window KV bytes, and a decode token's operations."""
    s = flops_moe.sizes(FULL)
    assert (s["sliding"], s["full"], s["held"], s["experts"]) == (12, 4, 8, 64)
    expert = 3 * 2304 * 896
    share = 1 - (1 - 8 / 64) ** 16
    assert flops_moe.expert_tick_bytes(FULL, 16) == pytest.approx(
        16 * 8 * share * expert * 2)
    # a step at cache_len 100 reads 100 positions, at 5000 the window's 1023
    kv = 12 * 2 * 4 * 128 * 2
    assert flops_moe.window_kv_bytes(FULL, [100, 5000]) == (100 + 1023) * kv
    weights = 16 * (2304 * 4096 * 2 + 2304 * 512 * 2 + 2304 * 64
                    + 1 * expert) + 98304 * 2304
    attn = 4 * 4096 * (4 * 3000 + 12 * 1024)
    assert flops_moe.token_flops(FULL, [3000]) == pytest.approx(
        2 * weights + attn)


class _Trace:
    """A reduced trace holding only what the readers ask of it."""
    def __init__(self, program_s, chunks, kernels):
        self.program_s, self.chunks, self.kernels = program_s, chunks, kernels

    def module_time(self, pattern):
        return self.program_s, self.chunks

    def kernel_time(self, kernel, pattern=""):
        return self.kernels.get(kernel, 0.0)


def _ctx(trace, records, counters, peaks):
    from bench.serve import Context, Record
    recs = {}
    for i, (plen, stamps) in enumerate(records):
        r = Record(0.0, 0.0, np.zeros(plen, np.int32), len(stamps))
        r.stamps = list(stamps)
        recs[i] = r
    return Context(cell={}, cfg=FULL, mix={"slots": 16}, setup_s=0.0,
                   t_open=0.0, t_end=10.0, records=recs, span=(0.0, 10.0),
                   counters=counters, window_compiles=0, trace=trace,
                   peaks=peaks, tiles=None)


def test_new_metric_readers_by_hand():
    from bench.serve import find_reader

    peaks = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}
    # one request: prompt 2000, first token before the span, then 8 decode
    # tokens inside it (cache_len 2000..2007), over one 8-tick chunk
    recs = [(2000, [-1.0] + [1.0 + i for i in range(8)])]
    counters = {"start": {"decode_ticks": 0}, "end": {"decode_ticks": 8}}
    tr = _Trace(0.1, 1, {"paged_attention_decode_window": 0.02,
                         "moe_experts": 0.04})
    ctx = _ctx(tr, recs, counters, peaks)
    kv = flops_moe.window_kv_bytes(FULL, np.arange(2000, 2008))
    assert find_reader("window_attn_roofline.decode")(ctx) == pytest.approx(
        100 * (kv / 819e9 / 8) / (0.02 / 8))
    assert find_reader("expert_roofline.decode")(ctx) == pytest.approx(
        100 * (flops_moe.expert_tick_bytes(FULL, 16) / 819e9) / (0.04 / 8))
    ops = flops_moe.token_flops(FULL, 2000 + np.arange(1, 9))
    assert find_reader("step_mfu_share.decode")(ctx) == pytest.approx(
        100 * ops / 10.0 / 197e12)
    # untraced, or on a trace without the kernels: nothing to read
    for name in ("window_attn_roofline.decode", "expert_roofline.decode"):
        assert find_reader(name)(_ctx(None, recs, counters, peaks)) is None
        assert find_reader(name)(_ctx(_Trace(0.1, 1, {}), recs, counters,
                                      peaks)) is None


def test_harness_runs_the_cell_at_a_small_size(monkeypatch):
    """A whole run of ``mellum2-mixed-backlog`` on the CPU at the small
    size, past the harness's look for a chip: mixed prompt lengths over
    the window, the backlog served through the engine, every request
    finished whole, no recovery, the reference check run.  (At this size,
    with ``initializer_range`` 0.125, bfloat16 routing flips at near ties
    spread the widest gap over 0-0.76 on 8 seeds against the float8
    control's 0.53-2.2, so no limit separates them here: correctness is
    judged at the cell's own size on the chip.)"""
    from bench import serve, traffic

    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    mix = {"arrivals": {"kind": "backlog", "requests": 8},
           "prompt_len": {"values": [16, 40, 72], "weights": [1, 1, 1]},
           "max_new": {"uniform": [6, 16]}, "slots": 3, "max_seq_len": 96,
           "check_requests": 4}
    monkeypatch.setattr(model, "load_config", lambda name: SMALL_BF16)
    monkeypatch.setattr(traffic, "load", lambda name: mix)
    res = serve.run_cell(bench, "mellum2-mixed-backlog", 2**32 + 7, 2.0,
                         False, log=lambda s: None)
    checks = res["checks"]
    assert res["attempted"] == 8 and res["failed"] == 0
    assert checks["lost_requests"]["value"] == 0
    assert checks["engine_recoveries"]["value"] == 0
    assert checks["checked_tokens"]["value"] > 0
    assert np.isfinite(checks["max_logit_gap"]["value"])
    assert set(res["metrics"]) == {"output_tok_s", "setup_s"}
