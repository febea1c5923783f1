#!/usr/bin/env python3
"""Bring-up smoke on one TPU chip: serve qwen1.5-0.5b at its published
widths through ``ServingEngine``, dense and knapsack-packed.

    python chip_smoke.py [--seed 0]

Weights are random, drawn from ``--seed``, at the published widths of
``configs/qwen1_5_0_5b.py`` (24 layers, d_model 1024, 16 heads, d_ff
2816, vocab 151,936, bf16).  Two engines each serve 8 requests (prompts
of 128-512 tokens, 32 new tokens, 8 slots, max_seq_len 1024) entering
through ``submit``/``run``: one on the dense params, one on the params
knapsack-pruned at 50% structure sparsity in 128x128 tiles and packed
to BSR.  Each engine serves the batch twice: once cold (compiling) and
once warm.

The run fails -- non-zero exit, no result line -- unless:

* JAX's first device is a TPU;
* every request finished with its whole token budget;
* the engine never recovered from anything: 0 chunk failures, 0 guard
  trips, not degraded;
* each engine's decode chunk holds Pallas kernels (``tpu_custom_call``),
  the packed one more than the dense one (its BSR matmuls);
* the first-token logits of the engine's own paged prefill agree with
  ``lm_forward`` in float32 at "highest" matmul precision -- on the
  dense params for the dense engine, on the masked dense params for
  the packed one -- to a relative L2 error of ``REL_TOL``.

Whether request 0's stream matches a solo decode of its prompt is
printed, not required.

The last line of stdout is ``{"ok": true, "device": {...}}``.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

ARCH = "qwen1.5-0.5b"
PROMPT_LENS = (128, 256, 512, 128, 256, 512, 128, 256)  # 3 prefill shapes
GEN = 32
SLOTS = 8
MAX_SEQ = 1024
PAGE_SIZE = 16            # a multiple of the fp32 pool's 8-row sublane tile
TICKS = 8                 # decode steps per on-device chunk
SPARSITY = 0.5
BLOCK = 128
N_CHECK = 2               # prompts whose logits are checked, per engine
# The engine keeps weights and activations in bf16 (8 significant bits,
# relative rounding <= 2^-9) with fp32 accumulation; the reference keeps
# everything in fp32.  Rounding at each of ~10 matmul inputs and layer
# boundaries per layer, 24 layers, adds like a random walk:
# sqrt(240) * 2^-9 ~ 3% relative L2 on the logits.  5% leaves margin for
# that and nothing else: a dropped tile, a wrong page or a broken mask
# moves the logits by O(1).
REL_TOL = 0.05


class SmokeFailure(RuntimeError):
    """A phase produced a wrong or incomplete result."""


def _require(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


class CompileClock:
    """Seconds JAX spent tracing, lowering and compiling, per phase."""

    EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
              "/jax/core/compile/jaxpr_to_mlir_module_duration",
              "/jax/core/compile/backend_compile_duration")

    def __init__(self):
        import jax

        self.total = 0.0
        self._mark = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **_) -> None:
        if event in self.EVENTS:
            self.total += duration

    def take(self) -> float:
        """Compile seconds since the previous take."""
        spent, self._mark = self.total - self._mark, self.total
        return spent


def _serve(name, params, cfg, prompts, clock, *, gen, slots, max_seq,
           page_size, ticks):
    """One engine over ``prompts``: cold run then warm run, each checked.
    Returns the warm engine (idle, its pools live) for the other checks."""
    from repro.serving import RequestStatus, ServingEngine

    engine = None
    for run in ("cold", "warm"):
        engine = None                       # free the previous pools first
        engine = ServingEngine(params, cfg, num_slots=slots,
                               page_size=page_size, max_seq_len=max_seq,
                               ticks_per_sync=ticks)
        for p in prompts:
            engine.submit(p, gen)
        t0 = time.perf_counter()
        done = engine.run()
        wall = time.perf_counter() - t0
        tokens = sum(len(r.tokens) for r in done.values())
        print(f"{name} {run}: {len(done)} requests, {tokens} tokens in "
              f"{wall:.3f} s ({tokens / wall:.1f} tok/s), compile "
              f"{clock.take():.1f} s", flush=True)
        bad = [(r.rid, r.status.value, len(r.tokens)) for r in done.values()
               if r.status is not RequestStatus.FINISHED
               or len(r.tokens) != gen]
        _require(len(done) == len(prompts) and not bad,
                 f"{name} {run}: unfinished or short requests {bad}")
        fs = engine.fault_stats
        _require(fs["chunk_failures"] == 0 and fs["guard_trips"] == 0
                 and not fs["degraded"],
                 f"{name} {run}: engine recovered from a fault: {fs} "
                 f"(last error: {engine.last_chunk_error})")
    return engine


def _kernel_count(engine, name, clock) -> int:
    n = engine.lower_decode_chunk().as_text().count("tpu_custom_call")
    print(f"{name}: {n} Pallas call sites in the lowered decode chunk "
          f"(lowering {clock.take():.1f} s)", flush=True)
    return n


def _check_logits(engine, name, ref_params, prompts, ref_forward, clock):
    import numpy as np

    worst = 0.0
    for p in prompts:
        got = engine.prefill_logits(p)
        want = ref_forward(ref_params, p)
        _require(bool(np.all(np.isfinite(got))),
                 f"{name}: non-finite prefill logits")
        rel = float(np.linalg.norm(got - want) / np.linalg.norm(want))
        worst = max(worst, rel)
        print(f"{name}: prompt of {len(p)} tokens: first-token logits rel "
              f"L2 err {rel:.5f}, max abs diff "
              f"{float(np.max(np.abs(got - want))):.5f}, argmax "
              f"{'agrees' if got.argmax() == want.argmax() else 'differs'} "
              f"with the fp32 reference", flush=True)
    print(f"{name}: logits check compile {clock.take():.1f} s", flush=True)
    _require(worst <= REL_TOL,
             f"{name}: prefill logits rel L2 err {worst:.5f} > {REL_TOL}")


def _report_solo(engine, name, params, cfg, gen, clock) -> None:
    """Report (not require) whether request 0 streamed the tokens a solo
    decode of its prompt gives.  Random bf16 weights leave near-tied
    argmaxes, and solo decode attends over a contiguous cache instead of
    the pages, so a difference here is not a fault."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.models import init_caches, lm_generate, lm_prefill

    req = engine.requests[0]
    toks = jnp.asarray(req.prompt[None])
    caches = init_caches(cfg, 1, req.prompt_len + gen, jnp.float32)
    logits, caches = jax.jit(
        lambda p, c, t: lm_prefill(p, c, {"tokens": t}, cfg))(
            params, caches, toks)
    first = jnp.argmax(logits[:, -1], -1)[:, None].astype(jnp.int32)
    want, _ = jax.jit(
        lambda p, c, t, n: lm_generate(p, c, t, n, gen, cfg))(
            params, caches, first, jnp.asarray(req.prompt_len, jnp.int32))
    want = np.asarray(want)[0]
    diff = np.nonzero(want != req.tokens)[0]
    print(f"{name}: request 0 vs solo decode: "
          + ("token-identical" if not diff.size
             else f"first differs at token {int(diff[0])} of {gen}")
          + f" (compile {clock.take():.1f} s)", flush=True)


def run_smoke(cfg, *, seed=0, prompt_lens=PROMPT_LENS, gen=GEN,
              slots=SLOTS, max_seq=MAX_SEQ, page_size=PAGE_SIZE,
              ticks=TICKS, block=BLOCK, n_check=N_CHECK):
    """Serve ``cfg`` dense and packed and check both; raises
    :class:`SmokeFailure` on the first failed check.  Returns the number
    of Pallas call sites in each engine's lowered decode chunk."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core import BlockingSpec
    from repro.core.masks import apply_masks
    from repro.models import init_params, lm_forward
    from repro.sparse import knapsack_prune, pack_params

    clock = CompileClock()
    dev = jax.devices()[0]
    rng = np.random.default_rng(seed)
    prompts = [rng.integers(0, cfg.vocab, size=n).astype(np.int32)
               for n in prompt_lens]
    check_prompts = prompts[:n_check]
    kw = dict(gen=gen, slots=slots, max_seq=max_seq, page_size=page_size,
              ticks=ticks)

    t0 = time.perf_counter()
    # op by op: one small program per distinct weight shape (the layers
    # share them), where one jit of the whole tree took ~70 s to compile
    # for a TPU v5e
    params = init_params(jax.random.PRNGKey(seed), cfg)
    jax.block_until_ready(params)
    print(f"init params: {time.perf_counter() - t0:.1f} s "
          f"(compile {clock.take():.1f} s)", flush=True)

    cfg32 = cfg.replace(param_dtype="float32", activ_dtype="float32",
                        remat="none")
    logits_last = jax.jit(
        lambda p, t: lm_forward(p, {"tokens": t}, cfg32)[0][0, -1])

    def ref_forward(p32, prompt):
        with jax.default_matmul_precision("highest"):
            return np.asarray(logits_last(p32, jnp.asarray(prompt[None])))

    def to_f32(tree):
        return jax.tree.map(lambda a: a.astype(jnp.float32), tree)

    dense = _serve("dense", params, cfg, prompts, clock, **kw)
    n_dense = _kernel_count(dense, "dense", clock)
    _check_logits(dense, "dense", to_f32(params), check_prompts,
                  ref_forward, clock)
    _report_solo(dense, "dense", params, cfg, gen, clock)
    dense = None
    stats = dev.memory_stats() or {}
    print(f"dense: peak device bytes in use "
          f"{stats.get('peak_bytes_in_use')}", flush=True)

    t0 = time.perf_counter()
    sel = knapsack_prune(params, sparsity=SPARSITY,
                         blocking=BlockingSpec(bk=block, bn=block))
    packed_params = pack_params(params, sel.masks, sel.structures)
    masked32 = to_f32(apply_masks(params, sel.masks))
    params = None
    print(f"knapsack prune + pack: kept {sel.kept}/{sel.total} tiles in "
          f"{time.perf_counter() - t0:.1f} s "
          f"(compile {clock.take():.1f} s)", flush=True)

    packed = _serve("packed", packed_params, cfg, prompts, clock, **kw)
    n_packed = _kernel_count(packed, "packed", clock)
    _check_logits(packed, "packed", masked32, check_prompts, ref_forward,
                  clock)
    _report_solo(packed, "packed", packed_params, cfg, gen, clock)
    stats = dev.memory_stats() or {}
    print(f"peak device bytes in use {stats.get('peak_bytes_in_use')}; "
          f"compile total {clock.total:.1f} s", flush=True)
    return {"dense": n_dense, "packed": n_packed}


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    import jax

    from repro.configs import get_config
    from repro.launch.compile_cache import enable_compile_cache

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: JAX's first device is {dev.platform!r}, not a "
              f"TPU", file=sys.stderr)
        return 1
    print(f"device: {dev.platform} {dev.device_kind} x{len(jax.devices())}; "
          f"compile cache {enable_compile_cache()}", flush=True)
    try:
        kernels = run_smoke(get_config(ARCH), seed=args.seed)
        _require(kernels["dense"] > 0,
                 "dense decode chunk holds no Pallas kernel")
        _require(kernels["packed"] > kernels["dense"],
                 f"packed decode chunk holds no BSR kernels ({kernels})")
    except SmokeFailure as e:
        print(f"chip_smoke FAILED: {e}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
