"""step_mfu_share: the model operations that the tokens served in the
traced span needed, over the span's seconds and the chip's bf16 peak, in
%, for a windowed mixture-of-experts configuration.

A first token costs the prefill of its whole prompt; every later token
one decode step at its context (``bench/flops_moe.token_flops``: two
operations per weight it uses -- attention, router, LM head and its
share of the held experts -- plus QK and PV over the context, the
window's part of it in sliding layers)."""
import numpy as np

from bench import flops_moe


def read(ctx):
    if not ctx.peaks or "router_experts" not in ctx.cfg:
        return None
    a, b = ctx.span
    total = 0.0
    for r in ctx.records.values():
        s = np.asarray(r.stamps)
        idx = np.nonzero((s > a) & (s <= b))[0]
        if idx.size == 0:
            continue
        p = len(r.prompt)
        if idx[0] == 0:
            total += flops_moe.token_flops(ctx.cfg, np.arange(p))
            idx = idx[1:]
        total += flops_moe.token_flops(ctx.cfg, p + idx)
    if total <= 0:
        return None
    return 100.0 * total / (b - a) / ctx.peaks["bf16_flops"]
