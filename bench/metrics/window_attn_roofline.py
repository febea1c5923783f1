"""window_attn_roofline: the least time the chip could take to read the K
and V that the sliding-window layers of the decode steps served in the
traced span must see (``bench/flops_moe.window_kv_bytes``: at most the
window's last positions per step, at the stated type, over HBM
bandwidth), over the device time of the windowed paged decode kernel
(``paged_attention_decode_window``) in the decode chunk program, both per
decode tick, in %."""
import numpy as np

from bench import flops_moe

KERNEL = "paged_attention_decode_window"
PROGRAM = r"_decode_chunk"


def read(ctx):
    if ctx.trace is None or not ctx.peaks or "sliding_window" not in ctx.cfg:
        return None
    _, n = ctx.trace.module_time(PROGRAM)
    kernel = ctx.trace.kernel_time(KERNEL, PROGRAM)
    c0, c1 = ctx.counters["start"], ctx.counters["end"]
    ticks = c1["decode_ticks"] - c0["decode_ticks"]
    if not n or kernel <= 0 or ticks <= 0:
        return None
    a, b = ctx.span
    cache_len = []
    for r in ctx.records.values():
        st = np.asarray(r.stamps)
        idx = np.nonzero((st > a) & (st <= b) & (np.arange(st.size) > 0))[0]
        cache_len.append(len(r.prompt) + idx - 1)
    need = flops_moe.window_kv_bytes(ctx.cfg, np.concatenate(cache_len)
                                     if cache_len else [])
    ideal_tick = need / ctx.peaks["hbm_bytes_per_s"] / ticks
    kernel_tick = kernel / (n * ctx.cfg["serving"]["ticks_per_sync"])
    return 100.0 * ideal_tick / kernel_tick
