"""step_hbm_share: the bytes one decode step needs -- every kept weight
and the KV of its live rows, at the configuration's stated type
(``bench/flops``) -- over HBM bandwidth, over the device time of one
decode step (``decode_tick_ms``), in %."""
import numpy as np

from bench import flops

PROGRAM = r"_decode_chunk"


def read(ctx):
    if ctx.trace is None or not ctx.peaks:
        return None
    s, n = ctx.trace.module_time(PROGRAM)
    c0, c1 = ctx.counters["start"], ctx.counters["end"]
    ticks = c1["decode_ticks"] - c0["decode_ticks"]
    if not n or ticks <= 0:
        return None
    tick_s = s / (n * ctx.cfg["serving"]["ticks_per_sync"])
    a, b = ctx.span
    positions = 0
    for r in ctx.records.values():
        st = np.asarray(r.stamps)
        idx = np.nonzero((st > a) & (st <= b) & (np.arange(st.size) > 0))[0]
        positions += int(np.sum(len(r.prompt) + idx))
    need = flops.weight_bytes(ctx.cfg) + \
        positions * flops.kv_bytes_per_position(ctx.cfg) / ticks
    return 100.0 * need / ctx.peaks["hbm_bytes_per_s"] / tick_s
