"""slot_occupancy: share of decode-slot ticks that advanced a live
request over the traced span -- the engine's counters
(active_slot_ticks / (decode_ticks * slots)), in %."""


def read(ctx):
    c0, c1 = ctx.counters["start"], ctx.counters["end"]
    ticks = c1["decode_ticks"] - c0["decode_ticks"]
    if ticks <= 0:
        return None
    live = c1["active_slot_ticks"] - c0["active_slot_ticks"]
    return 100.0 * live / (ticks * ctx.mix["slots"])
