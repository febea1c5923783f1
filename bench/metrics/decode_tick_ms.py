"""decode_tick_ms: device time of the engine's decode chunk program
(``_decode_chunk``) per decode step it ran, in ms.  Every chunk runs the
configuration's ``ticks_per_sync`` steps."""

PROGRAM = r"_decode_chunk"


def read(ctx):
    if ctx.trace is None:
        return None
    s, n = ctx.trace.module_time(PROGRAM)
    ticks = n * ctx.cfg["serving"]["ticks_per_sync"]
    return 1e3 * s / ticks if ticks else None
