"""copy_share: share of the decode chunk program's (``_decode_chunk``)
device time in which an XLA copy ran -- ``copy``, or the ``copy-start`` /
``copy-done`` pair of an asynchronous one -- in %.  The copies' intervals
are merged before they are summed, so overlapping copies count once and
the share cannot pass 100%."""

COPY = r"^%copy(-start|-done)?(\.\d+)? = "
PROGRAM = r"_decode_chunk"


def read(ctx):
    if ctx.trace is None:
        return None
    total, n = ctx.trace.module_time(PROGRAM)
    if not n or total <= 0:
        return None
    return 100.0 * ctx.trace.op_union(COPY, PROGRAM) / total
