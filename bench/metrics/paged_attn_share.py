"""paged_attn_share: device time of the paged decode attention kernel
(``kernels/paged_attention.py``, called through the jitted
``paged_attention_decode`` of ``kernels/ops.py``) inside the decode
chunk program, over that program's device time, in %."""

KERNEL = "paged_attention_decode"
PROGRAM = r"_decode_chunk"


def read(ctx):
    if ctx.trace is None:
        return None
    total, n = ctx.trace.module_time(PROGRAM)
    kernel = ctx.trace.kernel_time(KERNEL, PROGRAM)
    return 100.0 * kernel / total if n and kernel > 0 else None
