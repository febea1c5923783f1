"""bsr_roofline: the least time the chip could take for the decode
step's block-sparse matmuls (``kernels/block_sparse_matmul.py``) over the
device time of those kernels in the decode chunk program, in %.

The least time (``bench/flops.bsr_decode_ideal_s``) is, per matmul, the
larger of its operations over the peak and its bytes -- kept bf16 tiles,
activation rows in and out -- over HBM bandwidth; at decode batch sizes
the bytes bound it."""
from bench import flops

KERNEL = "bsr_matmul"
PROGRAM = r"_decode_chunk"


def read(ctx):
    if ctx.trace is None or not ctx.cfg.get("prune") or not ctx.peaks:
        return None
    _, n = ctx.trace.module_time(PROGRAM)
    kernel = ctx.trace.kernel_time(KERNEL, PROGRAM)
    if not n or kernel <= 0:
        return None
    ticks = n * ctx.cfg["serving"]["ticks_per_sync"]
    ideal = ticks * flops.bsr_decode_ideal_s(
        ctx.cfg, ctx.mix["slots"], ctx.peaks["bf16_flops"],
        ctx.peaks["hbm_bytes_per_s"])
    return 100.0 * ideal / kernel
