"""expert_roofline: the least time the chip could take to read the held
expert weights one decode tick touches (``bench/flops_moe
.expert_tick_bytes``: every MoE layer's held experts at the share that
uniform routing of the tick's rows touches, at the stated type, over HBM
bandwidth), over the device time per tick of the grouped expert kernel
(``moe_experts``) in the decode chunk program, in %."""
from bench import flops_moe

KERNEL = "moe_experts"
PROGRAM = r"_decode_chunk"


def read(ctx):
    if ctx.trace is None or not ctx.peaks or "router_experts" not in ctx.cfg:
        return None
    _, n = ctx.trace.module_time(PROGRAM)
    kernel = ctx.trace.kernel_time(KERNEL, PROGRAM)
    if not n or kernel <= 0:
        return None
    ideal = flops_moe.expert_tick_bytes(ctx.cfg, ctx.mix["slots"]) / \
        ctx.peaks["hbm_bytes_per_s"]
    return 100.0 * ideal / (kernel / (n * ctx.cfg["serving"]["ticks_per_sync"]))
