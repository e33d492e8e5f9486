"""Model step: operations the window's decode steps need (counted from
shapes, ``costs/model_step.py``) over the device-busy time of the decode
segment programs, times the chip's peak. Over busy time, not wall time:
in an open loop the wall-time rate only follows the offered rate."""
from bench.costs import model_step as MS
from bench.readers import module_busy, served_rows


def read(ctx):
    busy = module_busy(ctx, "flash_decode")
    if busy <= 0:
        return None
    flops = sum(MS.decode(ctx.conf, P, n) for P, n in served_rows(ctx))
    return 100.0 * flops / (busy * ctx.peak["bf16_flops_per_s"])
