"""Model step: operations the window's prompts need (counted from shapes,
``costs/model_step.py``) over the device-busy time of the programs that
prefill them, times the chip's peak."""
from bench.costs import model_step as MS
from bench.readers import module_busy


def read(ctx):
    busy = module_busy(ctx, "flash_attention")
    if busy <= 0:
        return None
    flops = sum(MS.prefill(ctx.conf, len(r["prompt"]))
                for r in ctx.result["requests"] if r["n"] > 0)
    return 100.0 * flops / (busy * ctx.peak["bf16_flops_per_s"])
