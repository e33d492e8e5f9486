"""Model step: operations the window's work needs (prefill of the
admitted prompts and every served token, ``costs/model_step.py``) per
second of the window, over the peak of all the cell's chips: the share of
the whole step's peak across the mesh."""
from bench.costs import model_step as MS
from bench.readers import served_rows


def read(ctx):
    res = ctx.result
    flops = sum(MS.prefill(ctx.conf, P) + MS.decode(ctx.conf, P, n)
                for P, n in served_rows(ctx))
    window = res["window_end"] - res["t0"]
    return 100.0 * flops / (window * ctx.chips
                            * ctx.peak["bf16_flops_per_s"])
