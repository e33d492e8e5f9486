"""Model step: operations the window's training steps need (forward and
the backward for activations and adapters; no frozen-weight gradients,
no recomputation: ``costs/model_step.py``) per second of the window,
over the chip's peak."""
from bench.costs import model_step as MS


def read(ctx):
    res, tr = ctx.result, ctx.traffic
    flops = res["steps"] * MS.train_step(
        ctx.conf, tr["clusters"] * tr["batch"], tr["seq"])
    return 100.0 * flops / ((res["t_done"] - res["t0"])
                            * ctx.peak["bf16_flops_per_s"])
