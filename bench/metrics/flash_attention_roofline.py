"""Kernels: least time the prefill attention of the window's prompts
needs (``costs/flash_attention.py``, every layer) over the traced time of
the flash_attention kernel."""
from bench.costs import flash_attention as FA
from bench.readers import least, model_sizes, roofline


def read(ctx):
    s = model_sizes(ctx)
    nkv = ctx.conf["num_key_value_heads"]
    pairs = [FA.cost(len(r["prompt"]), s["n_p"], s["nh"], nkv, s["hd"])
             for r in ctx.result["requests"] if r["n"] > 0]
    return roofline(ctx, "flash_attention", s["L"] * least(pairs, ctx.peak))
