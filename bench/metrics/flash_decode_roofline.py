"""Kernels: least time the window's decode attention needs (every served
token over its visible context, ``costs/flash_decode.py``, every layer)
over the traced time of the flash_decode kernel."""
from bench.costs import flash_decode as FD
from bench.readers import least, model_sizes, roofline, served_rows


def read(ctx):
    s = model_sizes(ctx)
    nkv = ctx.conf["num_key_value_heads"]
    pairs = []
    for P, n in served_rows(ctx):
        for j in range(n):
            pairs.append(FD.cost(P + j + 1 + s["n_p"], s["nh"], nkv, s["hd"]))
    return roofline(ctx, "flash_decode", s["L"] * least(pairs, ctx.peak))
