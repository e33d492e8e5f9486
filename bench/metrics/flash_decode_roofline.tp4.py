"""Kernels: least time one chip's share of the window's decode attention
needs (every served token over its visible context, ``costs/flash_decode.py``
with the chip's share of the heads: the query and KV heads over the
mesh's ``model`` axis; every layer) over chip 0's traced time of the
flash_decode kernel. A kernel that ran whole on every chip does four
times its share and reads a quarter of what a split one reads."""
from bench.costs import flash_decode as FD
from bench.readers import least, model_sizes, roofline, served_rows


def read(ctx):
    s = model_sizes(ctx)
    m = ctx.conf["mesh"]["model"]
    nh, nkv = s["nh"] // m, ctx.conf["num_key_value_heads"] // m
    pairs = []
    for P, n in served_rows(ctx):
        for j in range(n):
            pairs.append(FD.cost(P + j + 1 + s["n_p"], nh, nkv, s["hd"]))
    return roofline(ctx, "flash_decode", s["L"] * least(pairs, ctx.peak))
