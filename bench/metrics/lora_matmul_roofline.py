"""Kernels: least time the training round's LoRA projections need
(``costs/lora_matmul.py``: per step and layer, each LoRA target's forward,
its remat recomputation and its ``dx``, and the adapter-gradient kernel)
over the traced time of both lora_matmul kernels."""
from bench.costs import lora_matmul as LM
from bench.readers import kernel_seconds, least


def read(ctx):
    c, tr, res = ctx.conf, ctx.traffic, ctx.result
    tf, nf = kernel_seconds(ctx, "lora_matmul_fwd")
    tb, nb = kernel_seconds(ctx, "lora_matmul_bwd")
    p = c["peft"]
    n_t = len(p["lora_targets"])
    calls = res["steps"] * c["num_hidden_layers"] * n_t
    if nb == 0 or nb % calls or nf != 3 * nb:
        return None
    M = tr["clusters"] * tr["batch"] * tr["seq"] * calls // nb
    d, nh = c["hidden_size"], c["num_attention_heads"]
    hd = d // nh
    width = {"q": nh * hd, "k": c["num_key_value_heads"] * hd,
             "v": c["num_key_value_heads"] * hd, "o": d}
    r = p["lora_rank"]
    pairs = []
    for t in p["lora_targets"]:
        K, N = (d, width[t]) if t != "o" else (nh * hd, d)
        pairs += [LM.forward(M, K, N, r)] * 2 + [LM.forward(M, N, K, r)]
        pairs += [LM.backward(M, K, N, r)]
    least_s = (nb // n_t) * least(pairs, ctx.peak)
    return 100.0 * least_s / (tf + tb) if tf + tb > 0 else None
