"""Operations the model step needs, from the configuration's sizes.

Matrix products count 2 operations per multiply-add. A token's forward
through one layer costs 2 x (its projection and MLP weights) plus
attention over its visible context (4 x heads x head size x context) plus
the LoRA branches. Training adds the backward for the activations (as
much again as the forward's products; twice the forward's attention) and
for the adapters, and nothing for the frozen backbone's weights; the
recomputation of remat is not counted.
"""
from __future__ import annotations


def sizes(conf: dict) -> dict:
    d, ff = conf["hidden_size"], conf["intermediate_size"]
    nh, nkv = conf["num_attention_heads"], conf["num_key_value_heads"]
    hd = d // nh
    p = conf["peft"]
    width = {"q": nh * hd, "k": nkv * hd, "v": nkv * hd, "o": d}
    n_in = {"q": d, "k": d, "v": d, "o": nh * hd}
    lora = sum(2 * p["lora_rank"] * (n_in[t] + width[t])
               for t in p["lora_targets"])
    return {"L": conf["num_hidden_layers"], "d": d, "V": conf["vocab_size"],
            "nh": nh, "hd": hd, "n_p": p["n_prefix"],
            "layer": 2 * (d * nh * hd * 2 + 2 * d * nkv * hd + 3 * d * ff),
            "lora": lora}


def token_forward(conf: dict, ctx: float, head: bool) -> float:
    """One token's forward: ``ctx`` keys visible in each layer (prefix
    included), and the LM head if its logits are computed."""
    s = sizes(conf)
    per_layer = s["layer"] + s["lora"] + 4 * s["nh"] * s["hd"] * ctx
    return s["L"] * per_layer + (2 * s["d"] * s["V"] if head else 0)


def prefill(conf: dict, P: int) -> float:
    """A P-token prompt: every position's forward, logits at the last."""
    s = sizes(conf)
    mean_ctx = (P + 1) / 2 + s["n_p"]
    return P * token_forward(conf, mean_ctx, False) + 2 * s["d"] * s["V"]


def decode(conf: dict, P: int, n: int) -> float:
    """n decode steps of a row with a P-token prompt: step j attends over
    P + j + 1 positions and the prefix, and computes logits."""
    s = sizes(conf)
    mean_ctx = P + (n + 1) / 2 + s["n_p"]
    return n * token_forward(conf, mean_ctx, True)


def train_step(conf: dict, rows: int, seq: int) -> float:
    """Forward + backward (activations and adapters) of rows x seq tokens
    with logits at every position."""
    s = sizes(conf)
    mean_ctx = (seq + 1) / 2 + s["n_p"]
    attn = 4 * s["nh"] * s["hd"] * mean_ctx
    fwd = s["L"] * (s["layer"] + s["lora"] + attn) + 2 * s["d"] * s["V"]
    bwd = s["L"] * (s["layer"] + 2 * s["lora"] + 2 * attn) \
        + 2 * s["d"] * s["V"]
    return rows * seq * (fwd + bwd)
