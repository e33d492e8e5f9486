"""Training driver: the fused HFSL round (``hfsl.make_hfsl_round``).

Set-up builds the round and its state, and runs the first round from the
seed through the same call the window uses: that round compiles, and its
per-step losses, the adapters it leaves and the optimizer's first moments
are what the reference is compared with. The window then repeats rounds,
each ended by ``block_until_ready``, on further rows of the epoch bank,
until the window's length is reached; train tok/s is every token trained
over the time of all rounds in it.
"""
from __future__ import annotations

import gc
import time

import numpy as np

from bench import traffic as TR
from bench import weights as W


def run(ctx) -> dict:
    import jax
    import jax.numpy as jnp
    from repro.analysis.guards import compile_guard
    from repro.core import hfsl
    from repro.models import model as M
    from repro.optim.optimizers import adamw

    conf, tr, cfg = ctx.conf, ctx.traffic, ctx.cfg
    backbone, adapters = W.make(conf, ctx.seed, 1)
    W.check_layout(cfg, backbone, adapters)
    params = {"backbone": backbone, "adapters": W.adapter(adapters, 0)}
    opt = adamw(tr["lr"], b1=tr["b1"], b2=tr["b2"], eps=tr["eps"])
    state = hfsl.init_hfsl_state(None, cfg, tr["clusters"], opt,
                                 lambda c, k: params)
    bank_np = TR.train_bank(tr, ctx.seed, conf["vocab_size"])
    bank = jax.device_put({k: jnp.asarray(v) for k, v in bank_np.items()})
    steps = tr["steps"]
    round_fn = hfsl.make_hfsl_round(
        cfg, opt, M.lm_loss, steps=steps, sync_every=tr["sync_every"],
        remat=tr["remat"])
    before = jax.tree.map(np.asarray, state["adapters_c"])
    state, metrics = round_fn(state, bank, 0)
    first = {"loss": np.asarray(jax.block_until_ready(metrics["loss"])),
             "adapters": jax.tree.map(np.asarray, state["adapters_c"]),
             "m": jax.tree.map(np.asarray, state["opt"]["m"])}
    ctx.mark_setup_done()

    tokens_round = steps * tr["clusters"] * tr["batch"] * tr["seq"]
    rounds, offset = 0, steps
    ctx.start_trace()
    with compile_guard(max_compiles=None) as compiles:
        t0 = time.perf_counter()
        while True:
            state, metrics = round_fn(state, bank, offset)
            jax.block_until_ready((state, metrics))
            rounds += 1
            offset = (offset + steps) % tr["epoch_rows"]
            t1 = time.perf_counter()
            if t1 - t0 >= ctx.seconds:
                break
    ctx.stop_trace()
    losses = np.asarray(metrics["loss"])
    out = {"end_to_end": {"train_tok_s": rounds * tokens_round / (t1 - t0)},
           "t0": t0, "t_done": t1, "window_end": t1, "rounds": rounds,
           "tokens": rounds * tokens_round, "compiles": compiles.count,
           "compiled": list(compiles.names), "spans": [],
           "attempted": rounds, "failed": int(not np.isfinite(losses).all()),
           "steps": rounds * steps}
    out["memory_peak_bytes"] = ctx.memory_peak()
    del state, metrics, round_fn, params
    gc.collect()
    out["check"] = ctx.check_training(backbone, before, bank_np, first)
    return out
