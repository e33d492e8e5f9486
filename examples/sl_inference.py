"""SL-based task inference (paper Fig 5): the model's tunable stack is split
across a chain of 4 "clients" (devices), activations hop via D2D
(collective_permute), the end point's result returns to the start point.

Uses 4 virtual host devices — the XLA flag below must precede jax import.

  python examples/sl_inference.py
"""
import os
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=4")

import dataclasses
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, "src")

from repro.configs.base import get_config
from repro.core.comm import CostModel, sl_round_cost
from repro.core.sl_pipeline import (pipeline_classify, simulate_sl,
                                    split_for_stages)
from repro.data.synthetic import ClassificationTask
from repro.models import model as M

N_STAGES = 4

cfg = get_config("vit-edge").reduced().with_(n_layers=4, dtype="float32")
cfg = cfg.with_(peft=dataclasses.replace(cfg.peft, head_dim_out=5))
params = M.init(cfg, jax.random.PRNGKey(0))
task = ClassificationTask(5, cfg.vocab_size, 32, seed=0)

mesh = jax.make_mesh((N_STAGES,), ("stage",),
                     axis_types=(jax.sharding.AxisType.Auto,))
stages = split_for_stages(params, cfg, N_STAGES)
print(f"[sl] split {cfg.n_layers} layers across {N_STAGES} clients "
      f"({cfg.n_layers // N_STAGES} layers each)")

# batched inference requests from the start point (jitted once, reused)
infer = jax.jit(lambda p, st, toks: pipeline_classify(
    p, st, toks, cfg, mesh, n_microbatches=4))
for req in range(3):
    batch = task.dataset(16, seed=req)
    t0 = time.time()
    logits = jax.block_until_ready(
        infer(params, stages, jnp.asarray(batch["tokens"])))
    dt = time.time() - t0
    acc = float(np.mean(np.argmax(np.asarray(logits), -1) == batch["label"]))
    print(f"[sl] request {req}: 16 samples in {dt:.2f}s, acc={acc:.2f} "
          f"(untuned adapters — see hfsl_finetune.py)")

# verify against the monolithic model
mono = M.classify(params, {"tokens": jnp.asarray(batch["tokens"])}, cfg)
err = float(np.abs(np.asarray(mono) - np.asarray(logits)).max())
print(f"[sl] pipelined == monolithic: max err {err:.2e}")

# the paper's §III-D.2 metrics for this round, priced on the wireless model
trace = simulate_sl(cfg, batch=16, seq=32, n_clients=N_STAGES, training=False)
cost = sl_round_cost(trace, CostModel(),
                     model_delivery_bytes=0)   # adapters pre-delivered
print(f"[sl] per-request metrics (6G wireless pricing): "
      f"latency={cost.latency_s*1e3:.1f}ms comm={cost.comm_bytes/1e3:.0f}KB "
      f"energy={cost.energy_j:.3f}J mem={cost.memory_bytes/1e3:.0f}KB")
