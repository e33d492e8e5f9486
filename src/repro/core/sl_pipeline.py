"""Split-learning serial pipeline (paper §III-C/III-D, Figs 4-5) — faithful form.

The paper splits the edge model's tunable stack across an intra-cluster
chain of clients; activations ("smashed data") hop client-to-client over
D2D links, gradients hop back. On TPU the chain is a 1-D `stage` mesh axis:

- each stage holds a contiguous slice of layers (client ≡ device),
- each D2D hop is one `jax.lax.ppermute` (GPipe-style microbatch schedule,
  bubble = S-1 steps),
- the paper's "feedback of inference results to the start point" is the
  final psum that replicates the end-point logits,
- SL *fine-tuning* is simply `jax.grad` through the pipelined forward: the
  transpose of ppermute sends gradients backwards hop-by-hop, which is
  exactly the paper's reverse smashed-data flow.

This module is the fidelity path, validated on small host-device meshes
(tests/test_sl_pipeline.py); the 512-chip production path replaces the
serial chain with tensor parallelism (DESIGN.md §2). A device-free
simulator with byte/latency accounting backs the paper-metric benchmarks.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.configs.base import ModelConfig
from repro.models.layers import embed, rmsnorm
from repro.models.transformer import _apply_seq
from repro.sharding.rules import ParamSpec, init_from_spec
from repro.models import model as model_lib


# ---------------------------------------------------------------------------
# Stage-sharded parameters
# ---------------------------------------------------------------------------

def split_for_stages(params: dict, cfg: ModelConfig, n_stages: int) -> dict:
    """Reshape the single scan group (L, ...) -> (S, L/S, ...) per leaf.

    Only single-group families (dense/vlm/moe/ssm) are supported in the
    faithful pipeline — matching the paper's homogeneous client chain.
    """
    layers = params["backbone"]["layers"]
    if set(layers) != {"g0"}:
        raise ValueError(
            f"pipeline supports single-group stacks, got groups "
            f"{sorted(layers)}")

    def resh(x):
        L = x.shape[0]
        if L % n_stages != 0:
            raise ValueError(
                f"layer count {L} not divisible by n_stages={n_stages}")
        return x.reshape(n_stages, L // n_stages, *x.shape[1:])

    stage_layers = jax.tree.map(resh, layers["g0"])
    stage_adapters = jax.tree.map(resh, params["adapters"]["stack"].get("g0", {}))
    return {"layers": stage_layers, "adapters": stage_adapters}


def pipeline_classify(params: dict, stage_tree: dict, tokens: jax.Array,
                      cfg: ModelConfig, mesh: Mesh, *,
                      n_microbatches: int = 4) -> jax.Array:
    """SL forward: tokens (B, S) -> class logits (B, n_out), pipelined.

    `params` supplies embed/final_norm/head (start & end point modules);
    `stage_tree` the stage-split layer stack (from split_for_stages).
    """
    S = mesh.shape["stage"]
    B = tokens.shape[0]
    M = n_microbatches
    if B % M != 0:
        raise ValueError(
            f"batch size {B} not divisible by n_microbatches={M}")
    mb = B // M
    kinds = ("moe",) if cfg.family == "moe" else (
        ("ssm",) if cfg.family == "ssm" else ("attn",))

    emb_tbl = params["backbone"]["embed"]
    fnorm = params["backbone"]["final_norm"]
    head = params["adapters"]["head"]
    toks_mb = tokens.reshape(M, mb, -1)
    positions = jnp.arange(tokens.shape[1], dtype=jnp.int32)

    def stage_fn(layers, adapters, toks):
        # local slices: layers leaves (1, L/S, ...), toks replicated
        sid = jax.lax.axis_index("stage")
        layers = jax.tree.map(lambda x: x[0], layers)
        adapters = jax.tree.map(lambda x: x[0], adapters)
        d = cfg.d_model
        buf = jnp.zeros((mb, toks.shape[-1], d), jnp.dtype(cfg.dtype))
        outs = []

        def run_local(x):
            def body(x, layer):
                lp, la = layer
                for i, k in enumerate(kinds):
                    x, _, _ = _apply_seq(k, lp[f"s{i}"], la.get(f"s{i}", {}),
                                         x, cfg, positions=positions,
                                         make_cache=False)
                return x, None
            x, _ = jax.lax.scan(body, x, (layers, adapters))
            return x

        for t in range(M + S - 1):
            # start point: embed microbatch t (senses data, extracts features)
            if t < M:
                x0 = embed(emb_tbl, toks[t])
            else:
                x0 = jnp.zeros((mb, toks.shape[-1], d), jnp.dtype(cfg.dtype))
            x_in = jnp.where(sid == 0, x0, buf)
            y = run_local(x_in)
            # end point: head over the finished microbatch
            if t >= S - 1:
                pooled = jnp.mean(rmsnorm(fnorm, y).astype(jnp.float32), axis=1)
                logits = pooled @ head["w"] + head["b"]
                outs.append(jnp.where(sid == S - 1, logits, 0.0))
            # D2D hop: stage s -> s+1 (smashed data)
            buf = jax.lax.ppermute(y, "stage",
                                   [(i, (i + 1) % S) for i in range(S)])
        out = jnp.stack(outs)                              # (M, mb, n_out)
        # feedback to start point (paper: end point returns the result):
        # psum replicates — only the end stage holds nonzero logits.
        return jax.lax.psum(out, "stage")

    fn = jax.shard_map(
        stage_fn, mesh=mesh,
        in_specs=(P("stage"), P("stage"), P()),
        out_specs=P(),
        check_vma=False,
    )
    out = fn(stage_tree["layers"], stage_tree["adapters"], toks_mb)
    return out.reshape(B, -1)


def make_sl_finetune_step(params: dict, cfg: ModelConfig, mesh: Mesh,
                          optimizer, *, n_microbatches: int = 4,
                          lr_trainables: str = "adapters"):
    """SL fine-tuning: grad flows backwards through the ppermute chain."""
    from repro.models.layers import cross_entropy

    def loss_fn(stage_adapters, head, stage_layers, batch):
        st = {"layers": stage_layers, "adapters": stage_adapters}
        p = {"backbone": params["backbone"],
             "adapters": {**params["adapters"], "head": head}}
        logits = pipeline_classify(p, st, batch["tokens"], cfg, mesh,
                                   n_microbatches=n_microbatches)
        return cross_entropy(logits, batch["label"])

    def step(stage_tree, head, opt_state, batch):
        (loss), grads = jax.value_and_grad(loss_fn, argnums=(0, 1))(
            stage_tree["adapters"], head, stage_tree["layers"], batch)
        g_ad, g_head = grads
        updates, opt_state = optimizer.update(
            {"a": g_ad, "h": g_head}, opt_state,
            {"a": stage_tree["adapters"], "h": head})
        from repro.optim.optimizers import apply_updates
        new = apply_updates({"a": stage_tree["adapters"], "h": head}, updates)
        return {**stage_tree, "adapters": new["a"]}, new["h"], opt_state, loss

    return step


# ---------------------------------------------------------------------------
# Device-free SL simulator (paper metrics: §III-C.2 / §III-D.2)
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class SLTrace:
    """Per-round accounting of one SL pass over a client chain."""
    hops: int
    smashed_bytes: int          # total D2D activation traffic (fwd)
    gradient_bytes: int         # reverse traffic (0 for inference)
    feedback_bytes: int         # end->start result feedback
    per_client_flops: list[int]
    peak_activation_bytes: int


def simulate_sl(cfg: ModelConfig, batch: int, seq: int, n_clients: int, *,
                training: bool) -> SLTrace:
    """Analytic trace of the paper's serial workflow for the cost model."""
    d = cfg.d_model
    act = batch * seq * d * jnp.dtype(cfg.dtype).itemsize
    hops = n_clients - 1
    layer_flops = 2 * batch * seq * (
        4 * d * d + 2 * d * cfg.d_ff) if cfg.d_ff else 2 * batch * seq * 4 * d * d
    per_layer = [layer_flops] * cfg.n_layers
    per_client = [int(sum(per_layer[i::n_clients]))
                  for i in range(n_clients)]  # round-robin layer split
    mult = 3 if training else 1              # fwd + bwd ~ 2x fwd
    n_out = max(cfg.peft.head_dim_out, 1)
    return SLTrace(
        hops=hops,
        smashed_bytes=int(act) * hops,
        gradient_bytes=int(act) * hops if training else 0,
        feedback_bytes=batch * n_out * 4,
        per_client_flops=[c * mult for c in per_client],
        peak_activation_bytes=int(act),
    )
