"""Training launcher: HFSL fine-tuning (or plain PEFT/full FT) end-to-end.

Runs on whatever devices exist — a 1-device CPU box trains reduced configs
(examples use this), a real pod trains full configs with the same code path.

``--impl scan`` (default) runs the fused round engine: ``--log-every`` HFSL
steps per jitted ``lax.scan`` dispatch over a device-resident batch bank
(hfsl.make_hfsl_round); ``--impl loop`` keeps the legacy one-dispatch-per-
step path (benchmarks/finetune_bench.py measures the gap).

Usage:
  PYTHONPATH=src python -m repro.launch.train --arch vit-edge --reduced \
      --task classify --clusters 4 --steps 200 --sync-every 4 --impl scan
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint import io as ckpt
from repro.configs.base import get_config
from repro.core import hfsl, telemetry
from repro.core.peft import trainable_fraction, tree_bytes
from repro.data.noniid import partition_by_classes
from repro.data.pipeline import BatchBank, cluster_batches
from repro.data.synthetic import ClassificationTask, LMStream
from repro.launch.compile_cache import setup_compile_cache
from repro.launch.mesh import device_summary
from repro.models import model as M
from repro.optim.optimizers import adamw
from repro.optim.schedules import warmup_cosine


def build_cfg(args):
    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    if args.layers:
        cfg = cfg.with_depth(args.layers)
    if args.task == "classify" and not cfg.peft.head_dim_out:
        cfg = cfg.with_(peft=dataclasses.replace(cfg.peft,
                                                 head_dim_out=args.classes))
    return cfg


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="vit-edge")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--layers", type=int, default=None,
                    help="keep only this many layers at the published widths "
                         "(the depth that fits one chip)")
    ap.add_argument("--task", choices=("lm", "classify"), default="classify")
    ap.add_argument("--clusters", type=int, default=4)
    ap.add_argument("--classes", type=int, default=5)
    ap.add_argument("--classes-per-client", type=int, default=5)
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=32)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--sync-every", type=int, default=4)
    ap.add_argument("--impl", choices=("scan", "loop"), default="scan",
                    help="scan: fused round engine (one dispatch per "
                         "--log-every steps); loop: legacy per-step dispatch")
    ap.add_argument("--microbatches", type=int, default=1,
                    help="gradient-accumulation splits per cluster batch "
                         "(scan impl)")
    ap.add_argument("--remat", action="store_true",
                    help="checkpoint the per-layer forward (lm task, scan "
                         "impl): long-sequence activation memory relief")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--ckpt", default=None)
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--trace-out", default=None, metavar="PATH",
                    help="enable telemetry and write a Chrome trace-event "
                         "JSON here (open in Perfetto / chrome://tracing)")
    ap.add_argument("--metrics-out", default=None, metavar="PATH",
                    help="enable telemetry and write the counter/histogram "
                         "snapshot as JSON here")
    args = ap.parse_args(argv)
    setup_compile_cache()

    if args.trace_out or args.metrics_out:
        telemetry.enable()

    cfg = build_cfg(args)
    dev = device_summary()
    print(f"[train] {cfg.name}: {cfg.n_layers} layers (published "
          f"{get_config(args.arch).n_layers}) on {dev['platform']} "
          f"{dev['kind']} x{dev['count']}")
    key = jax.random.PRNGKey(args.seed)
    opt = adamw(warmup_cosine(args.lr, args.steps // 10 + 1, args.steps))

    state = hfsl.init_hfsl_state(key, cfg, args.clusters, opt, M.init)
    print(f"[train] {cfg.name}: trainable fraction "
          f"{trainable_fraction(hfsl.consensus_params(state)):.4%}, "
          f"adapter bytes/cluster "
          f"{tree_bytes(jax.tree.map(lambda x: x[0], state['adapters_c']))}")

    if args.task == "classify":
        task = ClassificationTask(args.classes, cfg.vocab_size, args.seq,
                                  seed=args.seed)
        data = task.dataset(200 * args.clusters, seed=args.seed)
        parts = partition_by_classes(data["label"], args.clusters,
                                     args.classes_per_client, seed=args.seed)
        it = cluster_batches(data, parts, args.batch, seed=args.seed)
        loss_fn = M.classify_loss
    else:
        streams = [LMStream(cfg.vocab_size, args.batch, args.seq,
                            seed=args.seed + i) for i in range(args.clusters)]
        its = [iter(s) for s in streams]

        def it_gen():
            while True:
                bs = [next(i) for i in its]
                yield {k: jnp.stack([b[k] for b in bs]) for k in bs[0]}
        it = it_gen()
        loss_fn = M.lm_loss                  # accepts remat= for the scan impl

    t0 = time.perf_counter()
    if args.impl == "scan":
        remat = True if (args.remat and args.task == "lm") else None
        # pack the run's whole batch stream (same iterator + seed as the
        # loop impl, so the two impls are step-for-step identical); very
        # long runs recycle the first 512 rows modulo-epoch
        bank = BatchBank.from_iterator(it, min(args.steps, 512))
        rounds: dict[int, object] = {}      # one compiled round per chunk len
        done = 0
        while done < args.steps:
            chunk = min(args.log_every, args.steps - done)
            if chunk not in rounds:
                rounds[chunk] = hfsl.make_hfsl_round(
                    cfg, opt, loss_fn, steps=chunk,
                    sync_every=args.sync_every,
                    microbatches=args.microbatches, remat=remat)
            # the span covers dispatch + the metric host-read (the float()
            # below syncs), so its duration is the blocked round time — the
            # nested hfsl.round_dispatch span is the host-dispatch share
            with telemetry.get().span("train.round", steps=chunk,
                                      done=done) as rsp:
                state, metrics = rounds[chunk](state, bank.arrays,
                                               bank.advance(chunk))
                done += chunk
                m = {k: float(v[-1]) for k, v in metrics.items()
                     if jnp.ndim(v) == 1}
                rsp.set(**m)
            print(f"[train] step {done:5d} {m} "
                  f"({(time.perf_counter()-t0)/done:.2f}s/step)")
    else:
        step_fn = jax.jit(hfsl.make_hfsl_step(cfg, opt, loss_fn,
                                              sync_every=args.sync_every))
        for i in range(args.steps):
            state, metrics = step_fn(state, next(it))
            if (i + 1) % args.log_every == 0 or i == 0:
                m = {k: float(v) for k, v in metrics.items()
                     if jnp.ndim(v) == 0}
                print(f"[train] step {i+1:5d} {m} "
                      f"({(time.perf_counter()-t0)/(i+1):.2f}s/step)")
    print(f"[train] done in {time.perf_counter()-t0:.1f}s; "
          f"fedavg bytes/sync: {hfsl.sync_bytes(state['adapters_c'])}")

    if args.trace_out or args.metrics_out:
        tel = telemetry.get()
        if args.trace_out:
            n = tel.export_trace(args.trace_out)
            print(f"[train] wrote {n} trace events to {args.trace_out}")
        if args.metrics_out:
            tel.export_metrics(args.metrics_out)
            print(f"[train] wrote metrics snapshot to {args.metrics_out}")
        print(tel.report())

    if args.ckpt:
        params = hfsl.consensus_params(state)
        nb = ckpt.save_adapters(args.ckpt, params)
        print(f"[train] adapter-only checkpoint: {nb} bytes -> {args.ckpt}")
    return state


if __name__ == "__main__":
    main()
