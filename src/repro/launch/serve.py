"""Serving launcher: SL-based task inference with batched requests.

Prefill + decode against a fine-tuned (adapter-loaded) model; the
parameter-efficient deployment path (§III-A.2): backbone weights are
initialized locally (presumed synchronized), only adapters come from a
checkpoint.

Decode-engine architecture (fast path first):

- ``--impl scan`` (default): :func:`repro.models.model.generate_scan` — the
  whole request (prefill + ``gen`` decode steps) is ONE jitted dispatch; the
  decode loop is a ``jax.lax.scan`` with the KV caches in the carry, and
  each step's cache attention runs through the flash-decode kernel dispatch
  (``kernels/ops.py::flash_decode``).
- ``--impl engine``: the batched serving layer
  (:mod:`repro.launch.engine`) — a continuous-batching-style request queue
  packed into fixed batch slots, used by ``core/integrated.py::produce``.
- ``--impl loop``: the legacy per-token Python loop (one host dispatch per
  token), kept as the benchmark baseline (benchmarks/decode_bench.py).
- ``--impl spec``: speculative serving — the engine drains with a tiny
  recurrent edge drafter (``core/spec_decode.py``): ``--draft-k`` proposed
  tokens per chunk, verified by ONE batched target pass, exact-match
  accepted with per-row rollback. Greedy output is token-for-token
  identical to ``--impl scan``; the printed acceptance rate is the
  measured draft quality (a fresh random drafter accepts near 0% — train
  or distill one for real speedups; benchmarks/spec_bench.py shows the
  acceptance=1.0 upper bound).

Usage:
  PYTHONPATH=src python -m repro.launch.serve --arch vit-edge --reduced \
      --batch 4 --prompt-len 16 --gen 8 [--adapters ckpt.npz] [--impl scan]
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint import io as ckpt_io
from repro.configs.base import get_config
from repro.core import telemetry
from repro.launch.compile_cache import setup_compile_cache
from repro.launch.mesh import device_summary
from repro.models import model as M


def generate_loop(params, cfg, prompts: jax.Array, *, gen: int,
                  extra_batch: dict | None = None, greedy: bool = True,
                  key=None):
    """LEGACY batched generation: per-token Python loop, one jitted dispatch
    per decode step. Superseded by :func:`repro.models.model.generate_scan`
    (token-for-token identical output); kept as the decode benchmark
    baseline. prompts: (B, S)."""
    B, S = prompts.shape
    n_vis = cfg.vlm.n_vis_tokens if cfg.family == "vlm" else 0
    batch = {"tokens": prompts, **(extra_batch or {})}
    prefill_j = jax.jit(lambda p, b: M.prefill(p, b, cfg, max_len=S + n_vis + gen))
    decode_j = jax.jit(lambda p, t, c, pos: M.decode_step(p, t, c, pos, cfg))

    logits, caches = prefill_j(params, batch)
    out = []
    tok = jnp.argmax(logits[:, -1], axis=-1)[:, None].astype(jnp.int32)
    for i in range(gen):
        out.append(tok)
        pos = jnp.asarray(S + n_vis + i, jnp.int32)
        logits, caches = decode_j(params, tok, caches, pos)
        if greedy or key is None:
            tok = jnp.argmax(logits[:, -1], axis=-1)[:, None].astype(jnp.int32)
        else:
            key, sub = jax.random.split(key)
            tok = jax.random.categorical(sub, logits[:, -1])[:, None].astype(jnp.int32)
    return jnp.concatenate(out, axis=1)


def generate(params, cfg, prompts: jax.Array, *, gen: int,
             extra_batch: dict | None = None, greedy: bool = True,
             key=None):
    """Batched greedy/sampled generation (single-dispatch scan path)."""
    return M.generate_scan(params, cfg, prompts, gen=gen,
                           extra_batch=extra_batch, greedy=greedy, key=key)


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="vit-edge")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--layers", type=int, default=None,
                    help="keep only this many layers at the published widths "
                         "(the depth that fits one chip)")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--gen", type=int, default=8)
    ap.add_argument("--adapters", default=None)
    ap.add_argument("--requests", type=int, default=3)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--impl", choices=("scan", "loop", "engine", "spec"),
                    default="scan")
    ap.add_argument("--draft-k", type=int, default=4,
                    help="--impl spec: drafted tokens per verify chunk")
    ap.add_argument("--trace-out", default=None, metavar="PATH",
                    help="enable telemetry and write a Chrome trace-event "
                         "JSON here (open in Perfetto / chrome://tracing)")
    ap.add_argument("--metrics-out", default=None, metavar="PATH",
                    help="enable telemetry and write the counter/histogram "
                         "snapshot as JSON here")
    args = ap.parse_args(argv)
    setup_compile_cache()

    traced = args.trace_out or args.metrics_out
    if traced:
        telemetry.enable()

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    if args.layers:
        cfg = cfg.with_depth(args.layers)
    dev = device_summary()
    print(f"[serve] {cfg.name}: {cfg.n_layers} layers (published "
          f"{get_config(args.arch).n_layers}) on {dev['platform']} "
          f"{dev['kind']} x{dev['count']}")
    key = jax.random.PRNGKey(args.seed)
    params = M.init(cfg, key)
    if args.adapters:
        params = ckpt_io.load_adapters(args.adapters, params)
        print(f"[serve] loaded adapters from {args.adapters} "
              f"(parameter-efficient deployment)")

    extra = None
    if cfg.family == "vlm":
        extra = {"vision_embeds": jnp.zeros(
            (args.batch, cfg.vlm.n_vis_tokens, cfg.d_model),
            jnp.dtype(cfg.dtype))}
    if cfg.family == "audio":
        extra = {"frames": jnp.zeros(
            (args.batch, cfg.audio.n_audio_frames, cfg.d_model),
            jnp.dtype(cfg.dtype))}

    def export_telemetry():
        if not traced:
            return
        tel = telemetry.get()
        if args.trace_out:
            n = tel.export_trace(args.trace_out)
            print(f"[serve] wrote {n} trace events to {args.trace_out}")
        if args.metrics_out:
            tel.export_metrics(args.metrics_out)
            print(f"[serve] wrote metrics snapshot to {args.metrics_out}")
        print(tel.report())

    if args.impl in ("engine", "spec"):
        from repro.launch.engine import DecodeEngine
        spec = None
        if args.impl == "spec":
            from repro.core.spec_decode import SpecDecoder
            # fold, don't split: the prompt stream must stay identical to
            # --impl engine/scan at the same seed (greedy spec serving is
            # token-for-token the plain output, so rows must match too)
            spec = SpecDecoder.init(cfg, jax.random.fold_in(key, 1337),
                                    k=args.draft_k)
        engine = DecodeEngine(cfg, slots=args.batch, spec=spec)
        for r in range(args.requests):
            key, sub = jax.random.split(key)
            prompts = jax.random.randint(sub, (args.batch, args.prompt_len),
                                         0, cfg.vocab_size, dtype=jnp.int32)
            toks, stats = engine.serve(params, np.asarray(prompts),
                                       gen=args.gen, extra_batch=extra)
            acc = (f", acceptance {stats.acceptance_rate:.2f} "
                   f"({stats.accepted}/{stats.drafted})"
                   if spec is not None else "")
            print(f"[serve] round {r}: {stats.requests} requests, "
                  f"{stats.tokens} tokens in {stats.wall_s:.2f}s "
                  f"({stats.tok_per_s:.1f} tok/s, {stats.waves} waves{acc}); "
                  f"first row: {toks[0][:8]}")
            if stats.ttft_hist:
                h = stats.ttft_hist
                print(f"[serve]   ttft p50={h['p50']:.3f}s "
                      f"p95={h['p95']:.3f}s p99={h['p99']:.3f}s")
        export_telemetry()
        return

    gen_fn = generate if args.impl == "scan" else generate_loop
    for r in range(args.requests):
        key, sub = jax.random.split(key)
        prompts = jax.random.randint(sub, (args.batch, args.prompt_len), 0,
                                     cfg.vocab_size, dtype=jnp.int32)
        t0 = time.perf_counter()
        with telemetry.get().span("serve.request", impl=args.impl,
                                  batch=args.batch, gen=args.gen):
            toks = gen_fn(params, cfg, prompts, gen=args.gen,
                          extra_batch=extra)
            toks = np.asarray(toks)
        dt = time.perf_counter() - t0
        tps = args.batch * args.gen / dt
        print(f"[serve] request {r}: generated {toks.shape} in {dt:.2f}s "
              f"({tps:.1f} tok/s); first row: {toks[0][:8]}")
    export_telemetry()


if __name__ == "__main__":
    main()
