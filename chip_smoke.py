#!/usr/bin/env python3
"""Chip smoke: the system's main path once on a TPU at qwen2-7b's widths.

Drives the entry points a user calls, in one process that owns the chip,
with random weights made from ``--seed``. All widths are qwen2-7b's
published ones (d_model 3584, 28 query / 4 KV heads of 128, d_ff 18944,
vocab 152064, QKV bias, PEFT prefix and LoRA); only the depth is cut, to
``--layers`` (default 14 of 28: 8.7 GB of bf16 weights, which leaves the
chip room for KV cache and training activations).

One chip (no options):

  (a) serve   ``DecodeEngine`` drains 16 requests of mixed prompt lengths
              (128-2048 tokens, 64 generated each) over 8 slots, dense,
              paged, and multi-tenant through an ``AdapterBank``. Every
              served token is scored by a full-sequence forward of the same
              weights on the ``xla`` backend (see ``check_against_xla``).
  (b) train   ``hfsl.make_hfsl_round`` takes 8 LM steps, 2 clusters, a
              FedAvg sync every 4 steps; the loss must be finite and fall.
  (c) cycle   ``IntegratedRuntime`` upgrade -> publish -> produce.

Each fused prefill, refill, decode segment and round program compiled in a
phase must contain a Pallas kernel (``tpu_custom_call``). Each phase
prints its wall time (ended by ``block_until_ready`` or a host read),
its compile time and the device's ``peak_bytes_in_use``.

Four chips (``--chips 4``), and nothing else:

  (m1) the cut-depth drain on a 1x4 ('data', 'model') mesh and on device 0
       alone, token for token (float32 weights: see ``mesh_phases``);
  (m2) the full 28-layer drain and HFSL round on the mesh, with the
       per-device bytes of the placed backbone.

The last line of stdout is ``{"ok": true, "device": {...}}``. Any failure
raises, and the script exits non-zero without that line. It refuses to
run on any platform but ``tpu``.

    python chip_smoke.py [--layers 14] [--seed 0] [--chips 1|4]
"""
from __future__ import annotations

import argparse
import contextlib
import dataclasses
import gc
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.configs.base import get_config  # noqa: E402
from repro.core import hfsl  # noqa: E402
from repro.core.adapter_bank import AdapterBank  # noqa: E402
from repro.core.integrated import IntegratedRuntime  # noqa: E402
from repro.core.paged import PagedSpec  # noqa: E402
from repro.data.synthetic import ClassificationTask  # noqa: E402
from repro.kernels import ops  # noqa: E402
from repro.launch.compile_cache import setup_compile_cache  # noqa: E402
from repro.launch.engine import DecodeEngine  # noqa: E402
from repro.launch.mesh import device_summary, make_test_mesh  # noqa: E402
from repro.models import model as M  # noqa: E402
from repro.models.layers import unembed  # noqa: E402
from repro.optim.optimizers import adamw  # noqa: E402
from repro.sharding import rules as R  # noqa: E402

ARCH = "qwen2-7b"
SLOTS, N_REQ, GEN = 8, 16, 64
# --chips 4 parity drain depth: its float32 weights at 14 layers (17 GB)
# exceed one chip, and device 0 alone is half of that comparison.
PARITY_LAYERS = 4
PROMPT_MIN, PROMPT_MAX = 128, 2048
DOMAINS = ("d0", "d1", "d2", "d3")
REF_ROWS = 4                     # rows per reference forward
# Tolerance of the served-token check. Both backends run the same bf16
# weights but round at different points: the kernels keep f32 scores and
# accumulators inside a block, XLA rounds activations to bf16 between ops,
# and decode reads K/V back from a bf16 cache where the reference recomputes
# them. Logits therefore differ by a few bf16 ulps of the hidden state,
# which can swap near-tied top tokens: on one v5e at 14 layers the served
# tokens were the reference argmax 0.959-0.972 of the time, and the worst
# lay 0.038-0.055 row standard deviations below the reference maximum. A
# wrong mask or a dropped cache entry lands far outside both: at reduced
# width, decode without the prefix-KV read 0.85 / 0.75 std, and a decode
# mask off by one 0.61 / 1.90 std (tests/test_chip_smoke.py plants both).
# So: at least REF_AGREE of the served tokens are the reference argmax, and
# none lies more than REF_GAP row standard deviations below its maximum.
REF_AGREE, REF_GAP = 0.9, 0.25
IR_DIR = ROOT / ".smoke_ir"      # lowered programs, read back for kernels
FUSED = ("jit_wave_prefill", "jit_refill", "jit_decode_segment",   # engine
         "jit_paged_prefill", "jit_paged_refill", "jit_paged_suffix",
         "jit_hfsl_round")                                # HFSL round


# ---------------------------------------------------------------------------
# Phase bookkeeping
# ---------------------------------------------------------------------------

class CompileClock:
    """Seconds JAX spends tracing, lowering and compiling (monitoring)."""

    def __init__(self) -> None:
        self.s = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **_) -> None:
        if event.startswith("/jax/core/compile/"):
            self.s += duration


def _ir_files() -> set:
    return set(IR_DIR.glob("*.mlir")) if IR_DIR.exists() else set()


@contextlib.contextmanager
def phase(name: str, clock: CompileClock, *, kernels: bool = True):
    """Time one phase; after it, every fused program it compiled must hold
    a Pallas kernel. Prints the phase line."""
    before, c0, t0 = _ir_files(), clock.s, time.perf_counter()
    yield
    wall = time.perf_counter() - t0
    compile_s = clock.s - c0
    fused = sorted(f for f in _ir_files() - before
                   if any(tag in f.name for tag in FUSED))
    if kernels:
        missing = [f.name for f in fused
                   if "tpu_custom_call" not in f.read_text()]
        if missing or not fused:
            raise AssertionError(f"{name}: no Pallas kernel in "
                                 f"{missing or 'any fused program'}")
    stats = jax.devices()[0].memory_stats() or {}
    print(f"[smoke] {name}: wall {wall:.3f}s, compile {compile_s:.3f}s, "
          f"peak_bytes_in_use {stats.get('peak_bytes_in_use')}, "
          f"fused programs compiled {len(fused)}", flush=True)


# ---------------------------------------------------------------------------
# Weights and traffic (all from the seed)
# ---------------------------------------------------------------------------

def domain_adapters(cfg, key, n: int) -> list:
    """``n`` distinct adapter trees: the configured prefix-KV and LoRA,
    with LoRA ``b`` drawn (its init is zeros) so every domain's LoRA
    branch changes what it computes."""
    out = []
    for k in jax.random.split(key, n):
        ka, kb = jax.random.split(k)
        ad = R.init_from_spec(ka, M.adapter_spec(cfg))
        lora_keys = iter(jax.random.split(kb, 64))

        def draw(path, x):
            if path[-1].key == "b" and any(getattr(p, "key", None) == "lora"
                                           for p in path):
                return (jax.random.normal(next(lora_keys), x.shape,
                                          jnp.float32) * 0.02).astype(x.dtype)
            return x
        out.append(jax.tree_util.tree_map_with_path(draw, ad))
    return out


def traffic(cfg, seed: int, n: int = N_REQ, lo: int = PROMPT_MIN,
            hi: int = PROMPT_MAX) -> list:
    """``n`` prompts with lengths spread over [lo, hi], both ends included."""
    rng = np.random.default_rng(seed)
    lens = rng.integers(lo, hi + 1, size=n)
    lens[0], lens[1] = hi, lo
    return [rng.integers(0, cfg.vocab_size, size=int(L), dtype=np.int32)
            for L in lens]


# ---------------------------------------------------------------------------
# (a) serving
# ---------------------------------------------------------------------------

def drain(engine: DecodeEngine, params, prompts, gen: int, domains=None):
    """Submit every prompt, run one drain; tokens in submission order."""
    uids = [engine.submit(p, gen,
                          domain=None if domains is None else domains[i])
            for i, p in enumerate(prompts)]
    comps, stats = engine.run(params)
    by_uid = {c.uid: np.asarray(c.tokens) for c in comps}
    toks = np.stack([by_uid[u] for u in uids])
    if stats.tokens != len(prompts) * gen or stats.timed_out:
        raise AssertionError(f"drain served {stats.tokens} tokens "
                             f"({stats.timed_out} timed out)")
    return toks, stats


def _reference_fn(cfg, gen: int):
    """Full-sequence forward -> per served token: the reference argmax, the
    gap between the reference maximum and the served token's logit, and
    the row's logit standard deviation (all f32)."""

    def impl(params, tokens, start, served, ids):
        h = M.forward(params, {"tokens": tokens}, cfg, mode="eval",
                      remat=False, adapter_ids=ids)["hidden"]
        idx = start[:, None] + jnp.arange(gen)[None, :]
        h = jnp.take_along_axis(h, idx[:, :, None], axis=1)
        head = params["backbone"].get("lm_head", params["backbone"]["embed"])
        logits = unembed(head, h).astype(jnp.float32)      # (B, gen, V)
        mx = jnp.max(logits, -1)
        got = jnp.take_along_axis(logits, served[..., None], -1)[..., 0]
        return jnp.argmax(logits, -1), mx - got, jnp.std(logits, -1)

    return jax.jit(impl)


def check_against_xla(name, ref_fn, params, prompts, toks, ids=None):
    """Teacher-forced check of served tokens against the xla backend.

    Row r's context is its prompt plus its served tokens; the position
    before served token i must predict it (see REF_AGREE/REF_GAP)."""
    gen = toks.shape[1]
    width = max(len(p) for p in prompts) + gen - 1
    width += (-width) % 8
    agree, worst = [], 0.0
    with ops.backend("xla"):
        for r0 in range(0, len(prompts), REF_ROWS):
            rows = range(r0, min(r0 + REF_ROWS, len(prompts)))
            seq = np.zeros((REF_ROWS, width), np.int32)
            start = np.zeros(REF_ROWS, np.int32)
            served = np.zeros((REF_ROWS, gen), np.int32)
            for j, r in enumerate(rows):
                L = len(prompts[r])
                seq[j, :L] = prompts[r]
                seq[j, L:L + gen - 1] = toks[r, :-1]
                start[j], served[j] = L - 1, toks[r]
            rid = None if ids is None else jnp.asarray(
                np.resize(np.asarray(ids)[r0:r0 + REF_ROWS], REF_ROWS))
            am, gap, sd = (np.asarray(a) for a in ref_fn(
                params, jnp.asarray(seq), jnp.asarray(start),
                jnp.asarray(served), rid))
            n = len(rows)
            agree.append(am[:n] == served[:n])
            worst = max(worst, float((gap[:n] / sd[:n]).max()))
    rate = float(np.concatenate(agree).mean())
    print(f"[smoke] {name}: served tokens vs xla reference: argmax "
          f"agreement {rate:.4f} (>= {REF_AGREE}), worst gap {worst:.4f} "
          f"logit std (<= {REF_GAP})", flush=True)
    if rate < REF_AGREE or worst > REF_GAP or not np.isfinite(worst):
        raise AssertionError(f"{name}: served tokens disagree with the xla "
                             "reference beyond tolerance")


def serve_phases(cfg, backbone, adapters, seed: int, clock) -> None:
    prompts = traffic(cfg, seed)
    ref_fn = _reference_fn(cfg, GEN)
    single = {"backbone": backbone, "adapters": adapters[0]}
    max_len = max(len(p) for p in prompts) + GEN
    n_blocks = SLOTS * -(-max_len // 16) + 8

    kern = ops.get_backend()
    paged_path = ("paged flash_decode kernel" if not cfg.peft.n_prefix else
                  "prefix bank present, so pool[table] gather + dense "
                  "flash_decode kernel (ROADMAP S4)")
    with phase("serve dense", clock):
        toks, st = drain(DecodeEngine(cfg, slots=SLOTS), single, prompts, GEN)
    print(f"[smoke]   {st.requests} requests, {st.tokens} tokens, "
          f"{st.waves} waves, {st.segments} segments; attention ({kern}): "
          "flash_attention prefill, dense flash_decode", flush=True)
    check_against_xla("serve dense", ref_fn, single, prompts, toks)

    with phase("serve paged", clock):
        eng = DecodeEngine(cfg, slots=SLOTS,
                           paged=PagedSpec(n_blocks=n_blocks, block_size=16))
        ptoks, st = drain(eng, single, prompts, GEN)
        del eng
    same = float((ptoks == toks).mean())
    print(f"[smoke]   {st.requests} requests, {st.tokens} tokens, "
          f"{st.waves} waves, pool peak {st.pool_peak_blocks} blocks; "
          f"tokens equal to dense {same:.4f}; attention ({kern}): "
          f"flash_attention prefill, {paged_path}", flush=True)
    check_against_xla("serve paged", ref_fn, single, prompts, ptoks)

    bank = AdapterBank.create(dict(zip(DOMAINS, adapters)))
    doms = [DOMAINS[i % len(DOMAINS)] for i in range(len(prompts))]
    with phase("serve multi-tenant", clock):
        mtoks, st = drain(DecodeEngine(cfg, slots=SLOTS, bank=bank),
                          bank.serving_params(backbone), prompts, GEN, doms)
    print(f"[smoke]   {st.requests} requests over {bank.n_slots} adapter "
          f"slots, {st.tokens} tokens; attention ({kern}): flash_attention "
          "prefill, dense flash_decode; projections: lora_bgmv", flush=True)
    check_against_xla("serve multi-tenant", ref_fn,
                      bank.serving_params(backbone), prompts, mtoks,
                      ids=bank.adapter_ids(doms))


# ---------------------------------------------------------------------------
# (b) fine-tuning
# ---------------------------------------------------------------------------

def train_phase(cfg, backbone, adapters, seed: int, clock, *,
                clusters: int = 2, batch: int = 2, seq: int = 256,
                steps: int = 8, sync_every: int = 4, lr: float = 1e-2,
                mesh=None) -> np.ndarray:
    """One fused HFSL round over a one-row bank (the same LM batch every
    step, so the adapters can fit it): per-step mean losses."""
    rng = np.random.default_rng(seed + 1)
    toks = rng.integers(0, cfg.vocab_size, size=(1, clusters, batch, seq + 1),
                        dtype=np.int32)
    bank = {"tokens": jnp.asarray(toks[..., :-1]),
            "labels": jnp.asarray(toks[..., 1:])}
    opt = adamw(lr)
    params = {"backbone": backbone, "adapters": adapters}
    state = hfsl.init_hfsl_state(None, cfg, clusters, opt,
                                 lambda c, k: params)
    spec = rules = None
    if mesh is not None:
        rules = R.hfsl_round_rules(cfg.family)
        spec = hfsl.hfsl_state_spec(cfg, clusters, opt, M.model_spec)
        sh = R.named_shardings(spec, mesh, rules)
        state = {**state, **jax.device_put(
            {k: state[k] for k in ("adapters_c", "opt", "step")},
            {k: sh[k] for k in ("adapters_c", "opt", "step")})}
        bank = jax.device_put(bank, R.dim_sharding(
            mesh, clusters, "cluster", index=1, rules=rules))
    round_fn = hfsl.make_hfsl_round(cfg, opt, M.lm_loss, steps=steps,
                                    sync_every=sync_every, remat=True,
                                    mesh=mesh, rules=rules, state_spec=spec)
    where = "" if mesh is None else f", {cfg.n_layers} layers on 1x4 mesh"
    with phase(f"train hfsl round{where}", clock):
        state, metrics = round_fn(state, bank, 0)
        losses = np.asarray(jax.block_until_ready(metrics["loss"]))
    print(f"[smoke]   {clusters} clusters x {batch} x {seq} tokens, {steps} "
          f"steps, FedAvg every {sync_every}: loss {losses[0]:.4f} -> "
          f"{losses[-1]:.4f} (per step {np.round(losses, 4).tolist()})",
          flush=True)
    if not np.isfinite(losses).all() or not losses[-1] < losses[0]:
        raise AssertionError("HFSL round loss is not finite and falling")
    return losses


# ---------------------------------------------------------------------------
# (c) the integrated cycle
# ---------------------------------------------------------------------------

def cycle_phase(cfg, seed: int, clock) -> None:
    """Upgrade one domain (HFSL round + hot publish), then serve a mixed
    round from the bank. Task token ids are drawn from the first 512 of
    the model's vocabulary (the synthetic task's Markov chains are
    vocab x vocab); the model keeps its published vocabulary."""
    cfg = cfg.with_(peft=dataclasses.replace(cfg.peft, head_dim_out=5))
    tasks = {"nlp": ClassificationTask(5, 512, 64, class_strength=0.6,
                                       seed=seed),
             "code": ClassificationTask(5, 512, 64, class_strength=0.6,
                                        seed=seed + 7)}
    with phase("cycle upgrade+publish+produce", clock):
        rt = IntegratedRuntime(cfg, tasks, n_clusters=2, steps_per_upgrade=4,
                               batch=4, sync_every=2, serve_batch=SLOTS,
                               serve_gen=4, serve_slots=SLOTS, seed=seed)
        v0 = rt.bank.version("nlp")
        _, up = rt.upgrade("nlp")
        published = rt.bank.snapshot("nlp")
        consensus = rt._consensus_adapters("nlp")
        profit, prod = rt.produce(["nlp", "code"])
        jax.block_until_ready(rt.bank.stacked)
    same = all(bool(jnp.array_equal(a, b)) for a, b in zip(
        jax.tree.leaves(published), jax.tree.leaves(consensus)))
    print(f"[smoke]   upgrade {up.examples} examples in {up.latency_s:.3f}s, "
          f"bank version {v0} -> {rt.bank.version('nlp')}, published == "
          f"consensus {same}; produce {prod.tokens} tokens, accuracy profit "
          f"{profit:.1f}", flush=True)
    if rt.bank.version("nlp") != v0 + 1 or not same:
        raise AssertionError("upgrade did not publish the round's adapters")
    if prod.tokens != SLOTS * 4 or prod.timed_out or \
            not 0.0 <= profit <= rt.profit_scale:
        raise AssertionError("produce did not serve the round")


# ---------------------------------------------------------------------------
# Four chips
# ---------------------------------------------------------------------------

def _per_device_share(tree) -> dict:
    per, logical = {}, 0
    for leaf in jax.tree.leaves(tree):
        logical += leaf.nbytes
        for s in leaf.addressable_shards:
            per[s.device.id] = per.get(s.device.id, 0) + s.data.nbytes
    return {d: b / logical for d, b in sorted(per.items())}


def mesh_phases(args, clock) -> None:
    """(m1) cut-depth drain, 1x4 mesh vs device 0, token for token. The
    comparison runs in float32 with full-precision contractions: in bf16
    the mesh's split contractions round partial sums where one device
    rounds the whole sum, and such one-ulp differences flip near-tied
    argmaxes over a thousand tokens. float32 alone is not enough on the
    chip: a default-precision contraction rounds its f32 operands to bf16,
    so a sub-ulp difference from summation order that crosses a bf16
    rounding boundary grows to bf16 size (the first four-chip run matched
    0.6846 of 1024 tokens in plain f32). With "highest" precision (also
    inside the Pallas kernels) only summation order differs, far below the
    top-2 logit gaps. (m2) the published 28 layers in bf16, drained on the
    mesh with every served token checked against an xla-backend forward of
    the same placed weights, then trained on the mesh."""
    if len(jax.devices()) != 4:
        raise RuntimeError(f"--chips 4 needs 4 devices, JAX sees "
                           f"{len(jax.devices())}")
    mesh = make_test_mesh(1, 4)
    ids = sorted(d.id for d in mesh.devices.flat)
    if ids != sorted(d.id for d in jax.devices()):
        raise AssertionError(f"mesh covers devices {ids}")
    base = get_config(ARCH)
    key = jax.random.PRNGKey(args.seed)

    cfg = base.with_depth(PARITY_LAYERS).with_(dtype="float32")
    prompts = traffic(cfg, args.seed, n=SLOTS)
    adapters = domain_adapters(cfg, jax.random.fold_in(key, 1), 1)[0]
    with jax.default_matmul_precision("highest"):
        with phase(f"mesh parity: {cfg.n_layers} layers f32 on device 0",
                   clock):
            params = {"backbone": M.init(cfg, key)["backbone"],
                      "adapters": adapters}
            solo, _ = drain(DecodeEngine(cfg, slots=SLOTS), params, prompts,
                            GEN)
        with phase(f"mesh parity: {cfg.n_layers} layers f32 on 1x4 mesh",
                   clock):
            placed = M.place_params(params, cfg, mesh)
            del params
            gc.collect()
            sharded, _ = drain(DecodeEngine(cfg, slots=SLOTS, mesh=mesh),
                               placed, prompts, GEN)
    same = float((solo == sharded).mean())
    print(f"[smoke]   mesh vs device 0: {solo.size} tokens, equal "
          f"{same:.4f}", flush=True)
    if same != 1.0:
        raise AssertionError("mesh drain differs from the device-0 drain")
    del placed
    gc.collect()

    cfg = base
    with phase(f"mesh serve: {cfg.n_layers} layers bf16 on 1x4 mesh", clock):
        backbone = M.init(cfg, key, shardings=R.named_shardings(
            M.model_spec(cfg), mesh, R.serving_rules()))["backbone"]
        share = _per_device_share(backbone)
        adapters = domain_adapters(cfg, jax.random.fold_in(key, 1), 1)[0]
        params = M.place_params({"backbone": backbone, "adapters": adapters},
                                cfg, mesh)
        prompts = traffic(cfg, args.seed, n=SLOTS, hi=1024)
        toks, st = drain(DecodeEngine(cfg, slots=SLOTS, mesh=mesh), params,
                         prompts, GEN)
    print(f"[smoke]   backbone bytes per device "
          f"{ {d: round(s, 4) for d, s in share.items()} }; {st.tokens} "
          f"tokens served", flush=True)
    if len(share) != 4 or not all(0.2 < s < 0.3 for s in share.values()):
        raise AssertionError(f"backbone is not spread over 4 chips: {share}")
    check_against_xla(f"mesh serve {cfg.n_layers} layers", _reference_fn(
        cfg, GEN), params, prompts, toks)
    train_phase(cfg, params["backbone"], params["adapters"], args.seed,
                clock, mesh=mesh)


# ---------------------------------------------------------------------------

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--layers", type=int, default=14,
                    help="depth cut for the one-chip phases (of 28)")
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    setup_compile_cache()
    dev = device_summary()
    if dev["platform"] != "tpu":
        print(f"chip_smoke: needs a TPU; JAX sees {dev['platform']} "
              f"({dev['kind']})", file=sys.stderr)
        return 2
    ops.set_backend("pallas")
    for f in _ir_files():
        f.unlink()
    jax.config.update("jax_dump_ir_to", str(IR_DIR))
    clock = CompileClock()
    print(f"[smoke] {dev['kind']} x{dev['count']}, kernel backend "
          f"{ops.get_backend()}", flush=True)

    if args.chips == 4:
        mesh_phases(args, clock)
    else:
        cfg = get_config(ARCH).with_depth(args.layers)
        print(f"[smoke] {cfg.name}: {cfg.n_layers} of "
              f"{get_config(ARCH).n_layers} layers, d_model {cfg.d_model}, "
              f"heads {cfg.n_heads}/{cfg.n_kv_heads}x{cfg.head_dim_}, d_ff "
              f"{cfg.d_ff}, vocab {cfg.vocab_size}", flush=True)
        key = jax.random.PRNGKey(args.seed)
        with phase("init", clock, kernels=False):
            backbone = jax.block_until_ready(M.init(cfg, key)["backbone"])
            adapters = domain_adapters(cfg, jax.random.fold_in(key, 1),
                                       len(DOMAINS))
        serve_phases(cfg, backbone, adapters, args.seed, clock)
        train_phase(cfg, backbone, adapters[0], args.seed, clock)
        del backbone, adapters
        gc.collect()
        cycle_phase(cfg, args.seed, clock)
    print(json.dumps({"ok": True, "device": dev}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
