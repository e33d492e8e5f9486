"""Mesh-sharded serving/training bench: throughput + per-device placement.

Runs the mesh-native hot paths (ISSUE 5) on a forced 4-host-device
(`data`, `model`) test mesh and reports, next to the unsharded baseline:

- ``shard_drain_tok_s_{unsharded,mesh}`` — mixed-domain ragged engine
  drain throughput (tokens/s; host-device meshes add collective overhead
  on CPU, so the mesh number is a *correctness+plumbing* figure — the
  speedup story needs real TPUs, see ROADMAP).
- ``shard_round_steps_s_{unsharded,mesh}`` — fused HFSL round steps/s.
- ``shard_devices_used`` / ``shard_bank_bytes_dev{i}`` — how many devices
  hold live shards of the AdapterBank + BatchBank and the per-device
  byte share (per-device utilization of the placement: equal shares =
  balanced slot/cluster parallelism).

The parent process may already own a single-device jax runtime (the
benchmarks/run.py runner, which on a TPU host holds the chip), so the
measurement runs in a CPU subprocess (``JAX_PLATFORMS=cpu``,
``XLA_FLAGS=--xla_force_host_platform_device_count=4``) that never asks
for the chip; pass ``--child`` to run the measurement directly.
"""
from __future__ import annotations

import os
import subprocess
import sys

ROOT = os.path.join(os.path.dirname(__file__), "..")


def _child() -> None:
    import time

    import numpy as np
    import jax
    import jax.numpy as jnp

    from repro.configs.base import get_config
    from repro.core import hfsl
    from repro.core.adapter_bank import AdapterBank
    from repro.data.noniid import partition_by_classes
    from repro.data.pipeline import BatchBank
    from repro.data.synthetic import ClassificationTask
    from repro.launch.engine import DecodeEngine
    from repro.launch.mesh import make_test_mesh
    from repro.models import model as M
    from repro.optim.optimizers import adamw
    from repro.sharding import rules as R

    def emit(name, us, derived=""):
        print(f"{name},{us:.0f},{derived}")

    mesh = make_test_mesh(2, 2)
    cfg = get_config("vit-edge").reduced().with_(dtype="float32",
                                                 vocab_size=64)
    doms = [f"d{i}" for i in range(4)]
    ks = jax.random.split(jax.random.PRNGKey(0), len(doms) + 1)
    adapters = {d: M.init(cfg, ks[i])["adapters"]
                for i, d in enumerate(doms)}
    backbone = M.init(cfg, ks[-1])["backbone"]
    key = jax.random.PRNGKey(5)
    prompts = np.asarray(jax.random.randint(key, (16, 10), 0,
                                            cfg.vocab_size))
    row_doms = [doms[i % len(doms)] for i in range(len(prompts))]
    GEN = 8

    def drain(engine, bank, bb):
        t0 = time.time()
        out, stats = engine.serve(bank.serving_params(bb), prompts,
                                  gen=GEN, domains=row_doms)
        return out, stats, time.time() - t0

    # -- serving: unsharded baseline vs mesh drain (warm both jits first)
    bank_u = AdapterBank.create(adapters)
    eng_u = DecodeEngine(cfg, slots=8, bank=bank_u)
    drain(eng_u, bank_u, backbone)
    out_u, stats_u, dt_u = drain(eng_u, bank_u, backbone)
    emit("shard_drain_tok_s_unsharded", dt_u * 1e6,
         f"{stats_u.tokens / dt_u:.1f}")

    bank_s = AdapterBank.create(adapters, mesh=mesh)
    bb_s = M.place_params({"backbone": backbone}, cfg, mesh)["backbone"]
    eng_s = DecodeEngine(cfg, slots=8, bank=bank_s, mesh=mesh)
    drain(eng_s, bank_s, bb_s)
    out_s, stats_s, dt_s = drain(eng_s, bank_s, bb_s)
    np.testing.assert_array_equal(out_s, out_u)    # parity is the contract
    emit("shard_drain_tok_s_mesh", dt_s * 1e6,
         f"{stats_s.tokens / dt_s:.1f}")

    # -- training: fused round, unsharded vs mesh
    C, BATCH, STEPS = 4, 8, 8
    opt = adamw(5e-3)
    task = ClassificationTask(5, 64, 24, class_strength=0.6, seed=0)
    data = task.dataset(60 * C, seed=11)
    parts = partition_by_classes(data["label"], C, cfg.peft.head_dim_out,
                                 seed=1)
    state0 = hfsl.init_hfsl_state(jax.random.PRNGKey(3), cfg, C, opt,
                                  M.init)
    bank_ut = BatchBank.pack(data, parts, BATCH, seed=2)
    round_u = hfsl.make_hfsl_round(cfg, opt, M.classify_loss, steps=STEPS,
                                   sync_every=2)
    round_u(state0, bank_ut.arrays, 0)             # warm
    t0 = time.time()
    su, _ = round_u(state0, bank_ut.arrays, 0)
    jax.block_until_ready(su["adapters_c"])
    dt = time.time() - t0
    emit("shard_round_steps_s_unsharded", dt * 1e6, f"{STEPS / dt:.2f}")

    rules = R.hfsl_round_rules(cfg.family)
    spec = hfsl.hfsl_state_spec(cfg, C, opt, M.model_spec)
    sh = hfsl.hfsl_state_shardings(cfg, C, opt, M.model_spec, mesh, rules)
    state_s = jax.device_put(state0, sh)
    bank_st = BatchBank.pack(data, parts, BATCH, seed=2, mesh=mesh,
                             rules=rules)
    round_s = hfsl.make_hfsl_round(cfg, opt, M.classify_loss, steps=STEPS,
                                   sync_every=2, mesh=mesh, rules=rules,
                                   state_spec=spec)
    round_s(state_s, bank_st.arrays, 0)            # warm
    t0 = time.time()
    ss, ms = round_s(state_s, bank_st.arrays, 0)
    jax.block_until_ready(ss["adapters_c"])
    dt = time.time() - t0
    # parity is the contract here too: same per-step losses as unsharded
    _, mu = round_u(state0, bank_ut.arrays, 0)
    np.testing.assert_allclose(np.asarray(ms["loss"]),
                               np.asarray(mu["loss"]),
                               rtol=2e-5, atol=1e-6)
    emit("shard_round_steps_s_mesh", dt * 1e6, f"{STEPS / dt:.2f}")

    # -- per-device placement utilization: each device's resident share of
    # the banks' LOGICAL bytes (AdapterBank slots + BatchBank clusters).
    # Slot/cluster dims split over the 2-way `data` axis and replicate
    # over `model`, so balanced placement prints 0.500 per device; a bank
    # that silently degraded to fully replicated prints ~1.000 PER DEVICE
    # — placement regressions are visible in the numbers, not hidden by
    # physical-total normalization (and the specs are hard-asserted).
    assert jax.tree.leaves(bank_s.stacked["stack"])[0].sharding.spec \
        == R.P(None, "data")
    assert jax.tree.leaves(bank_st.arrays)[0].sharding.spec \
        == R.P(None, "data")
    per_dev = {d.id: 0 for d in jax.devices()}
    logical = 0
    for leaf in (jax.tree.leaves(bank_s.stacked)
                 + jax.tree.leaves(bank_st.arrays)):
        logical += leaf.nbytes
        for s in leaf.addressable_shards:
            per_dev[s.device.id] += s.data.nbytes
    used = sum(1 for v in per_dev.values() if v > 0)
    emit("shard_devices_used", 0, f"{used}/{len(per_dev)}")
    for i, v in sorted(per_dev.items()):
        emit(f"shard_bank_bytes_dev{i}", 0, f"{v / logical:.3f}")
    import contextlib
    for name, leaf in (
            ("bank_head", bank_s.stacked["head"]["w"]),
            ("batch_bank", jax.tree.leaves(bank_st.arrays)[0])):
        print(f"# {name} sharding: {leaf.sharding.spec}", file=sys.stderr)
        with contextlib.redirect_stdout(sys.stderr):   # keep CSV clean
            jax.debug.visualize_array_sharding(
                leaf.reshape(leaf.shape[0], -1)
                if name == "bank_head" else leaf[0, :, 0])


def main() -> None:
    if "--child" in sys.argv or os.environ.get("REPRO_SHARD_BENCH_CHILD"):
        _child()
        return
    env = dict(os.environ,
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               JAX_PLATFORMS="cpu",      # host devices: never the chip
               REPRO_SHARD_BENCH_CHILD="1",
               PYTHONPATH="src" + (os.pathsep + os.environ["PYTHONPATH"]
                                   if os.environ.get("PYTHONPATH") else ""))
    r = subprocess.run([sys.executable, "-m", "benchmarks.shard_bench"],
                       cwd=ROOT, env=env, capture_output=True, text=True,
                       timeout=1800)
    sys.stdout.write(r.stdout)
    if r.returncode != 0:
        sys.stderr.write(r.stderr[-4000:])
        raise RuntimeError("shard_bench child failed")


if __name__ == "__main__":
    main()
