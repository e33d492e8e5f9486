"""Mesh-sharded serving and training (ISSUE 5).

Two layers of coverage:

- in-process: rule-set contents, dim_sharding divisibility fallback, the
  ParamSpec/_mesh ValueError bugfixes, AdapterBank publish donation, and
  the engine's extra_batch validation.
- subprocess (forced 4 host devices, like test_dryrun_smoke): on a
  2x2 (`data`, `model`) mesh, a mixed-domain ragged engine drain and a
  K-step HFSL round must match the unsharded path token-for-token /
  step-for-step, with the BatchBank `cluster` dim and the AdapterBank
  slot dim placed on `data` (asserted from the live array shardings and
  via jax.debug.visualize_array_sharding).
"""
import ast
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import get_config
from repro.core.adapter_bank import AdapterBank
from repro.launch.engine import DecodeEngine
from repro.models import model as M
from repro.sharding import rules as R

ROOT = os.path.join(os.path.dirname(__file__), "..")


# ---------------------------------------------------------------------------
# Rule sets + helpers (in-process, no mesh needed beyond 1 device)
# ---------------------------------------------------------------------------

def test_serving_rules_shape():
    r = R.serving_rules()
    assert r["batch"] == ("pod", "data")      # wave batch over data
    assert r["heads"] == "model"              # TP attention
    assert r["kv_seq"] is None                # per-row scatter stays local
    assert r["slots"] == ("pod", "data")      # bank slot parallelism


def test_hfsl_round_rules_disable_sequence_parallelism():
    r = R.hfsl_round_rules("dense")
    assert r["seq"] is None and r["cluster"] == ("pod", "data")
    # recurrent families keep their per-cluster batch rule
    assert R.hfsl_round_rules("ssm")["batch"] == "model"


def test_dim_sharding_divisibility_fallback():
    mesh = R.Mesh(np.asarray(jax.devices()[:1]).reshape(1, 1),
                  ("data", "model"))
    # size divides trivially on a 1-way axis
    sh = R.dim_sharding(mesh, 3, "slots", index=1)
    assert sh.spec == R.P(None, "data")
    # unknown logical name -> replicated
    assert R.dim_sharding(mesh, 4, "nonexistent").spec == R.P()


def test_param_spec_mismatch_raises_value_error():
    # bugfix: was a bare assert (vanishes under python -O)
    with pytest.raises(ValueError, match="logical axis per dim"):
        R.ParamSpec((4, 4), axes=("batch",))
    R.ParamSpec((4, 4), axes=("batch", None))          # valid: one per dim
    R.ParamSpec((4, 4))                                # valid: no axes


def test_mesh_too_few_devices_raises_value_error():
    # bugfix: was a bare assert (vanishes under python -O)
    from repro.launch.mesh import _mesh
    with pytest.raises(ValueError, match="devices"):
        _mesh((512, 512), ("data", "model"))


# ---------------------------------------------------------------------------
# AdapterBank publish donation (bugfix: hot-publish copied the whole bank)
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def small_bank_setup():
    cfg = get_config("vit-edge").reduced().with_(dtype="float32",
                                                 vocab_size=64)
    ks = jax.random.split(jax.random.PRNGKey(0), 4)
    adapters = {d: M.init(cfg, ks[i])["adapters"]
                for i, d in enumerate(["a", "b", "c"])}
    backbone = M.init(cfg, ks[-1])["backbone"]
    return cfg, backbone, adapters


def test_publish_donates_the_stacked_bank(small_bank_setup):
    """The hot-swap must reuse the resident buffers (donated input), not
    allocate a second bank — and serving behavior must be unchanged."""
    cfg, backbone, adapters = small_bank_setup
    bank = AdapterBank.create(adapters)
    before = jax.tree.leaves(bank.stacked)
    new = M.init(cfg, jax.random.PRNGKey(7))["adapters"]
    bank.publish("b", new)
    # donation invalidated the old buffers: the update was in place
    assert all(x.is_deleted() for x in before)
    # publish-then-serve parity: the published slot serves exactly like a
    # bank freshly created with the published adapters, other slots are
    # untouched, and snapshot() (non-donated) leaves the bank serving
    fresh = AdapterBank.create({**adapters, "b": new})
    prompts = np.asarray(jax.random.randint(
        jax.random.PRNGKey(9), (3, 10), 0, cfg.vocab_size))
    for g, w in zip(jax.tree.leaves(bank.snapshot("b")),
                    jax.tree.leaves(new)):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))
    got, _ = DecodeEngine(cfg, slots=3, bank=bank).serve(
        bank.serving_params(backbone), prompts, gen=4,
        domains=["a", "b", "c"])
    want, _ = DecodeEngine(cfg, slots=3, bank=fresh).serve(
        fresh.serving_params(backbone), prompts, gen=4,
        domains=["a", "b", "c"])
    np.testing.assert_array_equal(got, want)


# ---------------------------------------------------------------------------
# Engine serve() validates extra_batch (bugfix)
# ---------------------------------------------------------------------------

def test_serve_validates_extra_batch_rows():
    vcfg = get_config("llava-next-mistral-7b").reduced().with_(
        dtype="float32", vocab_size=64)
    params = M.init(vcfg, jax.random.PRNGKey(0))
    engine = DecodeEngine(vcfg, slots=2)
    prompts = np.zeros((3, 6), np.int32)
    short = np.zeros((2, vcfg.vlm.n_vis_tokens, vcfg.d_model), np.float32)
    with pytest.raises(ValueError, match="extra_batch\\['vision_embeds'\\]"):
        engine.serve(params, prompts, gen=2,
                     extra_batch={"vision_embeds": short})
    # a LONGER leading dim must also be rejected (silent truncation before)
    long = np.zeros((5, vcfg.vlm.n_vis_tokens, vcfg.d_model), np.float32)
    with pytest.raises(ValueError, match="one\\s+row per prompt"):
        engine.serve(params, prompts, gen=2,
                     extra_batch={"vision_embeds": long})
    assert engine.pending() == 0              # nothing half-submitted


# ---------------------------------------------------------------------------
# Host-device mesh parity (subprocess: needs forced host devices)
# ---------------------------------------------------------------------------

_MESH_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    os.environ["JAX_PLATFORMS"] = "cpu"
    import sys; sys.path.insert(0, "src")
    import numpy as np
    import jax, jax.numpy as jnp
    jax.config.update("jax_default_matmul_precision", "highest")

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

    mesh = make_test_mesh(2, 2)
    cfg = get_config("vit-edge").reduced().with_(dtype="float32",
                                                 vocab_size=64)
    DOMS = ["d0", "d1", "d2", "d3"]
    ks = jax.random.split(jax.random.PRNGKey(0), len(DOMS) + 1)
    adapters = {d: M.init(cfg, ks[i])["adapters"]
                for i, d in enumerate(DOMS)}
    backbone = M.init(cfg, ks[-1])["backbone"]

    # --- mixed-domain ragged drain: sharded == unsharded, token for token
    key = jax.random.PRNGKey(5)
    short = np.asarray(jax.random.randint(key, (4, 8), 0, cfg.vocab_size))
    long = np.asarray(jax.random.randint(key, (4, 12), 0, cfg.vocab_size))
    reqs = [(short[0], "d0", 4), (long[0], "d1", 3), (short[1], "d2", 5),
            (long[1], "d3", 4), (short[2], "d0", 2), (long[2], "d1", 6),
            (short[3], "d2", 3), (long[3], "d3", 4)]
    bank_u = AdapterBank.create(adapters)
    eng_u = DecodeEngine(cfg, slots=4, bank=bank_u)
    uids_u = [eng_u.submit(t, g, domain=d) for t, d, g in reqs]
    comps_u, _ = eng_u.run(bank_u.serving_params(backbone))
    want = {c.uid: c.tokens for c in comps_u}

    bank_s = AdapterBank.create(adapters, mesh=mesh)
    bb_s = M.place_params({"backbone": backbone}, cfg, mesh)["backbone"]
    eng_s = DecodeEngine(cfg, slots=4, bank=bank_s, mesh=mesh)
    uids_s = [eng_s.submit(t, g, domain=d) for t, d, g in reqs]
    comps_s, stats_s = eng_s.run(bank_s.serving_params(bb_s))
    got = {c.uid: c.tokens for c in comps_s}
    for uu, us in zip(uids_u, uids_s):
        np.testing.assert_array_equal(got[us], want[uu])
    assert stats_s.requests == len(reqs)
    print("DRAIN_PARITY_OK", stats_s.tokens)

    # --- placements: slot dims on `data` (4 slots over the 2-way axis)
    stack_leaf = jax.tree.leaves(bank_s.stacked["stack"])[0]
    head_leaf = bank_s.stacked["head"]["w"]
    assert stack_leaf.sharding.spec == R.P(None, "data"), \\
        stack_leaf.sharding.spec
    assert head_leaf.sharding.spec[0] == "data", head_leaf.sharding.spec
    jax.debug.visualize_array_sharding(
        head_leaf.reshape(head_leaf.shape[0], -1))
    print("BANK_PLACEMENT_OK")

    # --- K-step HFSL round: sharded == unsharded, step for step
    C, BATCH, STEPS = 4, 4, 4
    opt = adamw(5e-3)
    task = ClassificationTask(5, 64, 24, class_strength=0.6, seed=0)
    data = task.dataset(40 * C, seed=11)
    parts = partition_by_classes(data["label"], C, cfg.peft.head_dim_out,
                                 seed=1)
    state0 = hfsl.init_hfsl_state(jax.random.PRNGKey(3), cfg, C, opt, M.init)
    bank_ut = BatchBank.pack(data, parts, BATCH, seed=2)
    round_u = hfsl.make_hfsl_round(cfg, opt, M.classify_loss, steps=STEPS,
                                   sync_every=2)
    su, mu = round_u(state0, bank_ut.arrays, 0)

    rules = R.hfsl_round_rules(cfg.family)
    spec = hfsl.hfsl_state_spec(cfg, C, opt, M.model_spec)
    sh = hfsl.hfsl_state_shardings(cfg, C, opt, M.model_spec, mesh, rules)
    state_s = jax.device_put(state0, sh)
    bank_st = BatchBank.pack(data, parts, BATCH, seed=2, mesh=mesh,
                             rules=rules)
    assert jax.tree.leaves(bank_st.arrays)[0].sharding.spec \\
        == R.P(None, "data")
    round_s = hfsl.make_hfsl_round(cfg, opt, M.classify_loss, steps=STEPS,
                                   sync_every=2, mesh=mesh, rules=rules,
                                   state_spec=spec, donate=True)
    ss, ms = round_s(state_s, bank_st.arrays, 0)
    # per-STEP losses match (the scan replays the same local steps +
    # FedAvg boundaries; only cross-device reduction order may differ)
    np.testing.assert_allclose(np.asarray(ms["loss"]),
                               np.asarray(mu["loss"]),
                               rtol=2e-5, atol=1e-6)
    for g, w in zip(jax.tree.leaves(ss["adapters_c"]),
                    jax.tree.leaves(su["adapters_c"])):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                   rtol=1e-3, atol=3e-5)
    assert int(ss["step"]) == STEPS
    # train state stays resident on its mesh slice (pinned out_shardings)
    a_leaf = jax.tree.leaves(ss["adapters_c"])[0]
    assert a_leaf.sharding.spec[0] == "data", a_leaf.sharding.spec
    jax.debug.visualize_array_sharding(
        a_leaf.reshape(a_leaf.shape[0], -1))
    print("ROUND_PARITY_OK", float(ms["loss"][-1]))

    # --- publish the sharded round's consensus; serve it sharded; tokens
    # must equal the unsharded round's consensus served unsharded
    cons_s = hfsl.consensus_params({"backbone": bb_s,
                                    "adapters_c": ss["adapters_c"]})
    cons_u = hfsl.consensus_params({"backbone": backbone,
                                    "adapters_c": su["adapters_c"]})
    bank_s.publish("d1", cons_s["adapters"])
    bank_u.publish("d1", cons_u["adapters"])
    p = np.asarray(jax.random.randint(key, (2, 9), 0, cfg.vocab_size))
    got2, _ = DecodeEngine(cfg, slots=2, bank=bank_s, mesh=mesh).serve(
        bank_s.serving_params(bb_s), p, gen=4, domains=["d1", "d1"])
    want2, _ = DecodeEngine(cfg, slots=2, bank=bank_u).serve(
        bank_u.serving_params(backbone), p, gen=4, domains=["d1", "d1"])
    np.testing.assert_array_equal(got2, want2)
    print("TRAIN_TO_SERVE_OK")

    # --- GaisNet(mesh=...) glue: the runtime wires BOTH sides itself
    # (init-time state/backbone placement, round shardings, bank, engine,
    # classify) — component parity is proven above; this guards the wiring
    import dataclasses
    from repro.core.integrated import GaisNet
    icfg = cfg.with_(peft=dataclasses.replace(cfg.peft, head_dim_out=5))
    tasks = {n: ClassificationTask(5, 64, 24, class_strength=0.6, seed=s)
             for n, s in [("nlp", 0), ("cv", 7)]}
    rt = GaisNet(icfg, tasks, mesh=mesh, n_clusters=2, steps_per_upgrade=2,
                 serve_batch=4, serve_gen=3, serve_slots=4, seed=0)
    assert jax.tree.leaves(rt.bank.stacked["stack"])[0].sharding.spec \\
        == R.P(None, "data")
    assert jax.tree.leaves(rt._banks["cv"].arrays)[0].sharding.spec \\
        == R.P(None, "data")
    assert jax.tree.leaves(
        rt.domains["nlp"].adapters_c)[0].sharding.spec[0] == "data"
    profit, cost = rt.produce(["nlp", "cv"])       # mixed sharded drain
    assert 0.0 <= profit <= rt.profit_scale and cost.tokens == 4 * 3
    v0 = rt.bank.version("nlp")
    rt.upgrade("nlp")                              # sharded donated round
    assert rt.bank.version("nlp") == v0 + 1
    assert jax.tree.leaves(                        # placement survives
        rt.domains["nlp"].adapters_c)[0].sharding.spec[0] == "data"
    profit2, _ = rt.produce("nlp")                 # serves the publish
    assert 0.0 <= profit2 <= rt.profit_scale
    print("GAISNET_MESH_OK")
""")


@pytest.fixture(scope="module")
def mesh_parity_run():
    r = subprocess.run([sys.executable, "-c", _MESH_SCRIPT], cwd=ROOT,
                       # a CPU child (forced host devices): never the chip
                       env={**os.environ, "JAX_PLATFORMS": "cpu"},
                       capture_output=True, text=True, timeout=900)
    return r


def test_mesh_drain_parity(mesh_parity_run):
    r = mesh_parity_run
    assert "DRAIN_PARITY_OK" in r.stdout, \
        (r.stdout[-2000:] + r.stderr[-3000:])
    assert "BANK_PLACEMENT_OK" in r.stdout, \
        (r.stdout[-2000:] + r.stderr[-3000:])


def test_mesh_round_parity(mesh_parity_run):
    r = mesh_parity_run
    assert "ROUND_PARITY_OK" in r.stdout, \
        (r.stdout[-2000:] + r.stderr[-3000:])


def test_mesh_train_to_serve_loop(mesh_parity_run):
    r = mesh_parity_run
    assert "TRAIN_TO_SERVE_OK" in r.stdout, \
        (r.stdout[-2000:] + r.stderr[-3000:])


def test_gaisnet_mesh_wiring(mesh_parity_run):
    r = mesh_parity_run
    assert "GAISNET_MESH_OK" in r.stdout, \
        (r.stdout[-2000:] + r.stderr[-3000:])


# ---------------------------------------------------------------------------
# CI budget: the default suite deselects `slow`
# ---------------------------------------------------------------------------

def test_default_suite_excludes_slow_marker():
    """Tier-1 (`pytest -x -q`) must stay inside the CI budget: the
    exhaustive sweeps are `slow`-marked and deselected by default addopts
    (run them explicitly with `pytest -m slow` / `-m ""`)."""
    with open(os.path.join(ROOT, "pyproject.toml")) as f:
        txt = f.read()
    assert "not slow" in txt and "addopts" in txt
    assert "slow:" in txt                     # marker stays registered


# ---------------------------------------------------------------------------
# Pallas kernels under a mesh (subprocess: forced host devices)
# ---------------------------------------------------------------------------

_KERNEL_MESH_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import sys; sys.path.insert(0, "src")
    import numpy as np
    import jax
    jax.config.update("jax_default_matmul_precision", "highest")

    from repro.configs.base import get_config
    from repro.core import hfsl
    from repro.core.adapter_bank import AdapterBank
    from repro.kernels import ops
    from repro.launch.engine import DecodeEngine
    from repro.launch.mesh import make_test_mesh
    from repro.models import model as M
    from repro.optim.optimizers import adamw
    from repro.sharding import rules as R

    mesh = make_test_mesh(2, 2)
    cfg = get_config("qwen2-7b").reduced().with_(dtype="float32",
                                                 vocab_size=64)
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    adapters = {d: M.init(cfg, ks[i])["adapters"]
                for i, d in enumerate(["d0", "d1"])}
    backbone = M.init(cfg, ks[-1])["backbone"]
    # init placed leaf by leaf on the mesh draws the same values
    placed = M.init(cfg, ks[-1], shardings=R.named_shardings(
        M.model_spec(cfg), mesh, R.serving_rules()))["backbone"]
    for a, b in zip(jax.tree.leaves(placed), jax.tree.leaves(backbone)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert len(jax.tree.leaves(placed)[0].sharding.device_set) == 4
    rng = np.random.default_rng(0)
    reqs = [(rng.integers(0, 64, size=n), ["d0", "d1"][i % 2], 4)
            for i, n in enumerate([5, 9, 12, 7, 3, 10])]

    with ops.backend("interpret"):
        # the kernels run inside a replicated shard_map under the mesh:
        # multi-tenant drain (flash_attention, flash_decode, lora_bgmv)
        outs = []
        for m in (None, mesh):
            bank = AdapterBank.create(adapters, mesh=m)
            bb = backbone if m is None else \\
                M.place_params({"backbone": backbone}, cfg, m)["backbone"]
            eng = DecodeEngine(cfg, slots=4, bank=bank, mesh=m)
            uids = [eng.submit(t, g, domain=d) for t, d, g in reqs]
            comps, _ = eng.run(bank.serving_params(bb))
            by = {c.uid: c.tokens for c in comps}
            outs.append(np.stack([by[u] for u in uids]))
        np.testing.assert_array_equal(outs[0], outs[1])
        print("KERNEL_DRAIN_OK")

        # HFSL round through the attention and LoRA kernels' VJPs
        C, opt = 2, adamw(1e-2)
        toks = rng.integers(0, 64, size=(1, C, 2, 9))
        bank = {"tokens": toks[..., :-1], "labels": toks[..., 1:]}
        state = hfsl.init_hfsl_state(None, cfg, C, opt, lambda c, k: {
            "backbone": backbone, "adapters": adapters["d0"]})
        losses = []
        for m in (None, mesh):
            kw = {}
            st = state
            if m is not None:
                rules = R.hfsl_round_rules(cfg.family)
                spec = hfsl.hfsl_state_spec(cfg, C, opt, M.model_spec)
                st = jax.device_put(state, R.named_shardings(spec, m, rules))
                kw = dict(mesh=m, rules=rules, state_spec=spec)
            rnd = hfsl.make_hfsl_round(cfg, opt, M.lm_loss, steps=3,
                                       sync_every=2, **kw)
            _, met = rnd(st, bank, 0)
            losses.append(np.asarray(met["loss"]))
        np.testing.assert_allclose(losses[1], losses[0], rtol=2e-5)
        print("KERNEL_ROUND_OK")
""")


def test_pallas_kernels_under_a_mesh_match_unsharded():
    """Engine drain and HFSL round with the interpret-mode Pallas kernels
    on a 2x2 host mesh equal the unsharded run."""
    r = subprocess.run([sys.executable, "-c", _KERNEL_MESH_SCRIPT], cwd=ROOT,
                       env={**os.environ, "JAX_PLATFORMS": "cpu"},
                       capture_output=True, text=True, timeout=900)
    assert "KERNEL_DRAIN_OK" in r.stdout, r.stdout[-2000:] + r.stderr[-3000:]
    assert "KERNEL_ROUND_OK" in r.stdout, r.stdout[-2000:] + r.stderr[-3000:]


# ---------------------------------------------------------------------------
# Kernel blocks under a mesh: which dims each device holds a slice of
# ---------------------------------------------------------------------------

_KERNEL_SPLIT_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import sys; sys.path.insert(0, "src")
    import jax, jax.numpy as jnp, numpy as np
    jax.config.update("jax_default_matmul_precision", "highest")
    from repro.kernels import ops
    from repro.launch.mesh import make_test_mesh
    from repro.sharding import rules as R

    mesh = make_test_mesh(2, 2)
    seen = []
    real = jax.shard_map

    def recording(f, **kw):
        seen.append(kw["in_specs"] + (kw["out_specs"],))
        return real(f, **kw)

    jax.shard_map = recording

    def run(name, fn, *args):
        ref = fn(*args)

        def on_mesh(*a):
            with R.use_rules(mesh, R.serving_rules()):
                return fn(*a)
        seen.clear()
        out = jax.jit(on_mesh)(*args)
        for x, y in zip(jax.tree.leaves(ref), jax.tree.leaves(out)):
            np.testing.assert_allclose(np.asarray(y), np.asarray(x),
                                       rtol=1e-5, atol=1e-5)
        def plain(s):                 # PartitionSpec(s) -> tuples
            return (tuple(s) if isinstance(s, jax.sharding.PartitionSpec)
                    else tuple(plain(x) for x in s))
        print("SPLIT", name, repr([[plain(s) for s in call]
                                    for call in seen]))

    k = jax.random.split(jax.random.PRNGKey(0), 8)
    q = jax.random.normal(k[0], (2, 16, 4, 64))
    kv = jax.random.normal(k[1], (2, 16, 2, 64))
    pos = jnp.arange(16)
    run("attention", lambda q, kv: ops.flash_attention(
        q, kv, 2 * kv, q_pos=pos, kv_pos=pos, backend="interpret"), q, kv)
    run("attention_one_kv_head", lambda q, kv: ops.flash_attention(
        q, kv[:, :, :1], kv[:, :, 1:], q_pos=pos, kv_pos=pos,
        backend="interpret"), q, kv)
    hm = jnp.swapaxes(kv, 1, 2)              # resident (B, Hkv, T, D)
    run("decode", lambda q, kv: ops.flash_decode(
        q[:, 0], kv, 2 * kv, q_pos=jnp.array([5, 9]), kv_pos=pos,
        backend="interpret"), q, hm)
    pfx = jax.random.normal(k[4], (2, 3, 2, 64))  # per-row prefix bank
    run("decode_prefix", lambda q, kv, p: ops.flash_decode(
        q[:, 0], kv, 2 * kv, q_pos=jnp.array([5, 9]), kv_pos=pos,
        prefix_k=p, prefix_v=-p, backend="interpret"), q, hm, pfx)
    pool = jax.random.normal(k[3], (6, 4, 2, 64))
    tbl = jnp.array([[0, 2, 4, 6], [1, 3, 5, 6]], jnp.int32)
    run("paged", lambda q, p: ops.flash_decode_paged(
        q[:, 0], p, 2 * p, tbl, q_pos=jnp.array([9, 12]),
        backend="interpret"), q, pool)
    x, w = jax.random.normal(k[5], (8, 32)), jax.random.normal(k[6], (32, 64))
    a, b = jax.random.normal(k[7], (32, 4)), jax.random.normal(k[2], (4, 64))
    bias = jnp.arange(64.0)
    run("lora_grad", jax.grad(lambda x, a, b: jnp.sum(jnp.sin(
        ops.lora_matmul(x, w, a, b, 2.0, bias, backend="interpret"))),
        argnums=(0, 1, 2)), x, a, b)
    A = jax.random.normal(k[3], (3, 32, 4))
    B = jax.random.normal(k[4], (3, 4, 64))
    ids = jnp.array([0, 2, 1, 1, 0, 2, 2, 1])
    run("bgmv_rows", lambda x: ops.lora_bgmv(
        x, w, A, B, ids, 2.0, bias, backend="interpret"), x)
    run("bgmv_seq", lambda x: ops.lora_bgmv(
        x.reshape(2, 4, 32), w, A, B, ids[:2], 2.0, backend="interpret"), x)
""")

_D, _M = "data", "model"
_BSHD = (_D, None, _M, None)
_BHD = (_D, _M, None)
_POOL = (None, None, _M, None)
_CACHE = (None, _D, _M, None, None)
_KERNEL_SPLITS = {
    # q, k, v split by sequence over `data` and by head over `model`
    "attention": [[_BSHD, _BSHD, _BSHD, (None,), (None,), _BSHD]],
    # one KV head cannot split over two model devices: heads stay whole
    "attention_one_kv_head": [[(_D, None, None, None)] * 3
                              + [(None,), (None,), (_D, None, None, None)]],
    # the resident cache (L, B, Hkv, T, D) splits rows and whole heads
    "decode": [[_BHD, _CACHE, _CACHE, (None,), (_D,), (_D, None), _BHD]],
    "decode_prefix": [[_BHD, _CACHE, _CACHE, (None,), (_D,), (_D, None),
                       (_D, _M, None, None), (_D, _M, None, None), _BHD]],
    # any row's table may name any block: the pool splits by head only
    "paged": [[_BHD, _POOL, _POOL, (_D, None), (_D,), _BHD]],
    # forward: output columns over `model`; dx contracts the split columns
    # (partials summed); dA / dB sum over rows and columns as they contract
    "lora_grad": [[(_D, None), (None, _M), (None, None), (None, _M), (_M,),
                   (_D, _M)],
                  [(_D, _M), (_M, None), (_M, None), (None, None),
                   (_D, None)],
                  [(_D, None), (_D, _M), (None, None), (None, _M),
                   ((None, None), (None, _M))]],
    "bgmv_rows": [[(_D, None), (None, _M), (None, None, None),
                   (None, None, _M), (_D,), (_M,), (_D, _M)]],
    "bgmv_seq": [[(_D, None, None), (None, _M), (None, None, None),
                  (None, None, _M), (_D,), (_D, None, _M)]],
}


@pytest.fixture(scope="module")
def kernel_split_run():
    r = subprocess.run([sys.executable, "-c", _KERNEL_SPLIT_SCRIPT], cwd=ROOT,
                       env={**os.environ, "JAX_PLATFORMS": "cpu"},
                       capture_output=True, text=True, timeout=600)
    got = {}
    for line in r.stdout.splitlines():
        if line.startswith("SPLIT "):
            _, name, specs = line.split(" ", 2)
            got[name] = specs
    return got, r.stdout[-2000:] + r.stderr[-3000:]


@pytest.mark.parametrize("name", sorted(_KERNEL_SPLITS))
def test_kernel_blocks_split_over_the_mesh(kernel_split_run, name):
    """Under a 2x2 (`data`, `model`) mesh each Pallas kernel runs on its
    device's block — rows over `data`, heads / projection columns over
    `model` where they divide — and equals the call without a mesh."""
    got, log = kernel_split_run
    assert name in got, log
    assert ast.literal_eval(got[name]) == _KERNEL_SPLITS[name]
