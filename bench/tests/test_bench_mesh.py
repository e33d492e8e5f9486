"""The mesh serving path at toy widths on four CPU host devices: the
``serve_mesh`` driver through ``bench/run.py``, its placed weights, the
served logits against the plain reference, and the mesh drain against the
same drain on one device.

One subprocess (the host device count is fixed before JAX starts) runs
everything with the Pallas kernels in interpret mode, so each kernel runs
inside ``ops._on_mesh``'s ``shard_map`` as on the chip, on a configuration
with 4 KV heads over a 1x4 ('data', 'model') mesh, in float32 with
full-precision contractions. It prints one JSON object that the tests
read."""
import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]
CELL = "qwen2-7b-tp4.decode-batch"
SEED = 2147483901

# Logits of the mesh drain against the reference, as the widest absolute
# difference in standard deviations of the reference's logits at that
# position. Both run in float32 with full-precision contractions, and
# differ only by summation order (the mesh's split contractions, blocked
# attention): about 1e-6 here. A single bfloat16 rounding of an operand
# on the way moves logits by about 1e-3 of their spread, so 1e-4 holds the
# path to float32 throughout.
LOGIT_TOL = 1e-4

SCRIPT = r"""
import contextlib, io, json, sys
import jax
import jax.numpy as jnp
import numpy as np
jax.config.update("jax_default_matmul_precision", "highest")
jax.config.update("jax_enable_compilation_cache", False)
from repro.core.telemetry import Telemetry
from repro.kernels import ops
from repro.launch.engine import DecodeEngine
from repro.models import model as M
from bench import check, run, weights as W, model as BM
from bench.drivers import serve_mesh as SM
ops.set_backend("interpret")
cell, seed, here = sys.argv[1], int(sys.argv[2]), sys.argv[3]
conf = json.load(open(here + "/tiny-mesh.json"))
traffic = json.load(open(here + "/tiny-mesh-closed.json"))
out = {}

buf = io.StringIO()
with contextlib.redirect_stdout(buf):
    out["rc"] = run.main(["--workload", cell, "--seed", str(seed),
                          "--seconds", "2", "--trace", "0"],
                         require_tpu=False, conf=conf, traffic=traffic)
out["line"] = json.loads(buf.getvalue().strip().splitlines()[-1])

cfg = BM.program_config(conf, registered=False)
mesh = SM.make_mesh(conf)
sh = SM.placements(cfg, mesh, 1)
bb, ad = SM.make_placed(conf, seed, 1, sh)
bb0, ad0 = W.make(conf, seed, 1)
flat = lambda t: jax.tree_util.tree_flatten_with_path(t)[0]
out["leaves"] = len(flat({"b": bb, "a": ad}))
out["unequal"] = [jax.tree_util.keystr(p) for (p, x), (_, y) in zip(
    flat({"b": bb, "a": ad}), flat({"b": bb0, "a": ad0}))
    if not np.array_equal(np.asarray(x), np.asarray(y))]
out["misplaced"] = [jax.tree_util.keystr(p) for p, x in flat(bb)
                    if x.sharding != sh["backbone/" + "/".join(
                        str(k.key) for k in p)]]
out["split_share"] = {
    k: x.sharding.shard_shape(x.shape)[-1] / x.shape[-1] if k == "wo" else
    x.sharding.shard_shape(x.shape)[0] / x.shape[0] if k == "table" else
    x.sharding.shard_shape(x.shape)[-1] / x.shape[-1]
    for k, x in (("wq", bb["layers"]["g0"]["s0"]["attn"]["wq"]),
                 ("gate", bb["layers"]["g0"]["s0"]["mlp"]["gate"]),
                 ("table", bb["lm_head"]["table"]))}

# prefill + greedy decode on the mesh, logits against the reference's
# full forward over each row's prompt and served tokens
params = M.place_params({"backbone": bb, "adapters": W.adapter(ad, 0)},
                        cfg, mesh)
rng = np.random.default_rng(seed)
lens = np.array([5, 9, 13, 16], np.int32)
B, S, G, cap = len(lens), 16, 6, 32
prompts = np.zeros((B, S), np.int32)
for i, n in enumerate(lens):
    prompts[i, :n] = rng.integers(0, conf["vocab_size"], n)

@jax.jit
def pre(p, t, n):
    with M._wave_rules(mesh):
        return M.prefill(p, {"tokens": t}, cfg, max_len=cap, prompt_lens=n)

@jax.jit
def dec(p, t, c, pos):
    with M._wave_rules(mesh):
        return M.decode_step(p, t, c, pos, cfg)

lg, caches = pre(params, jnp.asarray(prompts), jnp.asarray(lens))
steps = [np.asarray(lg[:, 0])]
pos = jnp.asarray(lens)
for _ in range(G - 1):
    tok = jnp.argmax(lg[:, -1], -1)[:, None].astype(jnp.int32)
    lg, caches = dec(params, tok, caches, pos)
    steps.append(np.asarray(lg[:, 0]))
    pos = pos + 1
got = np.stack(steps, 1)                             # (B, G, V)
ref = check.reference(conf)
gaps = []
for i, n in enumerate(lens):
    seq = np.concatenate([prompts[i, :n], got[i, :-1].argmax(-1)])
    with jax.default_matmul_precision("highest"):
        h = ref.hidden(conf, bb0, W.adapter(ad0, 0), jnp.asarray(seq))
        want = np.asarray(ref.logits(conf, bb0, h))[n - 1:]
    gaps.append(float(np.max(np.abs(got[i] - want)
                             / want.std(-1, keepdims=True))))
out["logit_gap"] = max(gaps)

# the same drain on the mesh and on one device, token for token; the mesh
# engine's gauges
def drain(engine, p):
    for i, n in enumerate(lens):
        engine.submit(prompts[i, :n], 12 + 3 * i)
    comps, _ = engine.run(p)
    return [list(map(int, c.tokens)) for c in sorted(comps,
                                                      key=lambda c: c.uid)]
tel = Telemetry()
out["mesh_tokens"] = drain(DecodeEngine(cfg, slots=4, mesh=mesh, tel=tel),
                           params)
out["solo_tokens"] = drain(DecodeEngine(cfg, slots=4),
                           {"backbone": bb0, "adapters": W.adapter(ad0, 0)})
out["gauges"] = tel.gauges
print(json.dumps(out))
"""


@pytest.fixture(scope="module")
def mesh_run():
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "XLA_FLAGS": "--xla_force_host_platform_device_count=4",
           "PYTHONPATH": os.pathsep.join([str(ROOT / "src"), str(ROOT)])}
    p = subprocess.run([sys.executable, "-c", SCRIPT, CELL, str(SEED),
                        str(HERE)], cwd=ROOT, env=env, capture_output=True,
                       text=True, timeout=900)
    assert p.returncode == 0, p.stderr[-4000:]
    return json.loads(p.stdout.strip().splitlines()[-1])


def test_mesh_driver_prints_correct(mesh_run):
    """bench/run.py runs the cell's driver on the mesh and the served
    tokens agree with the reference; every kernel split over the mesh."""
    line = mesh_run["line"]
    assert mesh_run["rc"] == 0
    assert line["correct"] is True and line["failed"] == 0
    assert line["device"]["count"] == 4
    info = line["info"]
    assert info["unsplit_kernel_calls"] == 0
    assert {"flash_attention", "flash_decode", "lora_matmul_fwd"} \
        <= set(info["mesh_kernel_calls"])


def test_placed_weights_are_the_seeded_weights(mesh_run):
    """Drawn into their shardings, the weights equal weights.make's leaf
    for leaf, each placed as the program's serving rules place it: heads,
    MLP columns and vocabulary rows a quarter on each device."""
    assert mesh_run["leaves"] > 10
    assert mesh_run["unequal"] == []
    assert mesh_run["misplaced"] == []
    assert mesh_run["split_share"] == {"wq": 0.25, "gate": 0.25,
                                       "table": 0.25}


def test_mesh_logits_match_the_reference(mesh_run):
    """Prefill and decode through the cache on the mesh give the logits of
    the reference's full forward (LOGIT_TOL says why this tolerance)."""
    assert mesh_run["logit_gap"] <= LOGIT_TOL


def test_mesh_drain_equals_one_device(mesh_run):
    assert mesh_run["mesh_tokens"] == mesh_run["solo_tokens"]
    assert [len(t) for t in mesh_run["mesh_tokens"]] == [12, 15, 18, 21]


def test_mesh_engine_gauges_its_cache(mesh_run):
    """The per-device cache is a quarter of the whole: K and V split one
    KV head a device; only the small position plane is whole on each."""
    g = mesh_run["gauges"]
    share = g["engine.cache_bytes_per_device"] / g["engine.cache_bytes"]
    assert 0.25 <= share < 0.26
