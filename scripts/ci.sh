#!/usr/bin/env bash
# One-command verify recipe: dev deps + tier-1 tests + kernel + mesh smokes.
#
#   bash scripts/ci.sh
#
# Mirrors what the ROADMAP calls tier-1 (`python -m pytest -x -q`) and adds
# a fast interpret-mode Pallas smoke (flash attention + flash decode — incl.
# the ragged per-row-position serving layout + multi-LoRA adapter_ids —
# + trainable LoRA matmul fwd/bwd) so kernel regressions surface even when
# the suite is filtered.
set -euo pipefail
cd "$(dirname "$0")/.."

python -m pip install -q -r requirements-dev.txt \
    || echo "[ci] pip install failed (offline?); using preinstalled deps"

export PYTHONPATH="src${PYTHONPATH:+:$PYTHONPATH}"
export JAX_PLATFORMS="${JAX_PLATFORMS:-cpu}"

# tracelint gate first: pure-AST, ~2s, catches hot-path regressions
# (cache-key drift, host syncs, wall clocks, unregistered kernels)
# before the suite spends minutes reproducing them dynamically
python -m repro.analysis
echo "[ci] tracelint gate OK (R1-R6 clean against an empty baseline)"

python -m pytest -x -q

python - <<'PY'
import jax, jax.numpy as jnp, numpy as np
from repro.kernels import ops, ref

key = jax.random.PRNGKey(0)
B, S, T, Hq, Hkv, D = 1, 16, 24, 4, 2, 32
ks = jax.random.split(key, 3)
q = jax.random.normal(ks[0], (B, S, Hq, D))
k = jax.random.normal(ks[1], (B, T, Hkv, D))
v = jax.random.normal(ks[2], (B, T, Hkv, D))
qp, kp = jnp.arange(S), jnp.arange(T) - (T - S)
want = ref.attention(q, k, v, q_pos=qp, kv_pos=kp)
got = ops.flash_attention(q, k, v, q_pos=qp, kv_pos=kp, block_q=8,
                          block_kv=8, backend="interpret")
np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-3)

want = ref.decode_attention(q[:, -1], k, v, q_pos=S - 1, kv_pos=kp)
hm = lambda t: jnp.swapaxes(t, 1, 2)          # resident head-major cache
got = ops.flash_decode(q[:, -1], hm(k), hm(v), q_pos=S - 1, kv_pos=kp,
                       backend="interpret")
np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-3)

# ragged mixed-length serving layout: per-row q positions against a cache
# whose rows are written to DIFFERENT depths (+1e9 sentinel beyond each
# row's length) — the engine's continuous-batching decode shape
q2, k2, v2 = (jnp.tile(t, (2,) + (1,) * (t.ndim - 1))
              for t in (q[:, -1], k, v))                # 2-row wave
written = jnp.asarray([10, 18])                         # per-row cache fill
kp_rag = jnp.where(jnp.arange(T)[None, :] < written[:, None],
                   jnp.arange(T)[None, :], 10 ** 9)     # (2, T)
qp_rag = written - 1                                    # (2,)
want = ref.decode_attention(q2, k2, v2, q_pos=qp_rag, kv_pos=kp_rag)
got = ops.flash_decode(q2, hm(k2), hm(v2), q_pos=qp_rag, kv_pos=kp_rag,
                       backend="interpret")
np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-3)

# trainable LoRA matmul: fused forward + custom-VJP adapter grads
M_, K_, N_, r_ = 16, 32, 24, 4
ks = jax.random.split(key, 5)
x = jax.random.normal(ks[0], (M_, K_))
w = jax.random.normal(ks[1], (K_, N_)) * 0.05
a = jax.random.normal(ks[2], (K_, r_)) * 0.05
b = jax.random.normal(ks[3], (r_, N_)) * 0.05
dy = jax.random.normal(ks[4], (M_, N_))
want = ref.lora_matmul(x, w, a, b, 2.0)
got = ops.lora_matmul(x, w, a, b, 2.0, backend="interpret")
np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                           atol=1e-3, rtol=1e-3)
f = lambda x_, a_, b_: jnp.vdot(
    ops.lora_matmul(x_, w, a_, b_, 2.0, backend="interpret"), dy)
dx, da, db = jax.grad(f, argnums=(0, 1, 2))(x, a, b)
rdx, rda, rdb = ref.lora_matmul_bwd(x, w, a, b, 2.0, dy)
for g, r in ((dx, rdx), (da, rda), (db, rdb)):
    np.testing.assert_allclose(np.asarray(g), np.asarray(r),
                               atol=1e-3, rtol=1e-3)

# batched multi-LoRA (multi-tenant serving): rows (BGMV, masked-accumulation)
# and sequence (scalar-prefetched gather) fwd vs the gather oracle
n_slots = 3
ks = jax.random.split(key, 3)
a_s = jax.random.normal(ks[0], (n_slots, K_, r_)) * 0.05
b_s = jax.random.normal(ks[1], (n_slots, r_, N_)) * 0.05
ids = jax.random.randint(ks[2], (M_,), 0, n_slots, dtype=jnp.int32)
want = ref.lora_bgmv(x, w, a_s, b_s, ids, 2.0)
got = ops.lora_bgmv(x, w, a_s, b_s, ids, 2.0, backend="interpret")
np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                           atol=1e-3, rtol=1e-3)
xs = x.reshape(4, M_ // 4, K_)
want = ref.lora_bgmv(xs, w, a_s, b_s, ids[:4], 2.0)
got = ops.lora_bgmv(xs, w, a_s, b_s, ids[:4], 2.0, backend="interpret")
np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                           atol=1e-3, rtol=1e-3)
print("[ci] interpret-mode kernel smoke OK "
      "(attn + decode + ragged per-row decode + lora fwd/bwd "
      "+ multi-lora gathered fwd)")
PY

# Chaos smoke: a FaultPlan-driven integrated round (dropout + NaN-poisoned
# cluster updates) must complete with a finite serving bank, and a forced
# bad publish must be refused at the bank door with last-known-good
# rollback restoring the slot bitwise (the full sweep: tests/test_faults.py,
# `pytest -m chaos`).
python - <<'PY'
import dataclasses
import jax, jax.numpy as jnp, numpy as np
from repro.configs.base import get_config
from repro.core.faults import FaultPlan
from repro.core.integrated import IntegratedRuntime
from repro.data.synthetic import ClassificationTask

cfg = get_config("vit-edge").reduced().with_(dtype="float32", vocab_size=64)
cfg = cfg.with_(peft=dataclasses.replace(cfg.peft, head_dim_out=5))
tasks = {n: ClassificationTask(5, 64, 16, seed=i)
         for i, n in enumerate(["nlp", "cv"])}
plan = FaultPlan(seed=3, dropout=0.4, grad_nan=0.4)
rt = IntegratedRuntime(cfg, tasks, n_clusters=4, steps_per_upgrade=4,
                       batch=4, sync_every=2, serve_batch=8, serve_gen=2,
                       serve_slots=4, seed=0, faults=plan)
recs = rt.run(["nlp", "cv", "nlp"], policy=lambda r, lv: r % 2 if r < 2 else 2)
assert len(recs) == 3, recs
ups = [r for r in recs if r.action == "upgrade"]
assert sum(r.cost.dropped_clusters + r.cost.skipped_updates
           for r in ups) > 0, "chaos plan never fired"
for x in jax.tree.leaves(rt.bank.stacked):
    assert np.isfinite(np.asarray(x, np.float32)).all(), "bank went non-finite"

good = rt.bank.snapshot("nlp")
try:
    rt.bank.publish("nlp", jax.tree.map(lambda x: x * jnp.nan, good))
    raise SystemExit("poisoned publish was accepted")
except ValueError:
    pass
rt.bank.publish("nlp", jax.tree.map(lambda x: x + 1.0, good))
rt.bank.rollback("nlp")
for g, w in zip(jax.tree.leaves(rt.bank.snapshot("nlp")),
                jax.tree.leaves(good)):
    np.testing.assert_array_equal(np.asarray(g), np.asarray(w))
print("[ci] chaos smoke OK (masked round completed finite; "
      "bad publish refused; LKG rollback bitwise)")
PY

# Speculative-serving smoke: a spec engine drain (tiny recurrent drafter,
# batched verify, exact-match acceptance + rollback) must be token-for-token
# identical to plain generate_scan, with the plain baseline's decode
# attention running through the interpret-mode Pallas flash-decode path —
# so parity here covers kernel decode vs pure-jnp verify agreement too
# (the full sweep: tests/test_spec_decode.py).
python - <<'PY'
import jax, jax.numpy as jnp, numpy as np
from repro.configs.base import get_config
from repro.core.spec_decode import SpecDecoder, spec_generate
from repro.kernels import ops
from repro.launch.engine import DecodeEngine
from repro.models import model as M

cfg = get_config("vit-edge").reduced().with_(dtype="float32", vocab_size=64)
params = M.init(cfg, jax.random.PRNGKey(0))
spec = SpecDecoder.init(cfg, jax.random.PRNGKey(7), k=3)
prompts = np.asarray(jax.random.randint(
    jax.random.PRNGKey(1), (3, 10), 1, cfg.vocab_size, dtype=jnp.int32))
with ops.backend("interpret"):
    ref = np.asarray(M.generate_scan(params, cfg, jnp.asarray(prompts),
                                     gen=7))
out, stats = spec_generate(params, cfg, spec, prompts, gen=7)
np.testing.assert_array_equal(np.asarray(out), ref)
eng = DecodeEngine(cfg, slots=2, spec=spec)
served, st = eng.serve(params, prompts, gen=7)
np.testing.assert_array_equal(served, ref)
assert st.drafted > 0 and st.acceptance_rate == st.accepted / st.drafted
print("[ci] speculative smoke OK (spec_generate + spec engine drain "
      f"token-identical to greedy scan; acceptance {st.acceptance_rate:.2f})")
PY

# Host-device mesh smoke: benchmarks/shard_bench.py spawns a forced
# 4-host-device ('data','model') mesh subprocess, hard-asserts that the
# sharded engine drain is token-identical and the sharded HFSL round is
# loss-identical to the unsharded path, and checks the AdapterBank slot /
# BatchBank cluster placements (the full sweep, incl. the hot-publish
# train-to-serve loop, lives in tests/test_mesh_sharding.py).
python -m benchmarks.shard_bench
echo "[ci] host-device mesh smoke OK (sharded drain + sharded HFSL round parity)"

# Telemetry smoke: trace one mixed-domain drain + one HFSL upgrade round
# end-to-end and check the exported Chrome trace parses and contains the
# request-lifecycle, segment, round-dispatch, and bank-publish spans the
# observability layer promises (the full sweep: tests/test_telemetry.py).
python - <<'PY'
import dataclasses, json, tempfile, os
import numpy as np
from repro.configs.base import get_config
from repro.core import telemetry
from repro.core.integrated import IntegratedRuntime
from repro.data.synthetic import ClassificationTask

cfg = get_config("vit-edge").reduced().with_(dtype="float32", vocab_size=64)
cfg = cfg.with_(peft=dataclasses.replace(cfg.peft, head_dim_out=5))
tasks = {n: ClassificationTask(5, 64, 16, seed=i)
         for i, n in enumerate(["nlp", "cv"])}
tel = telemetry.enable()
rt = IntegratedRuntime(cfg, tasks, n_clusters=2, steps_per_upgrade=2,
                       batch=4, sync_every=2, serve_batch=8, serve_gen=2,
                       serve_slots=4, seed=0)
rt.upgrade("nlp")
rt.produce(["nlp", "cv"])
telemetry.disable()

path = os.path.join(tempfile.mkdtemp(), "trace.json")
n = tel.export_trace(path)
doc = json.load(open(path))
names = {e["name"] for e in doc["traceEvents"] if e.get("ph") == "X"}
for want in ("engine.prefill", "engine.segment", "engine.request",
             "engine.drain", "hfsl.round_dispatch", "bank.publish",
             "integrated.upgrade", "integrated.produce"):
    assert want in names, f"trace missing span {want!r} (got {sorted(names)})"
assert n == sum(1 for e in doc["traceEvents"] if e.get("ph") == "X")
assert tel.counters["engine.retired"] == 8
assert tel.hist_summary("engine.ttft_s")["count"] == 8
print(f"[ci] telemetry smoke OK ({n} spans; traced upgrade+produce round, "
      "Perfetto JSON parses with lifecycle/segment/round/publish spans)")
PY

# Paged-KV smoke: the interpret-mode block-table kernel must match the
# paged oracle bit-for-bit against the xla gather path's visible set, and
# a prefix-sharing paged drain must serve token-identically to dense
# serving while prefilling each shared block exactly once (the full
# sweep: tests/test_ragged.py paged suite + tests/test_kernels.py).
python - <<'PY'
import jax, jax.numpy as jnp, numpy as np
from repro.kernels import ops, ref

key = jax.random.PRNGKey(3)
B, maxb, bs, Hq, Hkv, D, nb = 2, 4, 8, 4, 2, 32, 16
ks = jax.random.split(key, 3)
q = jax.random.normal(ks[0], (B, Hq, D))
k_pool = jax.random.normal(ks[1], (nb, bs, Hkv, D))
v_pool = jax.random.normal(ks[2], (nb, bs, Hkv, D))
rng = np.random.default_rng(0)
table = jnp.asarray(np.stack([rng.choice(nb, maxb, replace=False)
                              for _ in range(B)]).astype(np.int32))
q_pos = jnp.asarray([maxb * bs - 1, maxb * bs - 9], jnp.int32)
want = ref.paged_decode_attention(q, k_pool, v_pool, table, q_pos=q_pos)
for backend in ("xla", "interpret"):
    got = ops.flash_decode_paged(q, k_pool, v_pool, table, q_pos=q_pos,
                                 backend=backend)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-3)
# fp32 bit-parity paged-vs-dense on the engine's xla path: same visible
# values + same accumulation order == bitwise-equal decode outputs
k = jnp.swapaxes(k_pool[table].reshape(B, maxb * bs, Hkv, D), 1, 2)
v = jnp.swapaxes(v_pool[table].reshape(B, maxb * bs, Hkv, D), 1, 2)
dense = ops.flash_decode(q, k, v, q_pos=q_pos,
                         kv_pos=jnp.arange(maxb * bs, dtype=jnp.int32),
                         window=0, causal=True, backend="xla")
paged = ops.flash_decode_paged(q, k_pool, v_pool, table, q_pos=q_pos,
                               backend="xla")
np.testing.assert_array_equal(np.asarray(paged), np.asarray(dense))
print("[ci] paged flash-decode smoke OK (block-table kernel vs oracle; "
      "fp32 bit-parity paged-vs-dense)")
PY

python - <<'PY'
import jax, jax.numpy as jnp, numpy as np
from repro.configs.base import get_config
from repro.core.paged import PagedSpec
from repro.launch.engine import DecodeEngine
from repro.models import model as M

cfg = get_config("qwen2-7b").reduced().with_(dtype="float32", vocab_size=64)
params = M.init(cfg, jax.random.PRNGKey(7))
bs, gen = 4, 3
rng = np.random.default_rng(1)
prefix = rng.integers(0, 64, 2 * bs).astype(np.int32)   # 2 full shared blocks
rows = [np.concatenate([prefix, rng.integers(0, 64, 3).astype(np.int32)])
        for _ in range(3)]
eng = DecodeEngine(cfg, slots=4,
                   paged=PagedSpec(n_blocks=32, block_size=bs,
                                   share_prefix=True))
uids = [eng.submit(r, gen) for r in rows]
comps, stats = eng.run(params)
assert stats.prefix_hits == 2, stats.prefix_hits
naive = sum(-(-(len(r) + gen) // bs) for r in rows)
assert eng._alloc.allocated == naive - 4       # shared blocks prefilled once
by_uid = {c.uid: c.tokens for c in comps}
for uid, r in zip(uids, rows):
    want = np.asarray(M.generate_scan(params, cfg, jnp.asarray(r[None]),
                                      gen=gen))[0]
    np.testing.assert_array_equal(by_uid[uid], want)
assert eng._alloc.used_blocks == 0
eng._alloc.check()
print("[ci] paged prefix-sharing smoke OK (2 prefix hits, shared blocks "
      "prefilled once, drain token-identical to solo serving)")
PY
