"""TPU compile rehearsals of the main-path kernels at qwen2-7b widths.

Each kernel test compiles one Pallas kernel for a described (not attached)
TPU v5e chip and asserts the compiled program carries the kernel
(``tpu_custom_call``); the mesh tests do the same for whole serving and
training programs over four described chips. This is what interpret mode cannot check: the
TPU compiler's block-shape, tiling and VMEM rules. Nothing runs, so these
say nothing about results or times; the ``ref.py`` oracles and the
interpret-mode sweeps in test_kernels.py cover correctness.

The topology is described inside a module fixture (never at import time):
only one process may load the TPU library, so only the worker that runs
this file loads it, and every worker still collects the same tests.
"""
import math
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import flash_attention as fa
from repro.kernels import flash_decode as fd
from repro.kernels import lora_bgmv as lb
from repro.kernels import lora_matmul as lm

# qwen2-7b published widths
D_MODEL, HQ, HKV, HD, RANK, SLOTS, N_PREFIX = 3584, 28, 4, 128, 8, 4, 16
BF16, I32 = jnp.bfloat16, jnp.int32


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:      # no TPU compiler here: nothing to rehearse
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def shape(topo):
    """ShapeDtypeStruct factory placed on one described chip."""
    one = SingleDeviceSharding(topo.devices[0])
    return lambda s, dt=BF16: jax.ShapeDtypeStruct(s, dt, sharding=one)


@pytest.fixture(autouse=True)
def no_persistent_cache():
    """A compile for a described chip cannot be read back without one."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


def _assert_kernel(fn, *args):
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text


def test_flash_attention_prefill_2048(shape):
    S, T = 2048, 2048 + N_PREFIX
    _assert_kernel(
        lambda q, k, v, qp, kp: fa.flash_attention_pallas(
            q, k, v, q_pos=qp, kv_pos=kp),
        shape((1, S, HQ, HD)), shape((1, T, HKV, HD)), shape((1, T, HKV, HD)),
        shape((S,), I32), shape((T,), I32))


@pytest.mark.parametrize("block_kv", [16, 256])
def test_flash_decode_dense(shape, block_kv):
    """The kernel reads one layer of the resident stacked cache
    (L, B, Hkv, T, D) in place, with a per-row prefix bank."""
    B, T, L = 8, 4096, 3
    _assert_kernel(
        lambda q, k, v, lay, qp, kp, pk, pv: fd.flash_decode_pallas(
            q, k, v, lay, q_pos=qp, kv_pos=kp, prefix_k=pk, prefix_v=pv,
            block_kv=block_kv),
        shape((B, HQ, HD)), shape((L, B, HKV, T, HD)),
        shape((L, B, HKV, T, HD)), shape((1,), I32), shape((B,), I32),
        shape((B, T), I32), shape((B, HKV, N_PREFIX, HD)),
        shape((B, HKV, N_PREFIX, HD)))


def test_flash_decode_paged(shape):
    B, T, bs = 8, 4096, 16
    maxb = T // bs
    nb = B * maxb + 8
    _assert_kernel(
        lambda q, k, v, t, qp: fd.flash_decode_paged_pallas(
            q, k, v, t, q_pos=qp),
        shape((B, HQ, HD)), shape((nb, bs, HKV, HD)),
        shape((nb, bs, HKV, HD)), shape((B, maxb), I32), shape((B,), I32))


@pytest.mark.parametrize("M", [8, 2048])       # decode rows, prefill rows
def test_lora_matmul_forward(shape, M):
    K = N = D_MODEL
    _assert_kernel(
        lambda x, w, a, b, bias: lm.lora_matmul_pallas(x, w, a, b, 2.0, bias),
        shape((M, K)), shape((K, N)), shape((K, RANK)), shape((RANK, N)),
        shape((N,)))


@pytest.mark.parametrize("M", [128, 2048])      # decode rows, prefill rows
def test_lora_matmul_split_columns_read_in_place(shape, M):
    """q's columns split four ways (896 a chip, not a multiple of the
    512-column tile) take one lane-aligned tile: the kernel reads w as it
    lies, with no padded copy of it per call."""
    K, N = D_MODEL, HQ * HD // 4
    text = jax.jit(lambda x, w, a, b, bias: lm.lora_matmul_pallas(
        x, w, a, b, 2.0, bias)).lower(
        shape((M, K)), shape((K, N)), shape((K, RANK)), shape((RANK, N)),
        shape((N,))).compile().as_text()
    assert "tpu_custom_call" in text
    tiled = f"{K},{-(-N // 512) * 512}"             # w padded to the tile
    padded = [line.strip()[:120] for line in text.splitlines()
              if (m := _INSTR.match(line)) and m.group(2) == tiled]
    assert not padded, padded


def test_lora_matmul_backward(shape):
    M, K, N = 2048, D_MODEL, D_MODEL
    _assert_kernel(
        lambda x, dy, a, b: lm.lora_matmul_bwd_pallas(x, dy, a, b, 2.0),
        shape((M, K)), shape((M, N)), shape((K, RANK)), shape((RANK, N)))


def test_lora_bgmv_rows(shape):
    M, K, N = 8, D_MODEL, D_MODEL
    _assert_kernel(
        lambda x, w, a, b, ids, bias: lb.lora_bgmv_rows_pallas(
            x, w, a, b, ids, 2.0, bias),
        shape((M, K)), shape((K, N)), shape((SLOTS, K, RANK)),
        shape((SLOTS, RANK, N)), shape((M,), I32), shape((N,)))


@pytest.mark.parametrize("S", [100, 2048])     # ragged width, long prompt
def test_lora_bgmv_seq(shape, S):
    B, K, N = 8, D_MODEL, D_MODEL
    _assert_kernel(
        lambda x, w, a, b, ids, bias: lb.lora_bgmv_seq_pallas(
            x, w, a, b, ids, 2.0, bias),
        shape((B, S, K)), shape((K, N)), shape((SLOTS, K, RANK)),
        shape((SLOTS, RANK, N)), shape((B,), I32), shape((N,)))


# ---------------------------------------------------------------------------
# The decode step's cache: resident, written in place, donated
# ---------------------------------------------------------------------------

_RELAYOUT = ("copy", "copy-start", "concatenate", "pad", "transpose",
             "reshape", "dynamic-slice")
_INSTR = re.compile(r"^\s*(?:ROOT )?%(\S+) = \w+\[([\d,]*)\]\S* "
                    r"([\w-]+)\(")


def _serving_program(shape, name):
    """qwen2-7b at published widths, 2 layers, 16 prefix slots: the
    decode segment (32 rows, cap 2048) or a 4-row refill into it, compiled
    for one described v5e with the Pallas kernels."""
    from repro.configs.base import get_config
    from repro.models import model as M
    from repro.sharding import rules as R
    cfg = get_config("qwen2-7b").with_depth(2)
    assert cfg.peft.n_prefix == N_PREFIX
    B, cap = 32, 2048

    def place(tree):
        return jax.tree.map(lambda s: shape(s.shape, s.dtype), tree)
    params = place(jax.eval_shape(lambda: M.init(cfg, jax.random.PRNGKey(0))))
    caches = place(R.shape_structs(M.cache_spec(cfg, B, cap)))
    if name == "segment":
        low = M._segment_fn(cfg, 4, True).lower(
            params, shape((B, 1), I32), caches, shape((B,), I32),
            shape((B,), I32), None, None)
    else:
        low = M._refill_fn(cfg, cap).lower(
            params, {"tokens": shape((4, 512), I32)}, shape((4,), I32),
            shape((4,), I32), shape((B, 1), I32), caches, shape((B,), I32),
            None)
    layer_k = B * cfg.n_kv_heads * cap * cfg.head_dim_
    return low.compile().as_text(), layer_k, B


def _cache_params_aliased(text):
    """Whether every cache parameter of the entry computation is aliased
    to an output (donated), and how many there are."""
    head, entry = text.splitlines()[0], text[text.index("\nENTRY"):]
    aliased = {int(p) for p in re.findall(r"\}: \((\d+), \{\}", head)}
    params = {int(n) for n in re.findall(
        r"%caches\S* = \S+ parameter\((\d+)\)", entry)}
    return params and params <= aliased, len(params)


@pytest.mark.parametrize("program", ["segment", "refill"])
def test_decode_cache_is_resident_and_donated(shape, program, monkeypatch):
    """The decode segment writes only the new tokens into the cache: no
    copy, concatenation, padding, transpose, reshape or slice of cache
    data in it (a result with the row dim) holds as much as one layer's K
    cache. The layer scan's slices of the stacked weights carry no row dim
    and are not the cache's. Segment and refill both take their cache
    arguments donated (aliased to their cache outputs), so no call copies
    the cache on entry."""
    from repro.kernels import ops
    monkeypatch.setattr(ops, "_BACKEND", "pallas")  # the CPU default is xla
    text, layer_k, rows = _serving_program(shape, program)
    assert "tpu_custom_call" in text
    ok, n = _cache_params_aliased(text)
    assert ok and n == 3, (n, text.splitlines()[0][:400])   # k, v, pos
    if program == "segment":
        big = []
        for line in text.splitlines():
            m = _INSTR.match(line)
            dims = [int(d) for d in m.group(2).split(",") if d] if m else []
            if m and m.group(3) in _RELAYOUT and rows in dims \
                    and math.prod(dims) >= layer_k:
                big.append(line.strip()[:160])
        assert not big, big


# ---------------------------------------------------------------------------
# Whole mesh programs: the kernels split over a described 1x4 mesh
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def mesh_setup(topo):
    """qwen2-7b at published widths, 2 layers, its parameters placed by the
    serving rules on a described 1x4 ('data', 'model') mesh."""
    import numpy as np
    from jax.sharding import Mesh

    from repro.configs.base import get_config
    from repro.models import model as M
    from repro.sharding import rules as R
    mesh = Mesh(np.asarray(topo.devices).reshape(1, 4), ("data", "model"))
    cfg = get_config("qwen2-7b").with_depth(2)
    shapes = jax.eval_shape(lambda: M.init(cfg, jax.random.PRNGKey(0)))
    sh = R.named_shardings(M.model_spec(cfg), mesh, R.serving_rules())
    params = jax.tree.map(
        lambda s, h: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=h),
        {k: shapes[k] for k in sh}, sh)
    return mesh, cfg, params


def _placed(tree, shardings):
    return jax.tree.map(
        lambda s, h: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=h),
        tree, shardings)


def _lower_program(name, mesh, cfg, params):
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.core import hfsl
    from repro.models import model as M
    from repro.optim.optimizers import adamw
    from repro.sharding import rules as R
    B, rows = 8, NamedSharding(mesh, P("data"))
    tokens = jax.ShapeDtypeStruct((B, 1024), I32, sharding=rows)
    lens = jax.ShapeDtypeStruct((B,), I32, sharding=rows)
    prefill = M._wave_prefill_fn(cfg, 2048, mesh)
    if name == "prefill":
        return prefill.lower(params, {"tokens": tokens}, lens, None)
    if name == "segment":
        out = prefill.lower(params, {"tokens": tokens}, lens, None).compile()
        tok, caches, pos = _placed(
            jax.eval_shape(prefill, params, {"tokens": tokens}, lens, None),
            out.output_shardings)
        return M._segment_fn(cfg, 32, True, mesh).lower(
            params, tok, caches, pos, pos, None, None)
    C, opt = 2, adamw(1e-2)
    rules = R.hfsl_round_rules(cfg.family)
    spec = hfsl.hfsl_state_spec(cfg, C, opt, M.model_spec)
    state_sh = R.named_shardings(spec, mesh, rules)
    state = _placed(jax.eval_shape(lambda: hfsl.init_hfsl_state(
        None, cfg, C, opt,
        lambda c, k: M.init(cfg, jax.random.PRNGKey(0)))), state_sh)
    core = hfsl.make_hfsl_round(cfg, opt, M.lm_loss, steps=2, sync_every=2,
                                mesh=mesh, rules=rules, state_spec=spec,
                                jit=False)
    # the plain round core, jitted with the shardings make_hfsl_round pins
    core = dict(zip(core.__code__.co_freevars, (
        c.cell_contents for c in core.__closure__)))["cores"][False]
    keys = hfsl._TRAIN_KEYS
    bank_sh = R.dim_sharding(mesh, C, "cluster", index=1, rules=rules)
    bank = {k: jax.ShapeDtypeStruct((1, C, 2, 256), I32, sharding=bank_sh)
            for k in ("tokens", "labels")}
    return jax.jit(core, in_shardings=(
        {k: state_sh[k] for k in keys}, state_sh["backbone"], bank_sh, None),
    ).lower({k: state[k] for k in keys}, state["backbone"], bank, 0)


@pytest.mark.parametrize("program", ["prefill", "segment", "hfsl_round"])
def test_mesh_program_compiles_with_kernels(mesh_setup, program,
                                            monkeypatch):
    """The engine's prefill and decode segment and the HFSL round compile
    for four described chips with their Pallas kernels inside (XLA cannot
    partition a Mosaic kernel: each runs in a shard_map), and each chip
    takes about a quarter of the model's bytes as arguments."""
    from repro.kernels import ops
    monkeypatch.setattr(ops, "_BACKEND", "pallas")  # the CPU default is xla
    mesh, cfg, params = mesh_setup
    compiled = _lower_program(program, mesh, cfg, params).compile()
    assert "tpu_custom_call" in compiled.as_text()
    model_bytes = sum(x.size * x.dtype.itemsize
                      for x in jax.tree.leaves(params))
    per_chip = compiled.memory_analysis().argument_size_in_bytes
    assert 0.2 < per_chip / model_bytes < 0.3


# ---------------------------------------------------------------------------
# The four-chip decode path: segment and refill of a 128-row wave
# ---------------------------------------------------------------------------

_COLLECTIVE = re.compile(r"^\s*(?:ROOT )?%\S+ = (\w+)\[([\d,]*)\]\S* "
                         r"(all-reduce|all-gather|reduce-scatter|"
                         r"collective-permute|all-to-all)(?:-start)?\(")


@pytest.fixture(scope="module")
def mesh_wave(mesh_setup):
    """The 128-row wave at cap 2048 as the engine holds it after its first
    prefill on the described 1x4 mesh: (tok, caches, pos) placed as that
    program's outputs are."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.kernels import ops
    from repro.models import model as M
    mesh, cfg, params = mesh_setup
    B, rows = 128, NamedSharding(mesh, P("data"))
    tokens = jax.ShapeDtypeStruct((B, 256), I32, sharding=rows)
    lens = jax.ShapeDtypeStruct((B,), I32, sharding=rows)
    prev, ops._BACKEND = ops._BACKEND, "pallas"
    try:
        prefill = M._wave_prefill_fn(cfg, 2048, mesh)
        out = prefill.lower(params, {"tokens": tokens}, lens, None).compile()
        wave = jax.eval_shape(prefill, params, {"tokens": tokens}, lens, None)
    finally:
        ops._BACKEND = prev
    return _placed(wave, out.output_shardings)


@pytest.mark.parametrize("program", ["segment", "refill"])
def test_mesh_decode_cache_split_and_donated(mesh_setup, mesh_wave, program,
                                             monkeypatch):
    """qwen2-7b widths, 2 layers, a 128-row wave at cap 2048 on four
    described chips: the decode segment, or a refill of 8 rows x 256 into
    the wave. The cache arguments are donated (aliased to the outputs);
    each chip holds a quarter of the cache (one KV head; only the small
    position plane is whole on every chip); and no collective moves as
    much as one layer's K cache of a chip or the wave's float32 logits:
    the all-reduces carry activations, and the greedy argmax over the
    vocabulary-split logits gathers each chip's (max, index). Every
    collective lies under a named scope that says what caused it."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.kernels import ops
    from repro.models import model as M
    monkeypatch.setattr(ops, "_BACKEND", "pallas")  # the CPU default is xla
    mesh, cfg, params = mesh_setup
    tok, caches, pos = mesh_wave
    if program == "segment":
        low = M._segment_fn(cfg, 4, True, mesh).lower(
            params, tok, caches, pos, pos, None, None)
    else:
        rows = NamedSharding(mesh, P("data"))
        r = lambda *s: jax.ShapeDtypeStruct(s, I32, sharding=rows)
        low = M._refill_fn(cfg, 2048, mesh).lower(
            params, {"tokens": r(8, 256)}, r(8), r(8), tok, caches, pos, None)
    compiled = low.compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    head, entry = text.splitlines()[0], text[text.index("\nENTRY"):]
    aliased = {int(p) for p in re.findall(r"\}: \((\d+), \{\}", head)}
    cache_params = {int(n) for n in re.findall(
        r"parameter\((\d+)\).*op_name=\"caches\[", entry)}
    assert len(cache_params) == 3 and cache_params <= aliased, \
        (cache_params, head[:400])                        # k, v, pos
    leaves = jax.tree.leaves(caches)
    whole = sum(x.size * x.dtype.itemsize for x in leaves)
    per_chip = sum(math.prod(x.sharding.shard_shape(x.shape))
                   * x.dtype.itemsize for x in leaves)
    assert 0.25 <= per_chip / whole < 0.26
    B, T = caches["g0"]["s0"]["k"].shape[1], caches["g0"]["s0"]["k"].shape[3]
    layer_k_chip = B * (cfg.n_kv_heads // 4) * T * cfg.head_dim_ * 2
    logits_f32 = B * cfg.vocab_size * 4
    item = {"bf16": 2, "f32": 4, "s32": 4, "u32": 4, "pred": 1}
    moved = [(m.group(3), m.group(1), m.group(2)) for m in map(
        _COLLECTIVE.match, text.splitlines()) if m]
    assert moved                              # the mesh's all-reduces
    named = [line for line in text.splitlines() if re.search(
        r" (?:all-reduce|all-gather|reduce-scatter|collective-permute|"
        r"all-to-all)(?:-start)?\(", line)]
    assert len(named) == len(moved), named    # none typed as a tuple
    unscoped = [line.strip()[:160] for line in named if not re.search(
        r'op_name="[^"]*/(?:attn|mlp|lm_head|sample|embed|kv_cache|lora)/',
        line)]
    assert not unscoped, unscoped             # each names what caused it
    big = [c for c in moved if item[c[1]] * math.prod(
        int(d) for d in c[2].split(",") if d) >= min(layer_k_chip,
                                                    logits_f32)]
    assert not big, big
