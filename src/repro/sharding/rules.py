"""Logical-axis sharding rules.

Single source of truth for parameter/activation layout:

- every parameter is declared once as a :class:`ParamSpec` (shape, dtype,
  logical axis names). From the spec tree we derive (a) initialized arrays,
  (b) `jax.ShapeDtypeStruct` stand-ins for the no-allocation dry-run, and
  (c) `PartitionSpec` trees for `jax.jit` in/out shardings.
- activations are constrained in model code via :func:`shard` using the same
  logical names, resolved against the active rule set.

Rules map a logical axis name -> mesh axis (str), tuple of mesh axes, or
``None`` (replicated). Rule sets are plain dicts so perf experiments can swap
them per run (see EXPERIMENTS.md §Perf).
"""
from __future__ import annotations

import contextlib
import dataclasses
import functools
import threading
from typing import Any, Callable, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

# ---------------------------------------------------------------------------
# Rule sets
# ---------------------------------------------------------------------------

# Production rules for the ('pod', 'data', 'model') mesh. On the single-pod
# ('data', 'model') mesh, the 'pod' axis name is simply absent and is dropped
# when resolving (see _resolve).
DEFAULT_RULES: dict[str, Any] = {
    # activations
    "batch": ("pod", "data"),
    "cluster": ("pod", "data"),       # HFSL client-cluster axis (core/hfsl.py)
    "seq": None,
    "attn_seq": None,                 # seq dim *inside* mixers/MLPs: always
                                      # replicated so SP reshards at entry
    "kv_seq": "model",                # KV caches shard their seq dim (heads
                                      # rarely divide 16); long_500k decode
                                      # overrides to ('pod','data')
    "kv_blocks": ("pod", "data"),     # paged KV block pool: blocks over the
                                      # batch axes (any row's table may name
                                      # any block, so the pool cannot follow
                                      # `batch`; block count scales with
                                      # aggregate wave size like batch does)
    "heads": "model",
    "kv_heads": "model",
    "head_dim": None,
    "d_model": None,
    "act_ff": "model",
    "act_experts": "model",
    # weights
    "fsdp": ("pod", "data"),          # second weight dim, ZeRO-3 style
    "moe_fsdp": ("pod", "data"),      # expert-weight d_model dim
    "d_ff": "model",
    "experts": "model",
    "vocab": "model",
    "d_inner": "model",
    "state": None,
    "conv": None,
    "lru": "model",
    "lora_rank": None,
    "prefix": None,
    "stage": "model",                 # SL pipeline stage axis (tests use a tiny mesh)
    "frames": None,
    "slots": ("pod", "data"),         # AdapterBank tenant-slot axis
}


def long_decode_rules() -> dict[str, Any]:
    """batch=1 decode: shard the KV-cache sequence dim instead of batch."""
    r = dict(DEFAULT_RULES)
    r["batch"] = None
    r["cluster"] = None
    r["kv_seq"] = ("pod", "data")
    return r


def moe_serving_rules() -> dict[str, Any]:
    """Inference-mode MoE sharding (EXPERIMENTS.md §Perf, kimi hillclimb).

    Training FSDP-shards expert weights over (pod, data) — correct when the
    all-gather amortizes over a big fwd+bwd, catastrophic for inference
    (every prefill re-gathers ~2 TB of experts). Serving flips to static
    expert parallelism: experts over `data` (384/16=24 per group), the
    expert d_model dim over `model`; tokens all-to-all to the expert shards
    (activation-sized traffic instead of weight-sized).
    """
    r = dict(DEFAULT_RULES)
    r["experts"] = "data"
    r["moe_fsdp"] = "model"
    r["act_experts"] = "data"
    return r


def serving_rules() -> dict[str, Any]:
    """Engine-wave serving rules (launch/engine.py mesh-native drains).

    The ragged continuous-batching wave shards its batch (slot) dim over
    (`pod`, `data`) and head/FF dims over `model`. Unlike DEFAULT_RULES the
    KV-cache seq dim stays replicated: the wave's per-row cache-slot
    scatter (`.at[rows, slot].set`) and the in-wave refill row-scatter
    address single positions along seq — sharding it would turn every
    decode-step write into a cross-device update. AdapterBank slot dims
    ride `data` (slot-parallel multi-tenant serving).
    """
    r = dict(DEFAULT_RULES)
    r["kv_seq"] = None
    return r


def drafter_rules() -> dict[str, Any]:
    """Speculative-decoding drafter rules: weights fully REPLICATED.

    The drafter is tiny — sharding its weights over `model` would trade a
    collective per draft step for negligible memory, and every device
    needs the whole drafter to propose for its local batch shard anyway.
    Activation batch dims keep the wave sharding over (`pod`, `data`)
    (the target's verify pass rides serving_rules unchanged); every other
    logical axis resolves to replicated.
    """
    keep = {"batch", "cluster", "slots"}
    return {k: (DEFAULT_RULES[k] if k in keep else None)
            for k in DEFAULT_RULES}


def train_rules(family: str) -> dict[str, Any]:
    """Per-family training rules (DESIGN.md §4 / EXPERIMENTS.md §Dry-run).

    - attention families: Megatron-style sequence parallelism — the residual
      stream shards its seq dim over `model`, bounding the remat carry
      (seq/16 per chip) at the cost of gather/scatter at layer boundaries.
    - recurrent families (ssm / hybrid): the time scan cannot shard seq, so
      the *per-cluster batch* shards over `model` instead.
    The inner `batch` rule is None in both cases when training under HFSL —
    the leading `cluster` dim carries the (pod, data) sharding.
    """
    r = dict(DEFAULT_RULES)
    r["batch"] = None
    if family in ("ssm", "hybrid"):
        r["batch"] = "model"
    else:
        r["seq"] = "model"
    return r


def hfsl_round_rules(family: str) -> dict[str, Any]:
    """Rules for the EXECUTED fused HFSL round (hfsl.make_hfsl_round).

    Same as :func:`train_rules` minus sequence parallelism: the SP
    gather/scatter inside the cluster-vmapped value_and_grad miscomputes
    VALUES (not just layout) under XLA:CPU SPMD on forced-host-device test
    meshes, and the round's parallelism story is the cluster dim on
    (`pod`, `data`) — pinned by the round's jit in/out shardings — with
    tensor parallelism over `model` inside each cluster. Re-enabling SP
    for real-TPU rounds is a ROADMAP follow-up; the dry-run still lowers
    the full train_rules SP path.
    """
    r = train_rules(family)
    r["seq"] = None
    return r


# ---------------------------------------------------------------------------
# Active context
# ---------------------------------------------------------------------------

_ctx = threading.local()


def _get() -> tuple[Optional[Mesh], Optional[dict]]:
    return getattr(_ctx, "mesh", None), getattr(_ctx, "rules", None)


def active_rules() -> tuple[Optional[Mesh], Optional[dict]]:
    """(mesh, rules) of the innermost :func:`use_rules` context
    ((None, None) outside)."""
    return _get()


@contextlib.contextmanager
def use_rules(mesh: Optional[Mesh], rules: Optional[dict] = None):
    """Activate (mesh, rules) for `shard()` constraints inside model code."""
    prev = _get()
    _ctx.mesh, _ctx.rules = mesh, (rules or DEFAULT_RULES)
    try:
        yield
    finally:
        _ctx.mesh, _ctx.rules = prev


def _resolve(axes: Sequence[Optional[str]], rules: dict, mesh: Mesh) -> P:
    """Logical axis names -> PartitionSpec.

    Mesh axes absent from the mesh are dropped; a mesh axis may appear only
    once per spec (earlier logical axes win — e.g. with sequence parallelism
    `seq` takes `model` and `heads` degrades to replicated)."""
    out = []
    used: set = set()
    for name in axes:
        tgt = rules.get(name) if name is not None else None
        if tgt is None:
            out.append(None)
            continue
        tgt_t = (tgt,) if isinstance(tgt, str) else tuple(tgt)
        tgt_t = tuple(a for a in tgt_t
                      if a in mesh.axis_names and a not in used)
        used.update(tgt_t)
        out.append(tgt_t if len(tgt_t) > 1 else (tgt_t[0] if tgt_t else None))
    while out and out[-1] is None:
        out.pop()
    return P(*out)


def spec_for(axes: Sequence[Optional[str]], mesh: Mesh,
             rules: Optional[dict] = None) -> P:
    return _resolve(axes, rules or DEFAULT_RULES, mesh)


def shard(x: jax.Array, *axes: Optional[str]) -> jax.Array:
    """Apply a with_sharding_constraint by logical names (no-op w/o context)."""
    mesh, rules = _get()
    if mesh is None:
        return x
    return jax.lax.with_sharding_constraint(
        x, NamedSharding(mesh, _resolve(axes, rules, mesh)))


# ---------------------------------------------------------------------------
# ParamSpec
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class ParamSpec:
    """Declaration of one parameter: shape + dtype + logical layout + init."""
    shape: tuple[int, ...]
    dtype: Any = jnp.bfloat16
    axes: tuple[Optional[str], ...] = ()
    init: str = "normal"              # normal | zeros | ones | scaled
    scale: float = 0.02

    def __post_init__(self):
        # a real error, not an assert: layout declarations are config-file
        # territory and must fail loudly even under `python -O`
        if len(self.axes) not in (0, len(self.shape)):
            raise ValueError(
                f"ParamSpec axes {self.axes} must be empty or name one "
                f"logical axis per dim of shape {self.shape}")


def _is_spec(x) -> bool:
    return isinstance(x, ParamSpec)


def _draw(key: jax.Array, s: ParamSpec) -> jax.Array:
    if s.init == "zeros":
        return jnp.zeros(s.shape, s.dtype)
    if s.init == "ones":
        return jnp.ones(s.shape, s.dtype)
    w = jax.random.normal(key, s.shape, jnp.float32)
    if s.init == "scaled":                # fan-in scaled normal
        fan_in = s.shape[-2] if len(s.shape) >= 2 else max(s.shape[-1], 1)
        return (w / np.sqrt(fan_in)).astype(s.dtype)
    return (w * s.scale).astype(s.dtype)


# tracelint: keys=specs,shardings
@functools.lru_cache(maxsize=64)
def _tree_drawer(specs: tuple, shardings: Optional[tuple]):
    """ONE jitted program that draws every leaf in its final dtype.

    Each leaf's f32 draw fuses into its cast, so the device holds only the
    final tree (an eager f32 draw of one stacked weight at published
    widths is several GB on top of the model), and one compile serves the
    whole model. With ``shardings`` every device draws just its shards."""

    def draw_tree(keys):
        return tuple(_draw(keys[i], s) for i, s in enumerate(specs))

    return jax.jit(draw_tree, out_shardings=shardings)


def init_from_spec(key: jax.Array, tree, shardings=None) -> Any:
    """Materialize a ParamSpec tree into initialized arrays.

    ``shardings`` (a matching tree of shardings, e.g. from
    :func:`named_shardings`) places each leaf where it is drawn, so no
    device ever holds the whole unplaced tree. Values do not depend on the
    placement (partitionable threefry)."""
    leaves, treedef = jax.tree.flatten(tree, is_leaf=_is_spec)
    keys = jax.random.split(key, len(leaves))
    places = None if shardings is None else tuple(jax.tree.leaves(shardings))
    return jax.tree.unflatten(treedef,
                              _tree_drawer(tuple(leaves), places)(keys))


def shape_structs(tree) -> Any:
    """ParamSpec tree -> ShapeDtypeStruct tree (dry-run: no allocation)."""
    return jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype), tree, is_leaf=_is_spec)


def fit_spec(p: P, shape: tuple[int, ...], mesh: Mesh) -> P:
    """Drop mesh axes whose product does not divide the dim size.

    jit in/out shardings (unlike with_sharding_constraint) require exact
    divisibility; e.g. 8 kv heads cannot shard over a 16-way `model` axis.
    Tuples degrade gracefully: ('pod','data') -> ('pod',) -> None.
    """
    out = []
    used: set = set()
    for i, entry in enumerate(p):
        if entry is None or i >= len(shape):
            out.append(None if i >= len(shape) else entry)
            continue
        axes = [entry] if isinstance(entry, str) else list(entry)
        axes = [a for a in axes if a not in used]   # an axis maps once

        def prod(a):
            n = 1
            for x in a:
                n *= mesh.shape[x]
            return n
        while axes and shape[i] % prod(axes) != 0:
            axes.pop()
        used.update(axes)
        out.append(tuple(axes) if len(axes) > 1 else (axes[0] if axes else None))
    while out and out[-1] is None:
        out.pop()
    return P(*out)


def partition_specs(tree, mesh: Mesh, rules: Optional[dict] = None) -> Any:
    """ParamSpec tree -> PartitionSpec tree for jit in/out shardings
    (shape-aware: non-dividing axes are dropped per fit_spec)."""
    r = rules or DEFAULT_RULES
    return jax.tree.map(
        lambda s: fit_spec(_resolve(s.axes, r, mesh), s.shape, mesh),
        tree, is_leaf=_is_spec)


def named_shardings(tree, mesh: Mesh, rules: Optional[dict] = None) -> Any:
    return jax.tree.map(lambda p: NamedSharding(mesh, p),
                        partition_specs(tree, mesh, rules),
                        is_leaf=lambda x: isinstance(x, P))


def dim_sharding(mesh: Mesh, size: int, logical: str, *, index: int = 0,
                 rules: Optional[dict] = None) -> NamedSharding:
    """NamedSharding placing ONE dim (at ``index``) on its logical axis.

    The workhorse for arrays that are not ParamSpec-declared (BatchBank
    rows, AdapterBank slot stacks): dim ``index`` of size ``size`` goes to
    the mesh axes ``rules[logical]`` resolves to, every other dim stays
    replicated. Non-dividing mesh axes are dropped per :func:`fit_spec`
    (device_put / jit shardings require exact divisibility), so e.g. 3
    tenant slots on a 2-way `data` axis degrade gracefully to replicated.
    """
    p = _resolve((None,) * index + (logical,), rules or DEFAULT_RULES, mesh)
    p = fit_spec(p, (1,) * index + (int(size),), mesh)
    return NamedSharding(mesh, p)


def param_bytes(tree) -> int:
    leaves = jax.tree.leaves(tree, is_leaf=_is_spec)
    return sum(int(np.prod(s.shape)) * jnp.dtype(s.dtype).itemsize for s in leaves)
