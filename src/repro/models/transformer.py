"""Decoder-stack assembly for all assigned families.

A model is a list of *scan groups*. Each group is a repeating pattern of
sub-layers (``kinds``) whose parameters are stacked along a leading dim and
executed with ``jax.lax.scan`` — this keeps the HLO one-pattern-sized, which
is what makes 512-way GSPMD compiles of 61..64-layer models tractable
(DESIGN.md §7). Dense/MoE/SSM models are a single group; recurrentgemma is
a scanned (rglru, rglru, attn) group plus an unrolled tail group.

Sub-layer kinds: ``attn`` | ``moe`` (attention + MoE FFN) | ``ssm`` |
``rglru`` (recurrent + gated-MLP sandwich, Griffin-style).

PEFT adapters mirror the group structure and are scanned alongside the
parameters; see core/peft.py for the trainable-subtree mechanics.
"""
from __future__ import annotations

import functools
from typing import Any, Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import attention as attn_mod
from repro.models import rglru as rglru_mod
from repro.models import ssm as ssm_mod
from repro.models.layers import mlp, mlp_spec, rmsnorm, rmsnorm_spec
from repro.models.moe import moe_apply, moe_spec
from repro.sharding.rules import ParamSpec, shard


# ---------------------------------------------------------------------------
# Group layout per config
# ---------------------------------------------------------------------------

def groups_for(cfg: ModelConfig) -> list[tuple[str, tuple[str, ...], int]]:
    """[(group_name, kinds, n_repeat)] — static model structure."""
    if cfg.family == "ssm":
        return [("g0", ("ssm",), cfg.n_layers)]
    if cfg.family == "moe":
        return [("g0", ("moe",), cfg.n_layers)]
    if cfg.family == "hybrid":
        pat = tuple(cfg.hybrid.pattern)
        tail = tuple(cfg.hybrid.tail)
        n = (cfg.n_layers - len(tail)) // len(pat)
        out = [("g0", pat, n)]
        if tail:
            out.append(("tail", tail, 1))
        return out
    # dense / vlm / (audio decoder handled in encdec.py)
    return [("g0", ("attn",), cfg.n_layers)]


def attn_window(cfg: ModelConfig, kind: str) -> int:
    if cfg.family == "hybrid":
        return cfg.hybrid.window
    if cfg.attn_variant == "sliding":
        return cfg.sliding_window
    return 0


# ---------------------------------------------------------------------------
# Per-sublayer specs
# ---------------------------------------------------------------------------

def sublayer_spec(cfg: ModelConfig, kind: str) -> dict:
    d = cfg.d_model
    if kind == "ssm":
        return {"ln1": rmsnorm_spec(d), "mix": ssm_mod.ssm_spec(cfg)}
    if kind == "rglru":
        return {"ln1": rmsnorm_spec(d), "mix": rglru_mod.rglru_spec(cfg),
                "ln2": rmsnorm_spec(d), "mlp": mlp_spec(d, cfg.d_ff, jnp.dtype(cfg.dtype))}
    if kind == "moe":
        return {"ln1": rmsnorm_spec(d), "attn": attn_mod.attn_spec(cfg),
                "ln2": rmsnorm_spec(d), "moe": moe_spec(cfg)}
    if kind != "attn":
        raise ValueError(f"unknown sublayer kind {kind!r}: expected "
                         "'attn', 'ssm', 'rglru', or 'moe'")
    return {"ln1": rmsnorm_spec(d), "attn": attn_mod.attn_spec(cfg),
            "ln2": rmsnorm_spec(d), "mlp": mlp_spec(d, cfg.d_ff, jnp.dtype(cfg.dtype))}


def sublayer_adapter_spec(cfg: ModelConfig, kind: str) -> dict:
    """PEFT adapter spec for one sub-layer (DESIGN.md §5)."""
    p = cfg.peft
    d, nh, nkv, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim_
    out: dict = {}
    if kind in ("attn", "moe"):
        if p.n_prefix > 0:
            out["prefix"] = {
                "k": ParamSpec((p.n_prefix, nkv, hd), jnp.dtype(cfg.dtype),
                               ("prefix", "kv_heads", "head_dim")),
                "v": ParamSpec((p.n_prefix, nkv, hd), jnp.dtype(cfg.dtype),
                               ("prefix", "kv_heads", "head_dim")),
            }
        if p.lora_rank > 0:
            lora = {}
            dims = {"q": nh * hd, "k": nkv * hd, "v": nkv * hd, "o": nh * hd}
            for t in p.lora_targets:
                n_out = dims[t] if t != "o" else d
                n_in = d if t != "o" else nh * hd
                lora[t] = {
                    "a": ParamSpec((n_in, p.lora_rank), jnp.dtype(cfg.dtype),
                                   ("fsdp", "lora_rank"), init="scaled"),
                    "b": ParamSpec((p.lora_rank, n_out), jnp.dtype(cfg.dtype),
                                   ("lora_rank", None), init="zeros"),
                }
            out["lora"] = lora
    elif kind == "ssm" and p.state_prompt:
        out["state0"] = ParamSpec((cfg.d_inner, cfg.ssm.d_state), jnp.float32,
                                  ("d_inner", "state"), init="zeros")
    elif kind == "rglru" and p.state_prompt:
        out["state0"] = ParamSpec((cfg.lru_width,), jnp.float32, ("lru",),
                                  init="zeros")
    return out


def _stack(tree, n: int):
    """Add a leading stacking dim of size n to every ParamSpec."""
    def f(s: ParamSpec) -> ParamSpec:
        axes = (None, *s.axes) if s.axes else (None,) * (len(s.shape) + 1)
        return ParamSpec((n, *s.shape), s.dtype, axes, init=s.init, scale=s.scale)
    return jax.tree.map(f, tree, is_leaf=lambda x: isinstance(x, ParamSpec))


def stack_spec(cfg: ModelConfig) -> dict:
    """Backbone layer-stack spec: {group: {sub_i: stacked spec}}."""
    out = {}
    for name, kinds, n in groups_for(cfg):
        grp = {f"s{i}": sublayer_spec(cfg, k) for i, k in enumerate(kinds)}
        out[name] = _stack(grp, n)
    return out


def adapter_stack_spec(cfg: ModelConfig) -> dict:
    out = {}
    for name, kinds, n in groups_for(cfg):
        grp = {f"s{i}": sublayer_adapter_spec(cfg, k) for i, k in enumerate(kinds)}
        out[name] = _stack(grp, n)
    return out


def cache_group_spec(cfg: ModelConfig, batch: int, seq_len: int, *,
                     paged=None) -> dict:
    """Decode-cache spec mirroring the group structure.

    ``paged=(n_blocks, block_size)`` switches the ELIGIBLE sub-layers
    (full-window attention/moe — see :func:`paged_subs`) to the paged
    block-pool layout; sliding-window and recurrent sub-layers keep
    their dense per-row layout either way."""
    out = {}
    for name, kinds, n in groups_for(cfg):
        grp = {}
        for i, k in enumerate(kinds):
            if k in ("attn", "moe"):
                w = attn_window(cfg, k)
                grp[f"s{i}"] = attn_mod.cache_spec(cfg, batch, seq_len,
                                                   window=w, layers=n,
                                                   paged=paged)
            elif k == "ssm":
                grp[f"s{i}"] = ssm_mod.ssm_cache_spec(cfg, batch, layers=n)
            elif k == "rglru":
                grp[f"s{i}"] = rglru_mod.rglru_cache_spec(cfg, batch, layers=n)
        out[name] = grp
    return out


def paged_subs(cfg: ModelConfig) -> list[tuple[str, str]]:
    """[(group, sub_key)] of sub-layers eligible for the paged KV layout:
    full-window (window == 0) attention/moe. Sliding-window layers keep
    their W-slot rolling buffer (already block-sized) and recurrent
    layers have O(1) state — a config with no eligible sub-layers still
    serves through the paged engine mode, it just allocates no blocks."""
    out = []
    for name, kinds, _ in groups_for(cfg):
        for i, k in enumerate(kinds):
            if k in ("attn", "moe") and not attn_window(cfg, k):
                out.append((name, f"s{i}"))
    return out


# ---------------------------------------------------------------------------
# Sub-layer application
# ---------------------------------------------------------------------------

def _select_state0(a: dict, adapter_ids):
    """Gather each row's state prompt from a stacked (n_slots, ...) bank."""
    if adapter_ids is None or not a or "state0" not in a:
        return a
    return {**a, "state0": jnp.take(a["state0"], adapter_ids, axis=0)}


def _apply_seq(kind: str, p: dict, a: dict, x, cfg: ModelConfig, *,
               positions, make_cache: bool, cache_len=None,
               adapter_ids=None, lengths=None):
    """Full-sequence sub-layer. Returns (x, cache, aux)."""
    aux = jnp.zeros((), jnp.float32)
    cache = None
    if kind == "ssm":
        h, cache = ssm_mod.ssm_seq(p["mix"], _select_state0(a, adapter_ids),
                                   rmsnorm(p["ln1"], x), cfg,
                                   make_cache=make_cache, lengths=lengths)
        return x + h, cache, aux
    if kind == "rglru":
        h, cache = rglru_mod.rglru_seq(p["mix"], _select_state0(a, adapter_ids),
                                       rmsnorm(p["ln1"], x), cfg,
                                       make_cache=make_cache, lengths=lengths)
        x = x + h
        x = x + mlp(p["mlp"], rmsnorm(p["ln2"], x))
        return x, cache, aux
    # attention-based
    w = attn_window(cfg, kind)
    h, cache = attn_mod.attention_seq(p["attn"], a, rmsnorm(p["ln1"], x), cfg,
                                      positions=positions, window=w,
                                      make_cache=make_cache,
                                      cache_len=cache_len,
                                      adapter_ids=adapter_ids,
                                      lengths=lengths)
    x = x + h
    if kind == "moe":
        h2, aux = moe_apply(p["moe"], rmsnorm(p["ln2"], x), cfg)
    else:
        h2 = mlp(p["mlp"], rmsnorm(p["ln2"], x))
    return x + h2, cache, aux


def _freeze_inactive(new_cache: dict, old_cache: dict, active):
    """Per-row cache select: retired rows keep their old (frozen) state."""
    if active is None:
        return new_cache
    return jax.tree.map(
        lambda n, o: jnp.where(active.reshape((-1,) + (1,) * (n.ndim - 1)),
                               n, o), new_cache, old_cache)


def _apply_decode(kind: str, p: dict, a: dict, x, cache, cfg: ModelConfig, *,
                  pos, adapter_ids=None, active=None, layer=None):
    if kind == "ssm":
        h, new = ssm_mod.ssm_decode(p["mix"], a, rmsnorm(p["ln1"], x), cache,
                                    cfg)
        return x + h, _freeze_inactive(new, cache, active)
    if kind == "rglru":
        h, new = rglru_mod.rglru_decode(p["mix"], a, rmsnorm(p["ln1"], x),
                                        cache, cfg)
        x = x + h
        return x + mlp(p["mlp"], rmsnorm(p["ln2"], x)), \
            _freeze_inactive(new, cache, active)
    w = attn_window(cfg, kind)
    h, cache = attn_mod.attention_decode(p["attn"], a, rmsnorm(p["ln1"], x),
                                         cache, cfg, pos=pos, window=w,
                                         adapter_ids=adapter_ids,
                                         active=active, layer=layer)
    x = x + h
    if kind == "moe":
        h2, _ = moe_apply(p["moe"], rmsnorm(p["ln2"], x), cfg)
    else:
        h2 = mlp(p["mlp"], rmsnorm(p["ln2"], x))
    return x + h2, cache


# ---------------------------------------------------------------------------
# Stack forward
# ---------------------------------------------------------------------------

def stack_seq(params: dict, adapters: dict, x: jax.Array, cfg: ModelConfig, *,
              positions: jax.Array, make_cache: bool = False,
              remat: bool = False, cache_len=None, adapter_ids=None,
              lengths=None):
    """Run all groups over a full sequence.

    With ``adapter_ids`` (multi-tenant serving) adapter leaves carry an
    ``n_slots`` dim after the scanned layer dim — ``(L, n_slots, ...)``,
    the AdapterBank serving layout — so every layer slice hands the whole
    slot stack to the batched multi-LoRA projections.

    ``lengths`` (B,) serves ragged right-padded rows: attention caches get
    per-row sentinel positions beyond each row's length, and the
    recurrent sub-layers (ssm/rglru) freeze their state identity-exactly
    over padded columns — so the caches a ragged prefill builds are
    bitwise the caches each row would build alone.

    Returns (x, caches | None, aux_sum)."""
    caches: dict = {}
    aux_total = jnp.zeros((), jnp.float32)

    for name, kinds, n in groups_for(cfg):
        gp, ga = params[name], adapters.get(name, {})

        @jax.named_scope("layer")
        def body(carry, layer):
            x, aux = carry
            lp, la = layer
            lcaches = {}
            for i, k in enumerate(kinds):
                x, c, a_ = _apply_seq(k, lp[f"s{i}"], la.get(f"s{i}", {}), x,
                                      cfg, positions=positions,
                                      make_cache=make_cache,
                                      cache_len=cache_len,
                                      adapter_ids=adapter_ids,
                                      lengths=lengths)
                aux = aux + a_
                if c is not None:
                    lcaches[f"s{i}"] = c
            return (x, aux), lcaches

        if remat:
            body = jax.checkpoint(body, prevent_cse=False)
        with jax.named_scope("layers"):
            (x, aux_total), gcache = jax.lax.scan(
                body, (x, aux_total), (gp, ga if ga else _empty_like(gp, n)))
        caches[name] = gcache
    return x, (caches if make_cache else None), aux_total


def stack_decode(params: dict, adapters: dict, x: jax.Array,
                 caches: dict, cfg: ModelConfig, *, pos: jax.Array,
                 adapter_ids=None, active=None):
    """Single-token step through all groups. Returns (x, new_caches).

    ``pos`` may be per-row (B,) (ragged serving); ``active`` (B,) bool
    freezes retired rows' caches while the rest of the wave decodes.

    Dense attention caches (those with a ``pos`` plane) are carried whole
    through the layer scan and written in place at ``[layer, row, slot]``,
    so no step slices out or writes back a layer's cache; recurrent state
    and paged pools ride the scan as per-layer slices."""
    new_caches: dict = {}
    for name, kinds, n in groups_for(cfg):
        gp, ga = params[name], adapters.get(name, {})
        gc = caches[name]
        resident = {key: c for key, c in gc.items() if "pos" in c}
        sliced = {key: c for key, c in gc.items() if "pos" not in c}

        @jax.named_scope("layer")
        def body(carry, layer):
            x, res = carry
            res = dict(res)
            lp, la, lc, idx = layer
            new_lc = {}
            for i, k in enumerate(kinds):
                key = f"s{i}"
                if key in res:
                    x, res[key] = _apply_decode(
                        k, lp[key], la.get(key, {}), x, res[key], cfg,
                        pos=pos, adapter_ids=adapter_ids, active=active,
                        layer=idx)
                else:
                    x, new_lc[key] = _apply_decode(
                        k, lp[key], la.get(key, {}), x, lc[key], cfg,
                        pos=pos, adapter_ids=adapter_ids, active=active)
            return (x, res), new_lc

        with jax.named_scope("layers"):
            (x, resident), new_sliced = jax.lax.scan(
                body, (x, resident),
                (gp, ga if ga else _empty_like(gp, n), sliced,
                 jnp.arange(n, dtype=jnp.int32)))
        new_caches[name] = {**new_sliced, **resident}
    return x, new_caches


def rec_cache_part(caches: dict) -> dict:
    """The recurrent ({'h','conv'}) sub-trees of a decode-cache tree — the
    part speculative decoding snapshots per step for rollback (attention
    caches, which carry a 'pos' or 'table' leaf, roll back by slot
    restore instead)."""
    return {g: {s: c for s, c in grp.items()
                if "pos" not in c and "table" not in c}
            for g, grp in caches.items()}


def stack_chunk(params: dict, adapters: dict, x: jax.Array, caches: dict,
                cfg: ModelConfig, *, start: jax.Array, valid: jax.Array,
                adapter_ids=None):
    """Length-W suffix chunk through a FULLY PAGED stack (prefix sharing).

    A prefix-cache hit row re-prefills only its private suffix: x is the
    embedded (B, W, d) suffix, row b at absolute positions
    ``start[b]..start[b]+W-1`` with ``valid`` (B, W) masking real tokens.
    Every sub-layer must be a full-window attention/moe layer holding a
    paged cache (prefix sharing is gated to such configs at the engine).
    Returns (x, new_caches)."""
    new_caches: dict = {}
    for name, kinds, n in groups_for(cfg):
        gp, ga = params[name], adapters.get(name, {})
        gc = caches[name]

        @jax.named_scope("layer")
        def body(x, layer):
            lp, la, lc = layer
            new_lc = {}
            for i, k in enumerate(kinds):
                key = f"s{i}"
                if k not in ("attn", "moe") or "table" not in lc[key]:
                    raise NotImplementedError(
                        "stack_chunk requires a fully paged attention stack")
                p_, a_ = lp[key], la.get(key, {})
                h, c = attn_mod.attention_chunk_paged(
                    p_["attn"], a_, rmsnorm(p_["ln1"], x), lc[key], cfg,
                    start=start, valid=valid, adapter_ids=adapter_ids)
                x = x + h
                if k == "moe":
                    h2, _ = moe_apply(p_["moe"], rmsnorm(p_["ln2"], x), cfg)
                else:
                    h2 = mlp(p_["mlp"], rmsnorm(p_["ln2"], x))
                x = x + h2
                new_lc[key] = c
            return x, new_lc

        with jax.named_scope("layers"):
            x, new_gc = jax.lax.scan(
                body, x, (gp, ga if ga else _empty_like(gp, n), gc))
        new_caches[name] = new_gc
    return x, new_caches


def stack_verify(params: dict, adapters: dict, x: jax.Array, caches: dict,
                 cfg: ModelConfig, *, pos: jax.Array, adapter_ids=None,
                 active=None):
    """Length-T chunk step through all groups (speculative verify).

    Like ``stack_decode`` but processes a whole draft chunk per row in one
    pass: attention sub-layers scatter the chunk's K/V then attend the
    updated cache (attention_verify — ONE cache read for T tokens, the
    speculative win); recurrent sub-layers chain T exact decode steps and
    emit per-step state snapshots. Returns (x, new_caches, rec_snaps):
    ``rec_snaps`` mirrors :func:`rec_cache_part` with a per-step axis at
    dim 2 ((L, B, T, ...)); ``new_caches`` assumes FULL acceptance —
    core/spec_decode.py::rollback_caches restores each row to its accepted
    length (and freezes inactive rows' recurrent state, which this pass
    advances unconditionally)."""
    new_caches: dict = {}
    snaps: dict = {}
    for name, kinds, n in groups_for(cfg):
        gp, ga = params[name], adapters.get(name, {})
        gc = caches[name]

        @jax.named_scope("layer")
        def body(x, layer):
            lp, la, lc = layer
            new_lc, snap_lc = {}, {}
            for i, k in enumerate(kinds):
                key = f"s{i}"
                p_, a_ = lp[key], la.get(key, {})
                if k == "ssm":
                    h, s = ssm_mod.ssm_verify(p_["mix"], a_,
                                              rmsnorm(p_["ln1"], x),
                                              lc[key], cfg)
                    x = x + h
                elif k == "rglru":
                    h, s = rglru_mod.rglru_verify(p_["mix"], a_,
                                                  rmsnorm(p_["ln1"], x),
                                                  lc[key], cfg)
                    x = x + h
                    x = x + mlp(p_["mlp"], rmsnorm(p_["ln2"], x))
                else:
                    w = attn_window(cfg, k)
                    h, c = attn_mod.attention_verify(
                        p_["attn"], a_, rmsnorm(p_["ln1"], x), lc[key], cfg,
                        pos=pos, window=w, adapter_ids=adapter_ids,
                        active=active)
                    x = x + h
                    if k == "moe":
                        h2, _ = moe_apply(p_["moe"], rmsnorm(p_["ln2"], x),
                                          cfg)
                    else:
                        h2 = mlp(p_["mlp"], rmsnorm(p_["ln2"], x))
                    x = x + h2
                    new_lc[key], snap_lc[key] = c, {}
                    continue
                new_lc[key] = jax.tree.map(lambda t: t[:, -1], s)
                snap_lc[key] = s
            return x, (new_lc, snap_lc)

        with jax.named_scope("layers"):
            x, (new_gc, snap_gc) = jax.lax.scan(
                body, x, (gp, ga if ga else _empty_like(gp, n), gc))
        new_caches[name] = new_gc
        snaps[name] = snap_gc
    return x, new_caches, snaps


def _empty_like(gp, n: int):
    """Zero-leaf pytree scannable alongside params when no adapters exist."""
    return {}
