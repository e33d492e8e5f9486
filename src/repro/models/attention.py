"""GQA attention with prefix-KV prompts, LoRA, sliding window, and KV caching.

The prefix-KV prompt module is the causal-LM analogue of the paper's
per-layer prompt modules (VPT-deep, §III-A/Fig 1): each layer owns ``n_p``
learned key/value slots, visible to every query, carrying no positional
encoding (position < 0 in the shared masking semantics).

Modes:
- train/prefill: full-sequence blocked flash attention (kernels/ops.py);
  prefill additionally returns the layer KV cache (rolling window buffer for
  the sliding variant).
- decode: single-token flash-decode attention against the cache
  (kernels/ops.py::flash_decode — split-KV Pallas kernel on TPU, blocked
  XLA online-softmax elsewhere); the cache is updated in place at ``pos``
  (or slot ``pos % window`` for sliding).

The dense cache is resident in one head-major layout: K and V are
``(B, Hkv, T, D)`` per layer, ``(L, B, Hkv, T, D)`` stacked, so a KV
head's ``(chunk, D)`` tile is a block of the stored array. The prefill
writer, the per-token writer and the kernel all use it as it lies; T is
allocated by ``ops.decode_slots`` so the kernel never pads it.
"""
from __future__ import annotations

from typing import Any, Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.kernels import ops as kops
from repro.models.layers import rope
from repro.sharding.rules import ParamSpec, shard


# ---------------------------------------------------------------------------
# Params
# ---------------------------------------------------------------------------

def attn_spec(cfg: ModelConfig) -> dict:
    d, hd = cfg.d_model, cfg.head_dim_
    nh, nkv = cfg.n_heads, cfg.n_kv_heads
    dt = jnp.dtype(cfg.dtype)
    s = {
        "wq": ParamSpec((d, nh * hd), dt, ("fsdp", "heads"), init="scaled"),
        "wk": ParamSpec((d, nkv * hd), dt, ("fsdp", "kv_heads"), init="scaled"),
        "wv": ParamSpec((d, nkv * hd), dt, ("fsdp", "kv_heads"), init="scaled"),
        "wo": ParamSpec((nh * hd, d), dt, ("heads", "fsdp"), init="scaled"),
    }
    if cfg.qkv_bias:
        s["bq"] = ParamSpec((nh * hd,), dt, ("heads",), init="zeros")
        s["bk"] = ParamSpec((nkv * hd,), dt, ("kv_heads",), init="zeros")
        s["bv"] = ParamSpec((nkv * hd,), dt, ("kv_heads",), init="zeros")
    return s


def _proj(x, w, bias, lora, scale, adapter_ids=None):
    """Projection with optional LoRA branch (kernel-dispatched).

    Both training and inference traverse ops.lora_matmul: its custom VJP
    keeps the fused kernel usable under ``jax.grad`` (adapter grads only —
    the frozen ``dW`` is never formed), so the HFSL fine-tuning round and
    the decode path share one projection fast path.

    Multi-tenant serving passes ``adapter_ids`` (one slot id per batch row)
    with ``lora`` leaves carrying a leading ``n_slots`` dim (the
    AdapterBank layout); the projection then dispatches to the batched
    multi-LoRA kernel so one wave mixes adapters from different domains.
    """
    if lora is not None:
        shp = x.shape
        with jax.named_scope("lora"):
            if adapter_ids is not None:
                return kops.lora_bgmv(x, w, lora["a"], lora["b"],
                                      adapter_ids, scale, bias)
            y = kops.lora_matmul(x.reshape(-1, shp[-1]), w, lora["a"],
                                 lora["b"], scale, bias)
            return y.reshape(*shp[:-1], w.shape[-1])
    return kops.lora_matmul(x, w, bias=bias)


def _qkv(params, adapters, x, cfg: ModelConfig, kv_x=None, adapter_ids=None):
    """Compute q, k, v with LoRA; reshape to (B, S, H, D)."""
    nh, nkv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim_
    lora = (adapters or {}).get("lora", {})
    lscale = cfg.peft.lora_alpha / max(cfg.peft.lora_rank, 1)
    kv_in = x if kv_x is None else kv_x
    q = _proj(x, params["wq"], params.get("bq"), lora.get("q"), lscale,
              adapter_ids)
    k = _proj(kv_in, params["wk"], params.get("bk"), lora.get("k"), lscale,
              adapter_ids)
    v = _proj(kv_in, params["wv"], params.get("bv"), lora.get("v"), lscale,
              adapter_ids)
    B, S = x.shape[:2]
    Skv = kv_in.shape[1]
    return (q.reshape(B, S, nh, hd), k.reshape(B, Skv, nkv, hd),
            v.reshape(B, Skv, nkv, hd))


@jax.named_scope("kv_cache")
def _with_prefix(k, v, adapters, B, adapter_ids=None, axis: int = 1):
    """Prepend per-layer prefix-KV slots (broadcast over batch; with
    ``adapter_ids`` each row gathers its own domain's slots from the
    stacked (n_slots, n_p, Hkv, D) bank) along the slot ``axis``: 1 for
    (B, S, Hkv, D), 2 for the head-major cache (B, Hkv, T, D)."""
    pfx = (adapters or {}).get("prefix")
    if pfx is None:
        return k, v, 0
    if adapter_ids is not None:
        pk = jnp.take(pfx["k"], adapter_ids, axis=0).astype(k.dtype)
        pv = jnp.take(pfx["v"], adapter_ids, axis=0).astype(v.dtype)
    else:
        pk = jnp.broadcast_to(pfx["k"][None],
                              (B, *pfx["k"].shape)).astype(k.dtype)
        pv = jnp.broadcast_to(pfx["v"][None],
                              (B, *pfx["v"].shape)).astype(v.dtype)
    n_p = pk.shape[1]
    if axis == 2:
        pk, pv = jnp.swapaxes(pk, 1, 2), jnp.swapaxes(pv, 1, 2)
    return (jnp.concatenate([pk, k], axis), jnp.concatenate([pv, v], axis),
            n_p)


# ---------------------------------------------------------------------------
# Full-sequence (train / prefill)
# ---------------------------------------------------------------------------

@jax.named_scope("attn")
def attention_seq(params: dict, adapters: Optional[dict], x: jax.Array,
                  cfg: ModelConfig, *, positions: jax.Array,
                  causal: bool = True, window: int = 0,
                  kv_x: Optional[jax.Array] = None,
                  kv_positions: Optional[jax.Array] = None,
                  use_rope: bool = True,
                  make_cache: bool = False,
                  cache_len: Optional[int] = None,
                  adapter_ids: Optional[jax.Array] = None,
                  lengths: Optional[jax.Array] = None):
    """Returns (out (B,S,d_model), cache or None).

    ``lengths`` (B,) marks ragged right-padded rows: row b's valid tokens
    occupy columns ``[0, lengths[b])``. Because padding sits on the RIGHT
    and masking is causal, valid rows never see padded columns, so the
    full-sequence output for valid tokens is exact without per-row q
    positions. Raggedness only matters for the cache: padded columns'
    K/V land in the buffer, so the per-row cache ``pos`` leaf (B, L)
    carries the ``+1e9`` sentinel beyond each row's length — decode-side
    length-aware masking then keeps them invisible forever.
    """
    B, S = x.shape[:2]
    q, k, v = _qkv(params, adapters, x, cfg, kv_x, adapter_ids)
    kv_positions = positions if kv_positions is None else kv_positions
    if kv_x is None and use_rope:                          # self-attention: RoPE
        q = rope(q, positions, cfg.rope_theta)
        k = rope(k, kv_positions, cfg.rope_theta)
    q = shard(q, "batch", "attn_seq", "heads", "head_dim")
    k = shard(k, "batch", "attn_seq", "kv_heads", "head_dim")
    v = shard(v, "batch", "attn_seq", "kv_heads", "head_dim")

    kp, vp, n_p = _with_prefix(k, v, adapters, B, adapter_ids)
    kv_pos = jnp.concatenate(
        [jnp.full((n_p,), -1, jnp.int32), kv_positions.astype(jnp.int32)]) \
        if n_p else kv_positions.astype(jnp.int32)

    out = kops.flash_attention(
        q, kp, vp, q_pos=positions.astype(jnp.int32), kv_pos=kv_pos,
        window=window, causal=causal)
    out = out.reshape(B, S, -1)
    y = _proj(out, params["wo"], None,
              (adapters or {}).get("lora", {}).get("o"),
              cfg.peft.lora_alpha / max(cfg.peft.lora_rank, 1), adapter_ids)
    y = shard(y, "batch", "seq", "d_model")

    cache = _prefill_cache(k, v, positions, S, cache_len, window,
                           lengths) if make_cache else None
    return y, cache


@jax.named_scope("kv_cache")
def _prefill_cache(k, v, positions, S: int, cache_len, window: int, lengths):
    """The layer's decode cache from its prompt K/V (B, S, Hkv, D), in the
    resident head-major layout (B, Hkv, T, D): a rolling buffer of
    ``window`` slots for the sliding variant, else ``cache_len`` slots,
    each rounded up by ``ops.decode_slots`` with sentinel slots."""
    B = k.shape[0]
    lens = jnp.full((B,), S, jnp.int32) if lengths is None \
        else lengths.astype(jnp.int32)
    if window and window > 0:                      # rolling buffer, W slots
        W = window
        # slot s holds the largest position p ≡ s (mod W) with
        # p <= len_b - 1 (the per-row rolling-buffer layout decode's
        # ``pos % W`` writes continue); p < 0 means the slot is empty.
        s_idx = jnp.arange(W, dtype=jnp.int32)
        p = s_idx[None, :] + W * ((lens[:, None] - 1 - s_idx[None, :])
                                  // W)                # (B, W)
        valid = p >= 0
        gidx = jnp.clip(p, 0, S - 1)[:, :, None, None]
        k = jnp.where(valid[:, :, None, None],
                      jnp.take_along_axis(k, gidx, axis=1),
                      jnp.zeros((), k.dtype))
        v = jnp.where(valid[:, :, None, None],
                      jnp.take_along_axis(v, gidx, axis=1),
                      jnp.zeros((), v.dtype))
        # +1e9 sentinel: empty slots must be *invisible* (negative would
        # mark them as always-visible prefix slots in the mask rules)
        cpos = jnp.where(valid, p, 10 ** 9)
        T = kops.decode_slots(W)
    else:
        T = kops.decode_slots(max(cache_len or S, S))
        cpos = jnp.where(jnp.arange(S)[None, :] < lens[:, None],
                         positions.astype(jnp.int32)[None, :], 10 ** 9)
    n = k.shape[1]
    slots = ((0, 0), (0, 0), (0, T - n), (0, 0))
    return {
        "k": jnp.pad(jnp.swapaxes(k, 1, 2), slots),
        "v": jnp.pad(jnp.swapaxes(v, 1, 2), slots),
        "pos": jnp.pad(cpos, ((0, 0), (0, T - n)), constant_values=10 ** 9),
    }


# ---------------------------------------------------------------------------
# Decode (single token against cache)
# ---------------------------------------------------------------------------

@jax.named_scope("attn")
def attention_decode(params: dict, adapters: Optional[dict], x: jax.Array,
                     cache: dict, cfg: ModelConfig, *, pos: jax.Array,
                     window: int = 0, cross: bool = False,
                     use_rope: bool = True,
                     adapter_ids: Optional[jax.Array] = None,
                     active: Optional[jax.Array] = None,
                     layer: Optional[jax.Array] = None):
    """x: (B, 1, d). cache: {'k','v','pos'} (+ static for cross). Returns
    (out, new_cache). ``adapter_ids`` selects each row's adapter from
    stacked (n_slots, ...) adapter leaves (multi-tenant serving).

    A dense cache is the layer-stacked resident buffer — K/V
    (L, B, Hkv, T, D), ``pos`` (L, B, T) — written and read in place at
    the scalar index ``layer``: the only cache bytes the step writes are
    each live row's new token, and ``new_cache`` is the whole stack.

    ``pos`` is a scalar or per-row (B,) position: each row writes its own
    cache slot ``pos[b]`` (``pos[b] % window`` for sliding), so one wave
    mixes rows at different sequence positions (ragged continuous
    batching). ``active`` (B,) bool retires rows in place: an inactive
    row's cache write is routed out of bounds and dropped, freezing its
    cache while the wave keeps decoding other rows.

    A PAGED cache (``{'k','v'}`` block pools (n_blocks, bs, Hkv, D) +
    ``'table'`` (B, max_blocks)) is detected by its ``table`` leaf: the
    slot scatter becomes a block-table-indirected write
    ``pos -> (table[b, pos // bs], pos % bs)`` and attention dispatches
    to :func:`ops.flash_decode_paged`. Distinct live rows always write
    distinct blocks (the allocator never shares a row's TAIL block), so
    the batched scatter stays race-free; inactive and pad rows route to
    the ``n_blocks`` sentinel and are dropped."""
    B = x.shape[0]
    nh, nkv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim_
    lora = (adapters or {}).get("lora", {})
    lscale = cfg.peft.lora_alpha / max(cfg.peft.lora_rank, 1)
    pos = jnp.broadcast_to(jnp.asarray(pos, jnp.int32), (B,))

    q = _proj(x, params["wq"], params.get("bq"), lora.get("q"), lscale,
              adapter_ids)
    q = q.reshape(B, 1, nh, hd)

    if cross:
        k, v = cache["k"], cache["v"]
        kv_pos = cache["pos"]
        new_cache = cache
    else:
        if use_rope:
            q = rope(q, pos[:, None], cfg.rope_theta)
        k1 = _proj(x, params["wk"], params.get("bk"), lora.get("k"), lscale,
                   adapter_ids)
        v1 = _proj(x, params["wv"], params.get("bv"), lora.get("v"), lscale,
                   adapter_ids)
        k1 = k1.reshape(B, 1, nkv, hd)
        if use_rope:
            k1 = rope(k1, pos[:, None], cfg.rope_theta)
        v1 = v1.reshape(B, 1, nkv, hd)
        with jax.named_scope("kv_cache"):
            if "table" in cache:         # paged: block-table indirected write
                table = cache["table"]
                nb, bs = cache["k"].shape[0], cache["k"].shape[1]
                blk = jnp.take_along_axis(table, (pos // bs)[:, None],
                                          axis=1)[:, 0]
                if active is not None:   # retired rows: write out of bounds
                    blk = jnp.where(active, blk, nb)
                off = pos % bs
                k = cache["k"].at[blk, off].set(
                    k1[:, 0].astype(cache["k"].dtype), mode="drop")
                v = cache["v"].at[blk, off].set(
                    v1[:, 0].astype(cache["v"].dtype), mode="drop")
                new_cache = {"k": k, "v": v, "table": table}
                kv_pos = None            # implicit: slot index == position
            else:                        # the new token at [layer, row, slot]
                T = cache["k"].shape[3]
                slot = (pos % window) if window and window > 0 else pos
                if active is not None:   # retired rows: write out of bounds
                    slot = jnp.where(active, slot, T)
                rows = jnp.arange(B)
                # one D-vector per (row, head): a window along the minor
                # dim alone, which the head-major layout stores as is
                at = (layer, rows[:, None], jnp.arange(nkv)[None, :],
                      slot[:, None])
                k = cache["k"].at[at].set(
                    k1[:, 0].astype(cache["k"].dtype), mode="drop")
                v = cache["v"].at[at].set(
                    v1[:, 0].astype(cache["v"].dtype), mode="drop")
                kv_pos = cache["pos"].at[layer, rows, slot].set(
                    pos, mode="drop")
                new_cache = {"k": k, "v": v, "pos": kv_pos}

    if "table" not in cache:
        k = shard(k, None, "batch", "kv_heads", "kv_seq", "head_dim")
        v = shard(v, None, "batch", "kv_heads", "kv_seq", "head_dim")
    else:
        k = shard(k, "kv_blocks", None, "kv_heads", "head_dim")
        v = shard(v, "kv_blocks", None, "kv_heads", "head_dim")

    # Single-token attention is kernel-dispatched; both paths take the
    # prefix bank as its own operand and merge it into the online softmax
    # (§Perf d2 — concatenating prefix slots onto the cache would copy it
    # every layer), the Pallas path being the split-KV flash-decode kernel
    # (kernels/flash_decode.py) with length-aware sentinel masking.
    pfx = (adapters or {}).get("prefix") if not cross else None
    pfx_k = pfx_v = None
    if pfx is not None:
        if adapter_ids is not None:                # per-row domain prefix
            with jax.named_scope("kv_cache"):
                pfx_k = jnp.take(pfx["k"], adapter_ids, axis=0)
                pfx_v = jnp.take(pfx["v"], adapter_ids, axis=0)
        else:
            pfx_k, pfx_v = pfx["k"], pfx["v"]
    if "table" in cache:
        o = kops.flash_decode_paged(
            q[:, 0], k, v, cache["table"], q_pos=pos.astype(jnp.int32),
            prefix_k=pfx_k, prefix_v=pfx_v)
    else:
        o = kops.flash_decode(
            q[:, 0], k, v, layer=layer, q_pos=pos.astype(jnp.int32),
            kv_pos=kv_pos.astype(jnp.int32),
            prefix_k=pfx_k, prefix_v=pfx_v,
            window=0 if cross else window, causal=not cross)
    o = o.reshape(B, 1, nh * hd).astype(x.dtype)
    y = _proj(o, params["wo"], None, lora.get("o"), lscale, adapter_ids)
    return y, new_cache


def chunk_slots(qpos: jax.Array, window: int, S: int,
                active: Optional[jax.Array] = None) -> jax.Array:
    """Per-row cache slots a verify chunk writes (and rollback restores).

    qpos: (B, T) absolute positions. Sliding-window caches write slot
    ``pos % window``, full caches slot ``pos``; inactive rows are routed
    out of bounds (``S``) so their scatters are dropped."""
    slot = (qpos % window) if window and window > 0 else qpos
    if active is not None:
        slot = jnp.where(active[:, None], slot, S)
    return slot


@jax.named_scope("attn")
def attention_verify(params: dict, adapters: Optional[dict], x: jax.Array,
                     cache: dict, cfg: ModelConfig, *, pos: jax.Array,
                     window: int = 0, use_rope: bool = True,
                     adapter_ids: Optional[jax.Array] = None,
                     active: Optional[jax.Array] = None):
    """Speculative verify: a length-T token chunk per row against the LIVE
    cache. x: (B, T, d) — row b's chunk occupies positions
    ``pos[b] .. pos[b]+T-1``. Returns (out (B, T, d), new_cache).

    The chunk's K/V are scattered into the cache first (per-row slots,
    exactly the footprint of T consecutive ``attention_decode`` writes),
    then every chunk query attends the updated cache under the shared
    masking semantics (kernels/ref.py): prefix slots (pos < 0) always
    visible, empty slots (+1e9 sentinel) never, sliding window per query
    position. T is tiny (draft_k + 1), so the attention itself is plain
    jnp GQA — ``flash_decode`` takes one query per row and
    ``flash_attention``'s q_pos is per-block, not per-row; a real-TPU
    verify kernel is a recorded ROADMAP follow-up.

    Rejected draft positions leave K/V writes behind: callers must restore
    the overwritten slots (core/spec_decode.py::rollback_caches) before
    the next chunk. Inactive rows' writes are dropped out of bounds, so
    retired rows' caches stay frozen through a speculative wave."""
    B, T = x.shape[:2]
    nh, nkv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim_
    lora = (adapters or {}).get("lora", {})
    lscale = cfg.peft.lora_alpha / max(cfg.peft.lora_rank, 1)
    pos = jnp.broadcast_to(jnp.asarray(pos, jnp.int32), (B,))
    qpos = pos[:, None] + jnp.arange(T, dtype=jnp.int32)[None, :]   # (B, T)

    q = _proj(x, params["wq"], params.get("bq"), lora.get("q"), lscale,
              adapter_ids).reshape(B, T, nh, hd)
    k1 = _proj(x, params["wk"], params.get("bk"), lora.get("k"), lscale,
               adapter_ids).reshape(B, T, nkv, hd)
    v1 = _proj(x, params["wv"], params.get("bv"), lora.get("v"), lscale,
               adapter_ids).reshape(B, T, nkv, hd)
    if use_rope:
        q = rope(q, qpos, cfg.rope_theta)
        k1 = rope(k1, qpos, cfg.rope_theta)

    S = cache["k"].shape[2]
    slot = chunk_slots(qpos, window, S, active)
    rows = jnp.arange(B)[:, None]
    with jax.named_scope("kv_cache"):
        k = cache["k"].at[rows, :, slot].set(k1.astype(cache["k"].dtype),
                                             mode="drop")
        v = cache["v"].at[rows, :, slot].set(v1.astype(cache["v"].dtype),
                                             mode="drop")
        kv_pos = cache["pos"].at[rows, slot].set(qpos, mode="drop")
    new_cache = {"k": k, "v": v, "pos": kv_pos}

    k = shard(k, "batch", "kv_heads", "kv_seq", "head_dim")
    v = shard(v, "batch", "kv_heads", "kv_seq", "head_dim")
    kp, vp, n_p = _with_prefix(k, v, adapters, B, adapter_ids, axis=2)
    if n_p:
        kv_pos = jnp.concatenate(
            [jnp.full((B, n_p), -1, jnp.int32), kv_pos], axis=1)

    vis = kv_pos[:, None, :] <= qpos[:, :, None]            # causal (B, T, S)
    if window and window > 0:
        vis &= (qpos[:, :, None] - kv_pos[:, None, :]) < window
    vis |= kv_pos[:, None, :] < 0                           # prefix slots
    g = nh // nkv
    qf = q.reshape(B, T, nkv, g, hd).astype(jnp.float32)
    scores = jnp.einsum("btngd,bnsd->bngts", qf,
                        kp.astype(jnp.float32)) * (hd ** -0.5)
    scores = jnp.where(vis[:, None, None], scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1)
    o = jnp.einsum("bngts,bnsd->btngd", probs, vp.astype(jnp.float32))
    o = o.reshape(B, T, nh * hd).astype(x.dtype)
    y = _proj(o, params["wo"], None, lora.get("o"), lscale, adapter_ids)
    return y, new_cache


@jax.named_scope("attn")
def attention_chunk_paged(params: dict, adapters: Optional[dict],
                          x: jax.Array, cache: dict, cfg: ModelConfig, *,
                          start: jax.Array, valid: jax.Array,
                          adapter_ids: Optional[jax.Array] = None):
    """Chunked continuation prefill against a PAGED cache (prefix sharing).

    A prefix-cache hit row skips re-prefilling its shared blocks: only
    the private SUFFIX runs through the stack, as a length-W chunk per
    row. x: (B, W, d) — row b's chunk occupies absolute positions
    ``start[b] .. start[b]+W-1``; ``valid`` (B, W) masks real suffix
    tokens (right padding). The chunk's K/V scatter into the row's
    private blocks through the table (invalid positions route to the
    ``n_blocks`` sentinel and drop), then every chunk query attends the
    updated pool gathered through the table — shared prefix blocks are
    READ here but never written, which is the copy-on-write guarantee.
    W is a suffix (typically < block_size tokens past the shared
    prefix), so the attention is plain jnp GQA like
    :func:`attention_verify`. Returns (out (B, W, d), new_cache)."""
    B, W = x.shape[:2]
    nh, nkv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim_
    lora = (adapters or {}).get("lora", {})
    lscale = cfg.peft.lora_alpha / max(cfg.peft.lora_rank, 1)
    start = jnp.broadcast_to(jnp.asarray(start, jnp.int32), (B,))
    qpos = start[:, None] + jnp.arange(W, dtype=jnp.int32)[None, :]  # (B, W)

    q = _proj(x, params["wq"], params.get("bq"), lora.get("q"), lscale,
              adapter_ids).reshape(B, W, nh, hd)
    k1 = _proj(x, params["wk"], params.get("bk"), lora.get("k"), lscale,
               adapter_ids).reshape(B, W, nkv, hd)
    v1 = _proj(x, params["wv"], params.get("bv"), lora.get("v"), lscale,
               adapter_ids).reshape(B, W, nkv, hd)
    q = rope(q, qpos, cfg.rope_theta)
    k1 = rope(k1, qpos, cfg.rope_theta)

    table = cache["table"]
    nb, bs = cache["k"].shape[0], cache["k"].shape[1]
    blk = jnp.take_along_axis(table, jnp.clip(qpos // bs, 0,
                                              table.shape[1] - 1), axis=1)
    blk = jnp.where(valid, blk, nb)               # pad tokens: dropped
    off = qpos % bs
    with jax.named_scope("kv_cache"):
        pool_k = cache["k"].at[blk, off].set(k1.astype(cache["k"].dtype),
                                             mode="drop")
        pool_v = cache["v"].at[blk, off].set(v1.astype(cache["v"].dtype),
                                             mode="drop")
        tbl = jnp.clip(table, 0, nb - 1)
        kg = pool_k[tbl].reshape(B, -1, nkv, hd)  # (B, cap, Hkv, D)
        vg = pool_v[tbl].reshape(B, -1, nkv, hd)
    new_cache = {"k": pool_k, "v": pool_v, "table": table}
    kv_pos = jnp.broadcast_to(
        jnp.arange(kg.shape[1], dtype=jnp.int32)[None], (B, kg.shape[1]))
    kp, vp, n_p = _with_prefix(kg, vg, adapters, B, adapter_ids)
    if n_p:
        kv_pos = jnp.concatenate(
            [jnp.full((B, n_p), -1, jnp.int32), kv_pos], axis=1)

    vis = kv_pos[:, None, :] <= qpos[:, :, None]  # causal (B, W, cap)
    vis |= kv_pos[:, None, :] < 0                 # prefix slots
    g = nh // nkv
    qf = q.reshape(B, W, nkv, g, hd).astype(jnp.float32)
    scores = jnp.einsum("btngd,bsnd->bngts", qf,
                        kp.astype(jnp.float32)) * (hd ** -0.5)
    scores = jnp.where(vis[:, None, None], scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1)
    o = jnp.einsum("bngts,bsnd->btngd", probs, vp.astype(jnp.float32))
    o = o.reshape(B, W, nh * hd).astype(x.dtype)
    y = _proj(o, params["wo"], None, lora.get("o"), lscale, adapter_ids)
    return y, new_cache


def cache_spec(cfg: ModelConfig, batch: int, seq_len: int, *,
               window: int = 0, layers: Optional[int] = None,
               paged: Optional[tuple] = None) -> dict:
    """ParamSpec tree for a (stacked-over-layers) KV cache.

    The dense cache is head-major, K/V ``(L, B, Hkv, S, D)``. The
    sliding-window cache is a rolling buffer of ``window`` slots — what
    the prefill path actually builds — regardless of how ``seq_len``
    compares to the window; S is ``ops.decode_slots`` of that (or of
    ``seq_len``). ``pos`` is per-row (L, B, S): each batch row tracks its
    own written slots (ragged serving).

    ``paged=(n_blocks, block_size)`` describes the PAGED layout instead
    (full-window layers only): a layer-stacked device block pool
    ``(L, n_blocks, bs, Hkv, D)`` shared by every row — sharded over
    ``kv_blocks`` (the data axis) instead of per-row ``kv_seq`` — plus
    per-row block tables ``(L, B, ceil(seq_len/bs))``. There is no
    ``pos`` plane: a table slot ``j`` holds positions ``[j*bs,(j+1)*bs)``
    by construction, so visibility is purely causal."""
    L = layers if layers is not None else cfg.n_layers
    nkv, hd = cfg.n_kv_heads, cfg.head_dim_
    S = kops.decode_slots(window if window and window > 0 else seq_len)
    dt = jnp.dtype(cfg.dtype)
    if paged is not None and not (window and window > 0):
        nb, bs = paged
        maxb = -(-seq_len // bs)
        return {
            "k": ParamSpec((L, nb, bs, nkv, hd), dt,
                           (None, "kv_blocks", None, "kv_heads", "head_dim"),
                           init="zeros"),
            "v": ParamSpec((L, nb, bs, nkv, hd), dt,
                           (None, "kv_blocks", None, "kv_heads", "head_dim"),
                           init="zeros"),
            "table": ParamSpec((L, batch, maxb), jnp.int32,
                               (None, "batch", None), init="zeros"),
        }
    return {
        "k": ParamSpec((L, batch, nkv, S, hd), dt,
                       (None, "batch", "kv_heads", "kv_seq", "head_dim"),
                       init="zeros"),
        "v": ParamSpec((L, batch, nkv, S, hd), dt,
                       (None, "batch", "kv_heads", "kv_seq", "head_dim"),
                       init="zeros"),
        "pos": ParamSpec((L, batch, S), jnp.int32, (None, "batch", "kv_seq"),
                         init="zeros"),
    }
