"""Public model API: spec / init / train forward / prefill / decode.

Params are split at the top level into ``backbone`` (frozen under the
paper's PEFT regime) and ``adapters`` (the tunable modules: prefix-KV
prompts, LoRA, state prompts, classification head). core/peft.py and
core/hfsl.py operate on this split.
"""
from __future__ import annotations

import functools
from typing import Any, Optional

import jax
import jax.numpy as jnp

from repro.configs.base import InputShape, ModelConfig
from repro.models import encdec
from repro.models.layers import (cross_entropy, embed, embed_spec, rmsnorm,
                                 rmsnorm_spec, unembed)
from repro.models.transformer import (adapter_stack_spec, cache_group_spec,
                                      paged_subs, rec_cache_part, stack_chunk,
                                      stack_decode, stack_seq, stack_spec,
                                      stack_verify)
from repro.sharding.rules import (ParamSpec, init_from_spec, serving_rules,
                                  shard, use_rules)

# ---------------------------------------------------------------------------
# Specs
# ---------------------------------------------------------------------------


def backbone_spec(cfg: ModelConfig) -> dict:
    s: dict = {"embed": embed_spec(cfg.vocab_size, cfg.d_model, jnp.dtype(cfg.dtype)),
               "final_norm": rmsnorm_spec(cfg.d_model)}
    if cfg.family == "audio":
        s["encdec"] = encdec.encdec_stack_spec(cfg)
    else:
        s["layers"] = stack_spec(cfg)
    if not cfg.tie_embeddings:
        s["lm_head"] = embed_spec(cfg.vocab_size, cfg.d_model, jnp.dtype(cfg.dtype))
    return s


def adapter_spec(cfg: ModelConfig) -> dict:
    if cfg.family == "audio":
        a: dict = {"stack": encdec.encdec_adapter_spec(cfg)}
    else:
        a = {"stack": adapter_stack_spec(cfg)}
    if cfg.peft.head_dim_out:
        a["head"] = {
            "w": ParamSpec((cfg.d_model, cfg.peft.head_dim_out), jnp.float32,
                           ("fsdp", None), init="scaled"),
            "b": ParamSpec((cfg.peft.head_dim_out,), jnp.float32, (None,),
                           init="zeros"),
        }
    return a


def model_spec(cfg: ModelConfig) -> dict:
    return {"backbone": backbone_spec(cfg), "adapters": adapter_spec(cfg)}


def init(cfg: ModelConfig, key: jax.Array, shardings=None) -> dict:
    """Seeded parameters; ``shardings`` (a tree matching
    :func:`model_spec`) places each leaf as soon as it is drawn."""
    return init_from_spec(key, model_spec(cfg), shardings)


def cache_spec(cfg: ModelConfig, batch: int, seq_len: int, *,
               paged=None) -> dict:
    """``paged=(n_blocks, block_size)`` describes the paged layout for the
    eligible (full-window attention) sub-layers — see
    transformer.cache_group_spec / attention.cache_spec."""
    if cfg.family == "audio":
        return encdec.encdec_cache_spec(cfg, batch, seq_len)
    return cache_group_spec(cfg, batch, seq_len, paged=paged)


# ---------------------------------------------------------------------------
# Inputs
# ---------------------------------------------------------------------------


def input_specs(cfg: ModelConfig, shape: InputShape) -> dict:
    """ShapeDtypeStruct stand-ins for every model input (dry-run contract)."""
    B, S = shape.global_batch, shape.seq_len
    dt = jnp.dtype(cfg.dtype)
    tok = lambda s: jax.ShapeDtypeStruct((B, s), jnp.int32)
    if shape.kind == "decode":
        batch: dict = {"token": jax.ShapeDtypeStruct((B, 1), jnp.int32)}
    elif cfg.family == "vlm":
        n_vis = cfg.vlm.n_vis_tokens
        batch = {"tokens": tok(S - n_vis),
                 "vision_embeds": jax.ShapeDtypeStruct(
                     (B, n_vis, cfg.d_model), dt)}
    elif cfg.family == "audio":
        batch = {"tokens": tok(S),
                 "frames": jax.ShapeDtypeStruct(
                     (B, cfg.audio.n_audio_frames, cfg.d_model), dt)}
    else:
        batch = {"tokens": tok(S)}
    if shape.kind == "train" and "tokens" in batch:
        batch["labels"] = jax.ShapeDtypeStruct(batch["tokens"].shape, jnp.int32)
    return batch


def input_pspec_axes(cfg: ModelConfig, shape: InputShape) -> dict:
    """Logical axes per input leaf (same tree structure as input_specs)."""
    out = {}
    for k, v in input_specs(cfg, shape).items():
        out[k] = ("batch",) + ("seq",) * (len(v.shape) - 1) if v.ndim <= 2 \
            else ("batch", "seq", "d_model")
    return out


# ---------------------------------------------------------------------------
# Forward passes
# ---------------------------------------------------------------------------


# Named scopes split each program into the layers a profile reports:
# ``embed``, ``layers`` (the layer scan; ``layer`` is its body), ``lm_head``
# (final norm, unembedding and the loss) and ``sample``; inside a layer
# ``attn`` (with ``kv_cache`` and ``lora``) and ``mlp``; ``steps`` around a
# decode segment's step loop, whose own ops are whatever XLA places around
# the layer scan. Scopes change only the ops' metadata, never the program.


@jax.named_scope("embed")
def _embed_tokens(params: dict, tokens: jax.Array) -> jax.Array:
    x = embed(params["backbone"]["embed"], tokens)
    return shard(x, "batch", "seq", "d_model")


@jax.named_scope("lm_head")
def _lm_head(params: dict, x: jax.Array) -> jax.Array:
    """Final norm and unembedding: logits."""
    x = rmsnorm(params["backbone"]["final_norm"], x)
    head_tbl = params["backbone"].get("lm_head", params["backbone"]["embed"])
    return unembed(head_tbl, x)


@jax.named_scope("embed")
def _embed_inputs(params: dict, batch: dict, cfg: ModelConfig):
    """Token (+modality) embedding. Returns (x, positions, label_offset)."""
    x = embed(params["backbone"]["embed"], batch["tokens"])
    x = shard(x, "batch", "seq", "d_model")
    n_vis = 0
    if cfg.family == "vlm" and "vision_embeds" in batch:
        vis = batch["vision_embeds"].astype(x.dtype)
        x = jnp.concatenate([vis, x], axis=1)
        n_vis = vis.shape[1]
    S = x.shape[1]
    return x, jnp.arange(S, dtype=jnp.int32), n_vis


def forward(params: dict, batch: dict, cfg: ModelConfig, *,
            mode: str = "train", remat: Optional[bool] = None,
            adapter_ids: Optional[jax.Array] = None) -> dict:
    """Full-sequence forward. Returns {'hidden', 'logits', 'aux'}.

    ``adapter_ids`` (B,) enables multi-tenant serving: adapter stack leaves
    carry an ``n_slots`` dim after the layer dim (the AdapterBank serving
    layout) and each batch row computes with its own domain's adapters.
    """
    remat = (mode == "train") if remat is None else remat
    adapters = params.get("adapters", {}).get("stack", {})
    if cfg.family == "audio":
        if adapter_ids is not None:
            raise NotImplementedError(
                "multi-tenant adapter_ids not supported for the audio "
                "encoder-decoder family")
        enc_out = encdec.encode(params["backbone"]["encdec"], adapters,
                                batch["frames"], cfg, remat=remat)
        tok_emb = embed(params["backbone"]["embed"], batch["tokens"])
        x, _ = encdec.decode_seq(params["backbone"]["encdec"], adapters,
                                 tok_emb, enc_out, cfg, remat=remat)
        aux = jnp.zeros((), jnp.float32)
    else:
        x, positions, _ = _embed_inputs(params, batch, cfg)
        x, _, aux = stack_seq(params["backbone"]["layers"], adapters, x, cfg,
                              positions=positions, remat=remat,
                              adapter_ids=adapter_ids)
    with jax.named_scope("lm_head"):
        x = rmsnorm(params["backbone"]["final_norm"], x)
        head_tbl = params["backbone"].get("lm_head",
                                          params["backbone"]["embed"])
        logits = unembed(head_tbl, x)
        logits = shard(logits, "batch", "seq", "vocab")
    return {"hidden": x, "logits": logits, "aux": aux}


def lm_loss(params: dict, batch: dict, cfg: ModelConfig, *,
            remat: Optional[bool] = None) -> tuple[jax.Array, dict]:
    out = forward(params, batch, cfg, mode="train", remat=remat)
    logits = out["logits"]
    labels = batch["labels"]
    with jax.named_scope("lm_head"):
        if logits.shape[1] != labels.shape[1]:      # vlm: loss on text only
            logits = logits[:, -labels.shape[1]:]
        loss = cross_entropy(logits, labels) + out["aux"]
    return loss, {"aux": out["aux"]}


def classify(params: dict, batch: dict, cfg: ModelConfig, *,
             remat: bool = False,
             adapter_ids: Optional[jax.Array] = None) -> jax.Array:
    """Paper case-study head: mean-pool hidden states -> adapter head logits.

    With ``adapter_ids`` the head is stacked (n_slots, d, out) and each row
    is scored by its own domain's head (mixed-domain accuracy in one call).
    """
    out = forward(params, batch, cfg, mode="eval", remat=remat,
                  adapter_ids=adapter_ids)
    pooled = jnp.mean(out["hidden"].astype(jnp.float32), axis=1)
    h = params["adapters"]["head"]
    if adapter_ids is not None:
        w = jnp.take(h["w"], adapter_ids, axis=0)      # (B, d, out)
        b = jnp.take(h["b"], adapter_ids, axis=0)      # (B, out)
        return jnp.einsum("bd,bdo->bo", pooled, w) + b
    return pooled @ h["w"] + h["b"]


def classify_loss(params: dict, batch: dict, cfg: ModelConfig) -> tuple[jax.Array, dict]:
    logits = classify(params, batch, cfg)
    loss = cross_entropy(logits, batch["label"])
    acc = jnp.mean((jnp.argmax(logits, -1) == batch["label"]).astype(jnp.float32))
    return loss, {"acc": acc}


# ---------------------------------------------------------------------------
# Serving: prefill + decode
# ---------------------------------------------------------------------------


def prefill(params: dict, batch: dict, cfg: ModelConfig,
            max_len: Optional[int] = None,
            adapter_ids: Optional[jax.Array] = None,
            prompt_lens: Optional[jax.Array] = None) -> tuple[jax.Array, dict]:
    """Run the prompt, build caches (padded to max_len for decoding into).

    ``prompt_lens`` (B,) serves a RAGGED wave: prompts are right-padded to
    a shared width and row b's valid tokens are ``tokens[b, :prompt_lens
    [b]]``. The caches come out bitwise identical to prefilling each row
    alone (per-row sentinel cache positions; identity-frozen recurrent
    state over padding), and the returned logits are each row's own
    last-token logits — decode then continues from per-row positions.

    Returns ((B, 1, vocab) last-token logits, caches)."""
    adapters = params.get("adapters", {}).get("stack", {})
    if cfg.family == "audio":
        if adapter_ids is not None:
            raise NotImplementedError(
                "multi-tenant adapter_ids not supported for the audio "
                "encoder-decoder family")
        enc_out = encdec.encode(params["backbone"]["encdec"], adapters,
                                batch["frames"], cfg)
        tok_emb = embed(params["backbone"]["embed"], batch["tokens"])
        lengths = prompt_lens
        x, caches = encdec.decode_seq(params["backbone"]["encdec"], adapters,
                                      tok_emb, enc_out, cfg, make_cache=True,
                                      cache_len=max_len, lengths=lengths)
    else:
        x, positions, n_vis = _embed_inputs(params, batch, cfg)
        lengths = None if prompt_lens is None else prompt_lens + n_vis
        x, caches, _ = stack_seq(params["backbone"]["layers"], adapters, x,
                                 cfg, positions=positions, make_cache=True,
                                 remat=False, cache_len=max_len,
                                 adapter_ids=adapter_ids, lengths=lengths)
    with jax.named_scope("lm_head"):
        if lengths is None:
            x = x[:, -1:]
        else:                              # per-row last VALID token
            B = x.shape[0]
            x = x[jnp.arange(B)[:, None], (lengths - 1)[:, None]]
    return _lm_head(params, x), caches


def _scan_steps(params: dict, cfg: ModelConfig, steps: int, greedy: bool,
                tok, caches, pos, remaining, key, adapter_ids,
                with_state: bool = False):
    """Scan ``steps`` decode steps with per-row positions and retirement.

    The carry is (token, caches, pos (B,), remaining (B,), key); each step
    emits the carried token then computes the next. Rows with
    ``remaining <= 0`` are RETIRED: their cache writes are dropped, their
    position and carried token freeze, and their emitted tokens are
    padding the caller discards — so a retired row costs the step's FLOPs
    (counted by the engine as ``padded_tokens``) but cannot perturb its
    own or any other row's generation.

    ``with_state`` additionally emits the post-step recurrent cache parts
    (transformer.rec_cache_part) per step — the drafter in speculative
    decoding IS this scan: step j's snapshot is the drafter state after
    processing chunk offset j, the exact rollback points spec_decode
    needs. Returns (toks (B, steps), carry[, snaps (L, B, steps, ...)])."""

    def step(carry, _):
        tok, caches, pos, remaining, key = carry
        active = remaining > 0
        logits, caches = decode_step(params, tok, caches, pos, cfg,
                                     adapter_ids=adapter_ids, active=active)
        with jax.named_scope("sample"):
            if greedy:
                nxt = jnp.argmax(logits[:, -1], axis=-1)[:, None]
            else:
                key, sub = jax.random.split(key)
                nxt = jax.random.categorical(sub, logits[:, -1])[:, None]
            nxt = jnp.where(active[:, None], nxt.astype(jnp.int32), tok)
        pos = pos + active.astype(jnp.int32)
        remaining = remaining - active.astype(jnp.int32)
        ys = (tok, rec_cache_part(caches)) if with_state else tok
        return (nxt, caches, pos, remaining, key), ys

    with jax.named_scope("steps"):
        carry, ys = jax.lax.scan(step, (tok, caches, pos, remaining, key),
                                 None, length=steps)
    if with_state:
        toks, snaps = ys
        snaps = jax.tree.map(lambda s: jnp.moveaxis(s, 0, 2), snaps)
        return jnp.swapaxes(toks[..., 0], 0, 1), carry, snaps
    return jnp.swapaxes(ys[..., 0], 0, 1), carry           # (B, steps), carry


def _prefill_state(params: dict, batch: dict, cfg: ModelConfig, cap: int,
                   adapter_ids, prompt_lens):
    """Shared prefill -> (tok0, caches, pos0) decode-entry state.

    ``cap`` is the cache capacity in PROMPT+GENERATION tokens; the vlm
    vision prefix is added on top internally."""
    S = batch["tokens"].shape[1]
    n_vis = cfg.vlm.n_vis_tokens if cfg.family == "vlm" else 0
    logits, caches = prefill(params, batch, cfg, max_len=cap + n_vis,
                             adapter_ids=adapter_ids, prompt_lens=prompt_lens)
    with jax.named_scope("sample"):
        tok0 = jnp.argmax(logits[:, -1], axis=-1)[:, None].astype(jnp.int32)
    B = batch["tokens"].shape[0]
    if prompt_lens is None:
        pos0 = jnp.full((B,), S + n_vis, jnp.int32)
    else:
        pos0 = prompt_lens.astype(jnp.int32) + n_vis
    return tok0, caches, pos0


def _wave_rules(mesh):
    """(mesh, rules) context for the fused serving dispatches.

    With a mesh, every wave/refill/segment jit traces under
    rules.serving_rules(): the wave batch constrains onto `data`, head/FF
    dims onto `model` (the shard() calls inside attention/moe/ssm resolve
    against the active rule set). Without one this is a no-op context —
    the unsharded path is byte-identical to before.
    """
    return use_rules(mesh, serving_rules() if mesh is not None else None)


# tracelint: keys=cfg,cap,mesh
@functools.lru_cache(maxsize=64)
def _wave_prefill_fn(cfg: ModelConfig, cap: int, mesh=None):
    """Jitted ragged wave prefill: batch + prompt_lens -> decode state."""

    def wave_prefill(params, batch, prompt_lens, adapter_ids):
        with _wave_rules(mesh):
            return _prefill_state(params, batch, cfg, cap, adapter_ids,
                                  prompt_lens)

    return jax.jit(wave_prefill)


# tracelint: keys=cfg,cap,mesh
@functools.lru_cache(maxsize=64)
def _refill_fn(cfg: ModelConfig, cap: int, mesh=None):
    """Jitted in-wave slot refill: prefill fresh rows INTO a live wave.

    ``batch`` holds ONLY the rows being admitted (padded to a pow2 row
    count — usually far fewer than the wave width), and ``row_idx`` maps
    each to its wave slot; padding rows carry an out-of-range index and
    are dropped by the scatter. Every cache leaf has batch at dim 1
    ((L, B, ...) group stacking), so the merge is one row-scatter per
    leaf and the surviving rows' generation state stays bitwise
    untouched. This is what makes the drain TRUE continuous batching: a
    freed slot is re-prefilled between scan segments at the cost of a
    refill-sized prefill, not a wave-sized one. The wave's caches are
    donated: the merge writes the admitted rows into them in place, and
    the caller rebinds them from the result."""

    def refill(params, batch, prompt_lens, row_idx, tok, caches, pos,
               adapter_ids):
        with _wave_rules(mesh):
            tok_n, caches_n, pos_n = _prefill_state(params, batch, cfg, cap,
                                                    adapter_ids, prompt_lens)

            def merge(old, new):
                return old.at[:, row_idx].set(new.astype(old.dtype),
                                              mode="drop")

            with jax.named_scope("kv_cache"):
                caches = jax.tree.map(merge, caches, caches_n)
            tok = tok.at[row_idx].set(tok_n, mode="drop")
            pos = pos.at[row_idx].set(pos_n, mode="drop")
            return tok, caches, pos

    return jax.jit(refill, donate_argnums=(5,))


# tracelint: keys=cfg,steps,greedy,mesh
@functools.lru_cache(maxsize=64)
def _segment_fn(cfg: ModelConfig, steps: int, greedy: bool, mesh=None):
    """Jitted decode segment: ``steps`` scanned steps of a ragged wave.

    Segment lengths are powers of two (the engine buckets them), so the
    jit cache stays O(log max_budget) across any mix of per-row budgets
    instead of growing per distinct budget. The caches are donated: the
    step loop writes each token into them in place, and the caller
    rebinds them from the result."""

    def decode_segment(params, tok, caches, pos, remaining, key,
                       adapter_ids):
        with _wave_rules(mesh):
            toks, (tok, caches, pos, remaining, key) = _scan_steps(
                params, cfg, steps, greedy, tok, caches, pos, remaining, key,
                adapter_ids)
            return toks, tok, caches, pos, remaining, key

    return jax.jit(decode_segment, donate_argnums=(2,))


# -- paged KV cache (block pool + per-row tables) ---------------------------

@jax.named_scope("kv_cache")
def _pool_commit(pool_sub: dict, dense_k, dense_v, tables, lens):
    """Scatter dense prefill K/V for B rows into the block pool.

    pool_sub: {'k','v'[,'table']} with pool leaves (L, nb, bs, Hkv, D);
    dense_k/v: (L, B, Hkv, S_pad, D) freshly prefilled rows; tables:
    (B, maxb) int32 block tables; lens: (B,) valid lengths. Token ``t``
    of row ``b`` lands at ``pool[:, tables[b, t//bs], t%bs]``; tokens at
    or beyond ``lens[b]`` route to the ``nb`` sentinel and drop (pad
    rows and prefix-HIT rows are excluded by an all-sentinel table /
    lens of 1 over a dummy prompt... their real state arrives via
    :func:`_paged_suffix_fn`). The values written are EXACTLY the dense
    prefill's — which is what keeps paged drains bit-identical."""
    nb, bs = pool_sub["k"].shape[1], pool_sub["k"].shape[2]
    S_pad = dense_k.shape[3]
    t_idx = jnp.arange(S_pad, dtype=jnp.int32)
    blk = jnp.where(t_idx[None, :] < lens[:, None],
                    tables[:, t_idx // bs], nb)            # (B, S_pad)
    off = jnp.broadcast_to(t_idx % bs, blk.shape)
    k = pool_sub["k"].at[:, blk, off].set(
        jnp.swapaxes(dense_k, 2, 3).astype(pool_sub["k"].dtype), mode="drop")
    v = pool_sub["v"].at[:, blk, off].set(
        jnp.swapaxes(dense_v, 2, 3).astype(pool_sub["v"].dtype), mode="drop")
    return k, v


# tracelint: keys=cfg,cap,bs,mesh
@functools.lru_cache(maxsize=64)
def _paged_prefill_fn(cfg: ModelConfig, cap: int, bs: int, mesh=None):
    """Jitted paged wave prefill: dense prefill -> pool commit.

    Runs the EXACT dense ragged prefill (same numerics, bit-for-bit),
    then scatters each eligible sub-layer's K/V into the device block
    pool through the host-built tables and swaps the sub-tree to the
    paged {'k','v','table'} layout (table broadcast over the scanned
    layer dim). Ineligible sub-layers (sliding window, recurrent) keep
    their dense cache untouched. ``pool`` is the persistent device pool
    tree {group: {sub: {'k','v'}}} for eligible subs."""
    subs = frozenset(paged_subs(cfg))

    def paged_prefill(params, batch, prompt_lens, tables, pool,
                      adapter_ids):
        with _wave_rules(mesh):
            tok0, dense, pos0 = _prefill_state(params, batch, cfg, cap,
                                               adapter_ids, prompt_lens)
            tables = jnp.asarray(tables, jnp.int32)
            B, maxb = tables.shape
            lens = prompt_lens.astype(jnp.int32)
            caches = {}
            for g, grp in dense.items():
                caches[g] = {}
                for s, c in grp.items():
                    if (g, s) in subs:
                        k, v = _pool_commit(pool[g][s], c["k"], c["v"],
                                            tables, lens)
                        L = k.shape[0]
                        caches[g][s] = {
                            "k": k, "v": v,
                            "table": jnp.broadcast_to(tables[None],
                                                      (L, B, maxb))}
                    else:
                        caches[g][s] = c
            return tok0, caches, pos0

    return jax.jit(paged_prefill)


# tracelint: keys=cfg,cap,bs,mesh
@functools.lru_cache(maxsize=64)
def _paged_refill_fn(cfg: ModelConfig, cap: int, bs: int, mesh=None):
    """Jitted paged in-wave refill: admitted rows' K/V commit into the
    LIVE pool through their fresh tables; table rows scatter at
    ``row_idx``; ineligible leaves row-merge exactly like _refill_fn."""
    subs = frozenset(paged_subs(cfg))

    def paged_refill(params, batch, prompt_lens, row_idx, tables_rows, tok,
                     caches, pos, adapter_ids):
        with _wave_rules(mesh):
            tok_n, dense_n, pos_n = _prefill_state(params, batch, cfg, cap,
                                                   adapter_ids, prompt_lens)
            tables_rows = jnp.asarray(tables_rows, jnp.int32)
            Br, maxb = tables_rows.shape
            lens = prompt_lens.astype(jnp.int32)
            out = {}
            for g, grp in caches.items():
                out[g] = {}
                for s, old in grp.items():
                    if (g, s) in subs:
                        cn = dense_n[g][s]
                        k, v = _pool_commit(old, cn["k"], cn["v"],
                                            tables_rows, lens)
                        L = k.shape[0]
                        table = old["table"].at[:, row_idx].set(
                            jnp.broadcast_to(tables_rows[None],
                                             (L, Br, maxb)), mode="drop")
                        out[g][s] = {"k": k, "v": v, "table": table}
                    else:
                        out[g][s] = jax.tree.map(
                            lambda o, n: o.at[:, row_idx].set(
                                n.astype(o.dtype), mode="drop"),
                            old, dense_n[g][s])
            tok = tok.at[row_idx].set(tok_n, mode="drop")
            pos = pos.at[row_idx].set(pos_n, mode="drop")
            return tok, out, pos

    return jax.jit(paged_refill)


# tracelint: keys=cfg,cap,bs,mesh
@functools.lru_cache(maxsize=64)
def _paged_suffix_fn(cfg: ModelConfig, cap: int, bs: int, mesh=None):
    """Jitted prefix-HIT admission: prefill ONLY the private suffix.

    A row whose prompt prefix matched cached blocks skips re-prefilling
    them — its table already maps the shared blocks (acquired, never
    written: copy-on-write), and this dispatch runs just the suffix
    chunk through the stack (transformer.stack_chunk), scattering
    suffix K/V into the row's private blocks and producing the row's
    first decode token + position. Requires a fully paged stack (the
    engine gates prefix sharing to such configs)."""

    def paged_suffix(params, tokens, suffix_lens, start, row_idx,
                     tables_rows, tok, caches, pos, adapter_ids):
        with _wave_rules(mesh):
            adapters = params.get("adapters", {}).get("stack", {})
            Br, W = tokens.shape
            tables_rows = jnp.asarray(tables_rows, jnp.int32)
            maxb = tables_rows.shape[1]
            suffix_lens = suffix_lens.astype(jnp.int32)
            start = start.astype(jnp.int32)
            x = _embed_tokens(params, tokens)
            valid = jnp.arange(W, dtype=jnp.int32)[None, :] \
                < suffix_lens[:, None]
            sub_caches = {
                g: {s: {"k": c["k"], "v": c["v"],
                        "table": jnp.broadcast_to(
                            tables_rows[None], (c["k"].shape[0], Br, maxb))}
                    for s, c in grp.items()}
                for g, grp in caches.items()}
            x, new_sub = stack_chunk(params["backbone"]["layers"], adapters,
                                     x, sub_caches, cfg, start=start,
                                     valid=valid, adapter_ids=adapter_ids)
            with jax.named_scope("lm_head"):
                xl = x[jnp.arange(Br)[:, None],
                       jnp.maximum(suffix_lens - 1, 0)[:, None]]
            logits = _lm_head(params, xl)
            with jax.named_scope("sample"):
                tok_n = jnp.argmax(logits[:, -1], axis=-1)[:, None] \
                    .astype(jnp.int32)
            pos_n = start + suffix_lens
            out = {}
            for g, grp in caches.items():
                out[g] = {}
                for s, old in grp.items():
                    ns = new_sub[g][s]
                    table = old["table"].at[:, row_idx].set(
                        jnp.broadcast_to(
                            tables_rows[None],
                            (old["k"].shape[0], Br, maxb)), mode="drop")
                    out[g][s] = {"k": ns["k"], "v": ns["v"], "table": table}
            tok = tok.at[row_idx].set(tok_n, mode="drop")
            pos = pos.at[row_idx].set(pos_n, mode="drop")
            return tok, out, pos

    return jax.jit(paged_suffix)


# Fused-fn cache-key invariant: every trace-shaping argument must appear
# in the lru key, and ONLY trace-shaping arguments (a spurious key arg
# would fork identical jits). The key tuples are machine-checked — each
# factory carries a ``# tracelint: keys=...`` declaration that
# repro.analysis rule R1 cross-checks against the signature AND against
# the names its jitted impl actually closes over. Non-obvious choices:
#   - _verify_fn deliberately excludes k: the chunk width T is a jit
#     input shape, so verify re-specializes per width for free.
#   - _segment_fn serves paged and dense waves through ONE key — jit
#     re-specializes on the cache TREE STRUCTURE, not the key tuple.
#   - Prompt/suffix widths and n_blocks/maxb are jit shapes everywhere,
#     never keys; mesh is a key everywhere (it picks the sharding rules).
# tests/test_spec_decode.py sweeps draft_k and asserts the caches stay
# bounded by exactly these key tuples.


# tracelint: keys=dcfg,k,mesh
@functools.lru_cache(maxsize=64)
def _draft_fn(dcfg: ModelConfig, k: int, mesh=None):
    """Jitted draft segment: k+1 scanned greedy drafter steps.

    The drafter processes [carry_tok, d1..dk] — one step more than it
    proposes — so its per-step state snapshots cover every rollback point
    a chunk can commit to (0..k accepted drafts). Returns (drafts (B, k),
    final drafter caches, per-step recurrent snapshots)."""

    from repro.core import spec_decode as sd                # lazy: no cycle

    def draft(dparams, tok, dcaches, pos, active):
        with _wave_rules(mesh):
            return sd.draft_chunk(dparams, dcfg, k, tok, dcaches, pos,
                                  active)

    return jax.jit(draft)


# tracelint: keys=cfg,mesh
@functools.lru_cache(maxsize=64)
def _verify_fn(cfg: ModelConfig, mesh=None):
    """Jitted one-pass chunk verify (see verify_step)."""

    def verify(params, tokens, caches, pos, active, adapter_ids):
        with _wave_rules(mesh):
            return verify_step(params, tokens, caches, pos, cfg,
                               adapter_ids=adapter_ids, active=active)

    return jax.jit(verify)


# tracelint: keys=cfg,dcfg,chunks,k,mesh
@functools.lru_cache(maxsize=64)
def _spec_segment_fn(cfg: ModelConfig, dcfg: ModelConfig, chunks: int,
                     k: int, mesh=None):
    """Jitted speculative decode segment: ``chunks`` scanned draft+verify
    chunks of a ragged wave (core/spec_decode.py::spec_segment). Chunk
    counts are pow2-bucketed by the engine, mirroring _segment_fn."""
    from repro.core import spec_decode as sd                # lazy: no cycle

    def spec_segment(params, dparams, tok, caches, dcaches, pos, remaining,
                     spec_rows, adapter_ids):
        with _wave_rules(mesh):
            return sd.spec_segment(params, dparams, cfg, dcfg, chunks, k,
                                   tok, caches, dcaches, pos, remaining,
                                   spec_rows, adapter_ids, mesh=mesh)

    return jax.jit(spec_segment)


# tracelint: keys=cfg,gen,greedy,mesh
@functools.lru_cache(maxsize=64)
def _generate_fn(cfg: ModelConfig, gen: int, greedy: bool, mesh=None):
    """Build + jit the fused prefill-and-scan generator for one config.

    The whole request — prefill, ``gen`` decode steps, sampling — is ONE
    jitted computation: the decode loop is a ``jax.lax.scan`` whose carry
    (token, caches, per-row positions, key) stays on device, so XLA
    donates the cache buffers step-to-step and the host dispatches once
    per request instead of once per token. Cached per (cfg, gen, greedy);
    jit re-specializes per input shape as usual.
    """

    def generate(params: dict, batch: dict, key: jax.Array,
                 adapter_ids, prompt_lens) -> jax.Array:
        with _wave_rules(mesh):
            S = batch["tokens"].shape[1]
            tok0, caches, pos0 = _prefill_state(params, batch, cfg, S + gen,
                                                adapter_ids, prompt_lens)
            B = batch["tokens"].shape[0]
            remaining = jnp.full((B,), gen, jnp.int32)
            toks, _ = _scan_steps(params, cfg, gen, greedy, tok0, caches,
                                  pos0, remaining, key, adapter_ids)
            return toks                                    # (B, gen)

    return jax.jit(generate)


def place_params(params: dict, cfg: ModelConfig, mesh,
                 rules: Optional[dict] = None) -> dict:
    """device_put a {backbone, adapters} tree onto ``mesh`` per the rule
    set (default serving_rules): weight dims shard where they divide, the
    rest replicate. Callers of the mesh-sharded serving path must place
    params before the first dispatch — jit rejects committed inputs whose
    placement disagrees with the computation's mesh."""
    from repro.sharding.rules import named_shardings
    spec = model_spec(cfg)
    spec = {k: spec[k] for k in params if k in spec}
    sh = named_shardings(spec, mesh, rules or serving_rules())
    return {**params, **jax.device_put({k: params[k] for k in sh}, sh)}


def generate_scan(params: dict, cfg: ModelConfig, prompts: jax.Array, *,
                  gen: int, extra_batch: Optional[dict] = None,
                  greedy: bool = True,
                  key: Optional[jax.Array] = None,
                  adapter_ids: Optional[jax.Array] = None,
                  prompt_lens=None, mesh=None) -> jax.Array:
    """Single-dispatch generation: prefill + scanned decode in one jit call.

    prompts: (B, S) int32. Returns (B, gen) generated tokens. Matches the
    legacy per-token loop (launch/serve.py::generate_loop) token-for-token:
    the first emitted token is the prefill argmax, subsequent tokens are
    argmax (greedy) or categorical samples drawn with the same per-step key
    splits.

    ``adapter_ids`` (B,) int32 serves a multi-tenant wave: params carry the
    AdapterBank stacked-adapter layout and row i generates with adapter
    slot ``adapter_ids[i]`` — token-for-token equal to serving row i alone
    with that slot's adapters.

    ``prompt_lens`` (B,) int32 serves a RAGGED wave: prompts are
    right-padded to the shared width and row b generates from position
    ``prompt_lens[b]`` — token-for-token equal to serving row b alone with
    its unpadded prompt.

    ``mesh`` traces the dispatch under rules.serving_rules() (batch over
    `data`, head/FF dims over `model`); params must already be placed on
    the mesh (:func:`place_params` / AdapterBank(mesh=...)).
    """
    batch = {"tokens": prompts, **(extra_batch or {})}
    if greedy or key is None:
        greedy, key = True, jax.random.PRNGKey(0)          # key unused
    ids = None if adapter_ids is None else \
        jnp.asarray(adapter_ids, jnp.int32)
    lens = None if prompt_lens is None else \
        jnp.asarray(prompt_lens, jnp.int32)
    return _generate_fn(cfg, int(gen), bool(greedy), mesh)(params, batch,
                                                           key, ids, lens)


def decode_step(params: dict, token: jax.Array, caches: dict,
                pos: jax.Array, cfg: ModelConfig,
                adapter_ids: Optional[jax.Array] = None,
                active: Optional[jax.Array] = None
                ) -> tuple[jax.Array, dict]:
    """One token. token: (B, 1) int32; pos: scalar or per-row (B,) int32
    (current position). ``active`` (B,) bool freezes retired rows' caches
    (ragged serving — see :func:`_scan_steps`)."""
    adapters = params.get("adapters", {}).get("stack", {})
    B = token.shape[0]
    pos = jnp.broadcast_to(jnp.asarray(pos, jnp.int32), (B,))
    x = _embed_tokens(params, token)
    if cfg.family == "audio":
        x, caches = encdec.decode_step(params["backbone"]["encdec"], adapters,
                                       x, caches, cfg, pos=pos, active=active)
    else:
        x, caches = stack_decode(params["backbone"]["layers"], adapters, x,
                                 caches, cfg, pos=pos,
                                 adapter_ids=adapter_ids, active=active)
    return _lm_head(params, x), caches


def verify_step(params: dict, tokens: jax.Array, caches: dict,
                pos: jax.Array, cfg: ModelConfig,
                adapter_ids: Optional[jax.Array] = None,
                active: Optional[jax.Array] = None):
    """Speculative verify: run the target model over a whole draft chunk in
    ONE pass against the live caches. tokens: (B, T) int32 — row b's chunk
    sits at positions ``pos[b] .. pos[b]+T-1``. Returns (logits (B, T,
    vocab), new_caches, rec_snaps); ``logits[:, j]`` is the distribution
    AFTER processing chunk offset j, so greedy targets are
    ``argmax(logits, -1)``. ``new_caches`` assumes full acceptance and
    ``rec_snaps`` carries per-step recurrent state — both feed
    core/spec_decode.py::rollback_caches, which is mandatory before the
    next chunk (see stack_verify)."""
    if cfg.family in ("audio", "vlm"):
        raise NotImplementedError(
            f"speculative verify not supported for family={cfg.family!r}")
    adapters = params.get("adapters", {}).get("stack", {})
    B = tokens.shape[0]
    pos = jnp.broadcast_to(jnp.asarray(pos, jnp.int32), (B,))
    x = _embed_tokens(params, tokens)
    x, caches, snaps = stack_verify(params["backbone"]["layers"], adapters,
                                    x, caches, cfg, pos=pos,
                                    adapter_ids=adapter_ids, active=active)
    return _lm_head(params, x), caches, snaps
