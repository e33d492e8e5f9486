"""Whisper-style encoder-decoder backbone.

Modality carve-out (spec): the mel-spectrogram + conv feature extractor is a
STUB — `input_specs()` supplies precomputed frame embeddings of shape
(B, n_frames, d_model). This module implements the transformer that consumes
them: a non-causal encoder stack and a causal decoder stack with per-layer
cross attention. Learned absolute positional embeddings (whisper uses
sinusoidal/learned, not RoPE).
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.kernels import ops as kops
from repro.models import attention as attn_mod
from repro.models.layers import layernorm, layernorm_spec, mlp, mlp_spec
from repro.models.transformer import (_stack, sublayer_adapter_spec)
from repro.sharding.rules import ParamSpec, shard


def enc_layer_spec(cfg: ModelConfig) -> dict:
    d = cfg.d_model
    return {"ln1": layernorm_spec(d), "attn": attn_mod.attn_spec(cfg),
            "ln2": layernorm_spec(d), "mlp": mlp_spec(d, cfg.d_ff, jnp.dtype(cfg.dtype))}


def dec_layer_spec(cfg: ModelConfig) -> dict:
    d = cfg.d_model
    return {"ln1": layernorm_spec(d), "self": attn_mod.attn_spec(cfg),
            "ln2": layernorm_spec(d), "cross": attn_mod.attn_spec(cfg),
            "ln3": layernorm_spec(d), "mlp": mlp_spec(d, cfg.d_ff, jnp.dtype(cfg.dtype))}


def encdec_stack_spec(cfg: ModelConfig) -> dict:
    a = cfg.audio
    d = cfg.d_model
    return {
        "enc_pos": ParamSpec((a.n_audio_frames, d), jnp.dtype(cfg.dtype),
                             ("frames", "fsdp")),
        # whisper's native decoder context is 448; sized to the largest
        # assigned prefill shape so the distribution config lowers
        # (semantic mismatch noted in DESIGN.md §6)
        "dec_pos": ParamSpec((32768, d), jnp.dtype(cfg.dtype), (None, "fsdp")),
        "enc": _stack(enc_layer_spec(cfg), a.n_enc_layers),
        "dec": _stack(dec_layer_spec(cfg), cfg.n_layers),
        "enc_ln": layernorm_spec(d),
    }


def encdec_adapter_spec(cfg: ModelConfig) -> dict:
    return {
        "enc": _stack(sublayer_adapter_spec(cfg, "attn"), cfg.audio.n_enc_layers),
        "dec": _stack(sublayer_adapter_spec(cfg, "attn"), cfg.n_layers),
    }


def encode(params: dict, adapters: dict, frames: jax.Array, cfg: ModelConfig,
           remat: bool = False) -> jax.Array:
    """frames: (B, F, d_model) stub embeddings -> encoder states (B, F, d)."""
    F = frames.shape[1]
    x = frames + params["enc_pos"][:F][None].astype(frames.dtype)
    pos = jnp.arange(F, dtype=jnp.int32)

    def body(x, layer):
        lp, la = layer
        h, _ = attn_mod.attention_seq(lp["attn"], la, layernorm(lp["ln1"], x),
                                      cfg, positions=pos, causal=False,
                                      use_rope=False)
        x = x + h
        x = x + mlp(lp["mlp"], layernorm(lp["ln2"], x))
        return x, None

    if remat:
        body = jax.checkpoint(body, prevent_cse=False)
    x, _ = jax.lax.scan(body, x, (params["enc"], adapters.get("enc", {})))
    return layernorm(params["enc_ln"], x)


def _dec_positions(S: int):
    return jnp.arange(S, dtype=jnp.int32)


def decode_seq(params: dict, adapters: dict, tok_emb: jax.Array,
               enc_out: jax.Array, cfg: ModelConfig, *,
               make_cache: bool = False, remat: bool = False,
               cache_len=None, lengths=None):
    """Teacher-forced decoder pass. tok_emb: (B, S, d). Returns (x, caches).

    ``lengths`` (B,) marks ragged right-padded decoder prompts: the self
    cache gets per-row sentinel positions beyond each row's length (the
    cross cache is static per request and unaffected)."""
    B, S, _ = tok_emb.shape
    F = enc_out.shape[1]
    x = tok_emb + params["dec_pos"][:S][None].astype(tok_emb.dtype)
    pos = _dec_positions(S)
    enc_pos = jnp.arange(F, dtype=jnp.int32)

    def body(x, layer):
        lp, la = layer
        h, self_cache = attn_mod.attention_seq(
            lp["self"], la, layernorm(lp["ln1"], x), cfg, positions=pos,
            causal=True, use_rope=False, make_cache=make_cache,
            cache_len=cache_len, lengths=lengths)
        x = x + h
        h, _ = attn_mod.attention_seq(
            lp["cross"], None, layernorm(lp["ln2"], x), cfg, positions=pos,
            kv_x=enc_out, kv_positions=enc_pos, causal=False, use_rope=False)
        x = x + h
        x = x + mlp(lp["mlp"], layernorm(lp["ln3"], x))
        cache = None
        if make_cache:
            # cross-attention KV is static per request: cache it per layer
            # (pos is replicated per row so every cache leaf is
            # batch-addressable — the engine's in-wave refill merges caches
            # row-wise)
            from repro.models.attention import _qkv
            _, ck, cv = _qkv(lp["cross"], None, enc_out, cfg, enc_out)
            Fs = kops.decode_slots(F)        # resident head-major layout
            slots = ((0, 0), (0, 0), (0, Fs - F), (0, 0))
            cache = {"self": self_cache,
                     "cross": {"k": jnp.pad(jnp.swapaxes(ck, 1, 2), slots),
                               "v": jnp.pad(jnp.swapaxes(cv, 1, 2), slots),
                               "pos": jnp.broadcast_to(
                                   jnp.pad(enc_pos, (0, Fs - F),
                                           constant_values=10 ** 9),
                                   (B, Fs))}}
        return x, cache

    if remat:
        body = jax.checkpoint(body, prevent_cse=False)
    x, caches = jax.lax.scan(body, x, (params["dec"], adapters.get("dec", {})))
    return x, (caches if make_cache else None)


def decode_step(params: dict, adapters: dict, tok_emb: jax.Array,
                caches: dict, cfg: ModelConfig, *, pos: jax.Array,
                active=None):
    """One decoder token. tok_emb: (B, 1, d). ``pos`` scalar or (B,)."""
    B = tok_emb.shape[0]
    pos = jnp.broadcast_to(jnp.asarray(pos, jnp.int32), (B,))
    x = tok_emb + jnp.take(params["dec_pos"], pos,
                           axis=0)[:, None].astype(tok_emb.dtype)

    def body(carry, layer):             # caches resident, read in place
        x, self_cache = carry
        lp, la, idx = layer
        h, self_cache = attn_mod.attention_decode(
            lp["self"], la, layernorm(lp["ln1"], x), self_cache, cfg,
            pos=pos, use_rope=False, active=active, layer=idx)
        x = x + h
        h, _ = attn_mod.attention_decode(
            lp["cross"], None, layernorm(lp["ln2"], x), caches["cross"], cfg,
            pos=pos, cross=True, layer=idx)
        x = x + h
        x = x + mlp(lp["mlp"], layernorm(lp["ln3"], x))
        return (x, self_cache), None

    L = caches["cross"]["pos"].shape[0]
    (x, self_cache), _ = jax.lax.scan(
        body, (x, caches["self"]),
        (params["dec"], adapters.get("dec", {}),
         jnp.arange(L, dtype=jnp.int32)))
    return x, {"self": self_cache, "cross": caches["cross"]}


def encdec_cache_spec(cfg: ModelConfig, batch: int, seq_len: int) -> dict:
    L = cfg.n_layers
    F = kops.decode_slots(cfg.audio.n_audio_frames)
    return {
        "self": attn_mod.cache_spec(cfg, batch, seq_len, layers=L),
        "cross": {
            "k": ParamSpec((L, batch, cfg.n_kv_heads, F, cfg.head_dim_),
                           jnp.dtype(cfg.dtype),
                           (None, "batch", "kv_heads", "frames", "head_dim"),
                           init="zeros"),
            "v": ParamSpec((L, batch, cfg.n_kv_heads, F, cfg.head_dim_),
                           jnp.dtype(cfg.dtype),
                           (None, "batch", "kv_heads", "frames", "head_dim"),
                           init="zeros"),
            "pos": ParamSpec((L, batch, F), jnp.int32,
                             (None, "batch", "frames"), init="zeros"),
        },
    }
