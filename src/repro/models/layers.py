"""Common layers: norms, rotary embeddings, gated MLP, token embedding.

All modules follow the repo convention: ``<mod>_spec(cfg) -> ParamSpec tree``
and a pure ``<mod>(params, x, ...)`` apply function. Math accumulates in f32,
weights stay in the config dtype (bf16 by default).
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.sharding.rules import ParamSpec, shard


# ---------------------------------------------------------------------------
# Norms
# ---------------------------------------------------------------------------

def rmsnorm_spec(d: int) -> dict:
    return {"scale": ParamSpec((d,), jnp.float32, ("d_model",), init="ones")}


def rmsnorm(params: dict, x: jax.Array, eps: float = 1e-6) -> jax.Array:
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    out = xf * jax.lax.rsqrt(var + eps) * params["scale"]
    return out.astype(x.dtype)


def layernorm_spec(d: int) -> dict:
    return {"scale": ParamSpec((d,), jnp.float32, ("d_model",), init="ones"),
            "bias": ParamSpec((d,), jnp.float32, ("d_model",), init="zeros")}


def layernorm(params: dict, x: jax.Array, eps: float = 1e-5) -> jax.Array:
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.var(xf, axis=-1, keepdims=True)
    out = (xf - mu) * jax.lax.rsqrt(var + eps) * params["scale"] + params["bias"]
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# Rotary position embedding
# ---------------------------------------------------------------------------

def rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """Apply rotary embedding. x: (..., S, H, D); positions: broadcastable (..., S)."""
    d = x.shape[-1]
    half = d // 2
    freq = 1.0 / (theta ** (jnp.arange(0, half, dtype=jnp.float32) / half))
    ang = positions[..., None].astype(jnp.float32) * freq          # (..., S, half)
    cos = jnp.cos(ang)[..., None, :]                                # (..., S, 1, half)
    sin = jnp.sin(ang)[..., None, :]
    x1, x2 = x[..., :half], x[..., half:]
    out = jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# Gated MLP (SwiGLU)
# ---------------------------------------------------------------------------

def mlp_spec(d: int, ff: int, dtype=jnp.bfloat16) -> dict:
    return {
        "gate": ParamSpec((d, ff), dtype, ("fsdp", "d_ff"), init="scaled"),
        "up": ParamSpec((d, ff), dtype, ("fsdp", "d_ff"), init="scaled"),
        "down": ParamSpec((ff, d), dtype, ("d_ff", "fsdp"), init="scaled"),
    }


@jax.named_scope("mlp")
def mlp(params: dict, x: jax.Array) -> jax.Array:
    h = jax.nn.silu(x @ params["gate"]) * (x @ params["up"])
    h = shard(h, *(("batch",) + ("attn_seq",) * (h.ndim - 2) + ("act_ff",))[-h.ndim:])
    return h @ params["down"]


# ---------------------------------------------------------------------------
# Embedding / LM head
# ---------------------------------------------------------------------------

def embed_spec(vocab: int, d: int, dtype=jnp.bfloat16) -> dict:
    return {"table": ParamSpec((vocab, d), dtype, ("vocab", "fsdp"))}


def embed(params: dict, tokens: jax.Array) -> jax.Array:
    return jnp.take(params["table"], tokens, axis=0)


def unembed(params: dict, x: jax.Array) -> jax.Array:
    """Logits in f32 (tied or dedicated table of shape (vocab, d))."""
    return jnp.einsum("...d,vd->...v", x.astype(jnp.float32),
                      params["table"].astype(jnp.float32))


def cross_entropy(logits: jax.Array, labels: jax.Array,
                  mask: Optional[jax.Array] = None) -> jax.Array:
    lse = jax.nn.logsumexp(logits, axis=-1)
    ll = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    nll = lse - ll
    if mask is not None:
        return jnp.sum(nll * mask) / jnp.maximum(jnp.sum(mask), 1.0)
    return jnp.mean(nll)
