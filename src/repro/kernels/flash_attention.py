"""Flash attention Pallas kernel (TPU target).

TPU-native adaptation: online-softmax over KV tiles held in VMEM, MXU-aligned
(block_q x head_dim) @ (head_dim x block_kv) dots, f32 accumulators in VMEM
scratch persisting across the sequential last grid dimension. Masking is
position-based (prefix-KV slots have negative positions and are always
visible; see kernels/ref.py for the shared semantics), so the same kernel
serves causal, sliding-window, and prefix-tuned attention.

Layout: q, k, v and o are viewed as ``(B, seq, heads * Dp)`` (head_dim
padded to the 128-lane width), so one head's ``(block, Dp)`` tile is a
lane-aligned column slab of the flattened head axis. Every block then ends
in ``(multiple of 8, 128)``, which is what the TPU compiler requires; a
block of 1 on the head axis of a ``(B, S, H, D)`` array is refused.
Positions ride as a ``(Sp, 1)`` column (q) and a ``(1, Tp)`` row (kv), so
the visibility mask is a plain broadcast with no in-kernel transpose; on
TPU ``block_kv`` must be a multiple of 128 or cover all of kv.

Grid: (B, Hq, num_q_blocks, num_kv_blocks) — the kv dimension is innermost
and sequential; scratch (acc, m, l) carries across it, out is written on the
last kv step. GQA is expressed in the k/v index_maps (head h reads kv head
h // group).
"""
# tracelint: kernel-op=flash_attention oracle=attention
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _kernel(qpos_ref, kpos_ref, q_ref, k_ref, v_ref, o_ref,
            acc_ref, m_ref, l_ref, *, scale: float, causal: bool,
            window: int, nk: int):
    j = pl.program_id(3)

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    q = q_ref[...].astype(jnp.float32)                      # (bq, Dp)
    k = k_ref[...].astype(jnp.float32)                      # (bkv, Dp)
    v = v_ref[...].astype(jnp.float32)

    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale

    qpos = qpos_ref[...]                                    # (bq, 1)
    kpos = kpos_ref[...]                                    # (1, bkv)
    vis = (kpos <= qpos) if causal else (kpos < 10 ** 8)   # mask padding
    if window and window > 0:
        vis = jnp.logical_and(vis, (qpos - kpos) < window)
    vis = jnp.logical_or(vis, kpos < 0)
    s = jnp.where(vis, s, NEG_INF)

    m_prev, l_prev, acc_prev = m_ref[...], l_ref[...], acc_ref[...]
    m_cur = jnp.max(s, axis=-1)[:, None]                    # (bq, 1)
    m_new = jnp.maximum(m_prev, m_cur)
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.exp(s - m_new)                                  # (bq, bkv)
    l_new = l_prev * alpha + jnp.sum(p, axis=-1)[:, None]
    pv = jax.lax.dot_general(p, v, (((1,), (0,)), ((), ())),
                             preferred_element_type=jnp.float32)
    acc_new = acc_prev * alpha + pv

    m_ref[...] = m_new
    l_ref[...] = l_new
    acc_ref[...] = acc_new

    @pl.when(j == nk - 1)
    def _done():
        out = acc_new / jnp.maximum(l_new, 1e-30)
        o_ref[...] = out.astype(o_ref.dtype)


def _pad(x, axis, mult, value=0):
    n = x.shape[axis]
    p = (-n) % mult
    if p == 0:
        return x
    w = [(0, 0)] * x.ndim
    w[axis] = (0, p)
    return jnp.pad(x, w, constant_values=value)


@functools.partial(jax.jit, static_argnames=(
    "window", "causal", "scale", "block_q", "block_kv", "interpret"))
def flash_attention_pallas(q, k, v, *, q_pos, kv_pos, window: int = 0,
                           causal: bool = True, scale: Optional[float] = None,
                           block_q: int = 512, block_kv: int = 1024,
                           interpret: bool = False):
    B, S, Hq, D = q.shape
    _, T, Hkv, _ = k.shape
    g = Hq // Hkv
    scale = scale if scale is not None else D ** -0.5
    bq, bkv = min(block_q, S), min(block_kv, T)

    with jax.named_scope("kv_cache"):
        # Pad: seq dims to block multiples, head_dim to the 128-lane MXU
        # width, then flatten (heads, Dp) so each head is a lane-aligned
        # column slab.
        Dp = max(128, D + (-D) % 128)
        qp = _pad(_pad(q, 1, bq), 3, Dp)
        kp = _pad(_pad(k, 1, bkv), 3, Dp)
        vp = _pad(_pad(v, 1, bkv), 3, Dp)
        Sp, Tp = qp.shape[1], kp.shape[1]
        nq, nk = Sp // bq, Tp // bkv
        qp = qp.reshape(B, Sp, Hq * Dp)
        kp = kp.reshape(B, Tp, Hkv * Dp)
        vp = vp.reshape(B, Tp, Hkv * Dp)
        qpos = _pad(q_pos.astype(jnp.int32), 0, bq,
                    value=-(10 ** 9))[:, None]
        kpos = _pad(kv_pos.astype(jnp.int32), 0, bkv,
                    value=10 ** 9)[None, :]

    grid = (B, Hq, nq, nk)
    out = pl.pallas_call(
        functools.partial(_kernel, scale=scale, causal=causal,
                          window=window, nk=nk),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bq, 1), lambda b, h, i, j: (i, 0)),
            pl.BlockSpec((1, bkv), lambda b, h, i, j: (0, j)),
            pl.BlockSpec((None, bq, Dp), lambda b, h, i, j: (b, i, h)),
            pl.BlockSpec((None, bkv, Dp), lambda b, h, i, j: (b, j, h // g)),
            pl.BlockSpec((None, bkv, Dp), lambda b, h, i, j: (b, j, h // g)),
        ],
        out_specs=pl.BlockSpec((None, bq, Dp), lambda b, h, i, j: (b, i, h)),
        out_shape=jax.ShapeDtypeStruct((B, Sp, Hq * Dp), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, Dp), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
        ],
        interpret=interpret,
        name="flash_attention",
    )(qpos, kpos, qp, kp, vp)
    return out.reshape(B, Sp, Hq, Dp)[:, :S, :, :D]
