"""Split-KV flash-decode Pallas kernel (TPU target).

Decode attention: ONE query token per sequence attends to a length-T KV
cache. The cache is orders of magnitude larger than the query, so the kernel
is memory-bound and its only job is to stream K/V through VMEM exactly once.

Grid: ``(B, Hkv, num_kv_chunks)`` — the KV-chunk dimension is innermost and
sequential. Each step loads one ``(block_kv, D)`` K/V chunk and folds it into
f32 online-softmax partials ``(acc, m, l)`` held in VMEM scratch that persist
across the chunk dimension (the split-KV reduction); the normalized output is
written on the last chunk. GQA is expressed in the index_maps: the
``g = Hq // Hkv`` query heads sharing one KV head are stacked into the
sublane dim of a single ``(g, D)`` q tile, so grouped queries ride along for
free instead of duplicating KV reads per query head.

Layout (what the TPU compiler accepts): the dense kernel reads the
resident layer-stacked cache ``(L, B, Hkv, T, D)`` as it lies — one KV
head's chunk is a ``(block_kv, D)`` block of the stored array, and the
layer index rides in SMEM with the per-row query positions (scalar
prefetch), so no cache-sized copy precedes the kernel. The prefix bank is
a small operand of its own, folded into the online-softmax state before the
chunk sweep. The pool is viewed as ``(n_blocks, bs, Hkv * Dp)``, so one KV
head's block is a ``(bs, Dp)`` lane-aligned column slab, with the block
table scalar-prefetched. The dense kernel's kv positions ride as a
``(B, nk, block_kv)`` plane whose whole-row block stays resident in VMEM
across the chunk sweep (each step reads its ``(1, block_kv)`` row).

Masking is position-based and length-aware (kernels/ref.py semantics):
unwritten cache slots carry the ``+1e9`` sentinel position and are never
visible — decode never reads garbage K/V even though the buffer is padded to
``max_len``; prefix-KV slots carry negative positions and are always
visible. ``q_pos`` may be per-row ``(B,)`` and ``kv_pos`` per-row ``(B, T)``
so batch slots at different sequence positions (the serving engine's
continuous-batching layout) share one kernel launch.
"""
# tracelint: kernel-op=flash_decode oracle=decode_attention
# tracelint: kernel-op=flash_decode_paged oracle=paged_decode_attention
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _kernel(layer_ref, qpos_ref, kpos_ref, q_ref, *refs, scale: float,
            causal: bool, window: int, nk: int, prefix: bool):
    if prefix:
        pk_ref, pv_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref = refs
    else:
        k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref = refs
    b, j = pl.program_id(0), pl.program_id(2)
    q = q_ref[...].astype(jnp.float32)                      # (g, D)

    @pl.when(j == 0)
    def _init():
        if prefix:               # always-visible prefix slots open the state
            s = jax.lax.dot_general(
                q, pk_ref[...].astype(jnp.float32), (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * scale   # (g, n_p)
            m = jnp.max(s, axis=-1)[:, None]
            p = jnp.exp(s - m)
            m_ref[...] = m
            l_ref[...] = jnp.sum(p, axis=-1)[:, None]
            acc_ref[...] = jax.lax.dot_general(
                p, pv_ref[...].astype(jnp.float32), (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
        else:
            acc_ref[...] = jnp.zeros_like(acc_ref)
            m_ref[...] = jnp.full_like(m_ref, NEG_INF)
            l_ref[...] = jnp.zeros_like(l_ref)

    k = k_ref[...].astype(jnp.float32)                      # (bkv, D)
    v = v_ref[...].astype(jnp.float32)

    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale

    qp = qpos_ref[b]                                        # SMEM scalar
    kpos = kpos_ref[pl.ds(j, 1), :]                         # (1, bkv)
    vis = (kpos <= qp) if causal else (kpos < 10 ** 8)     # sentinel padding
    if window and window > 0:
        vis = jnp.logical_and(vis, (qp - kpos) < window)
    vis = jnp.logical_or(vis, kpos < 0)                     # prefix slots
    s = jnp.where(vis, s, NEG_INF)

    m_prev, l_prev, acc_prev = m_ref[...], l_ref[...], acc_ref[...]
    m_cur = jnp.max(s, axis=-1)[:, None]                    # (g, 1)
    m_new = jnp.maximum(m_prev, m_cur)
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.exp(s - m_new)                                  # (g, bkv)
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


def _kv_block(T: int, block_kv: int) -> int:
    """The split-KV chunk for a cache of ``T`` slots: the whole cache when
    it fits one chunk, else the largest multiple of 8 up to ``block_kv``
    that divides ``T`` (the allocator, ``ops.decode_slots``, makes
    ``block_kv`` itself divide it), so no call pads the cache."""
    if T <= block_kv:
        return T
    for b in range(block_kv - block_kv % 8, 7, -8):
        if T % b == 0:
            return b
    raise ValueError(f"a decode cache of {T} slots has no chunk of 8 to "
                     f"{block_kv} slots that divides it: allocate it with "
                     "ops.decode_slots")


def _pad(x, axis, mult, value=0):
    n = x.shape[axis]
    p = (-n) % mult
    if p == 0:
        return x
    w = [(0, 0)] * x.ndim
    w[axis] = (0, p)
    return jnp.pad(x, w, constant_values=value)


def _paged_kernel(tbl_ref, qpos_ref, q_ref, k_ref, v_ref, o_ref,
                  acc_ref, m_ref, l_ref, *, scale: float, bs: int, nk: int):
    """Block-table split-KV step: one grid step = one POOL BLOCK.

    Identical online-softmax math to :func:`_kernel`; the only
    differences are (a) K/V arrive through the scalar-prefetched block
    table (the index_maps below gather ``pool[table[b, j]]``), and (b)
    kv positions are implicit — pool blocks have no position plane, a
    table slot ``j`` holds tokens ``[j*bs, (j+1)*bs)`` by construction,
    so visibility is purely causal against ``q_pos``. Unwritten slots
    (garbage blocks, stale data past the row's length) sit at positions
    ``> q_pos`` and mask to an exact f32 zero, which is what makes the
    paged path bit-identical to the dense kernel at ``block_kv == bs``.
    """
    b, j = pl.program_id(0), pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    q = q_ref[...].astype(jnp.float32)                      # (g, Dp)
    k = k_ref[...].astype(jnp.float32)                      # (bs, Dp)
    v = v_ref[...].astype(jnp.float32)

    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale

    qp = qpos_ref[b]                                        # SMEM scalar
    kpos = j * bs + jax.lax.broadcasted_iota(jnp.int32, (1, bs), 1)
    s = jnp.where(kpos <= qp, s, NEG_INF)                   # causal only

    m_prev, l_prev, acc_prev = m_ref[...], l_ref[...], acc_ref[...]
    m_cur = jnp.max(s, axis=-1)[:, None]                    # (g, 1)
    m_new = jnp.maximum(m_prev, m_cur)
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.exp(s - m_new)                                  # (g, bs)
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


@functools.partial(jax.jit, static_argnames=("scale", "interpret"))
def flash_decode_paged_pallas(q, k_pool, v_pool, table, *, q_pos,
                              scale: Optional[float] = None,
                              interpret: bool = False):
    """Paged flash-decode: gather KV chunks THROUGH the block table.

    q: (B, Hq, D); k_pool, v_pool: (n_blocks, bs, Hkv, D) device pool;
    table: (B, max_blocks) int32 — row b's logical token ``t`` lives at
    ``pool[table[b, t // bs], t % bs]``. One kv-chunk = one pool block:
    the table rides in as a scalar-prefetch operand so the K/V
    index_maps can dereference ``table[b, j]`` when scheduling block
    DMAs. Causal-only (full-window decode; sliding/prefix rows stay on
    the dense path). Out-of-pool table entries (the ``n_blocks``
    sentinel in unwritten slots) are clamped to block 0 — those slots
    are beyond ``q_pos`` and fully masked. Returns (B, Hq, D).
    """
    B, Hq, D = q.shape
    nb, bs, Hkv, _ = k_pool.shape
    g = Hq // Hkv
    maxb = table.shape[1]
    scale = scale if scale is not None else D ** -0.5

    with jax.named_scope("kv_cache"):
        Dp = max(128, D + (-D) % 128)
        qp4 = _pad(q.reshape(B, Hkv, g, D), 3, Dp)
        kp = _pad(k_pool, 3, Dp).reshape(nb, bs, Hkv * Dp)
        vp = _pad(v_pool, 3, Dp).reshape(nb, bs, Hkv * Dp)
        qpos = jnp.broadcast_to(jnp.asarray(q_pos, jnp.int32), (B,))
        tbl = jnp.clip(table.astype(jnp.int32), 0, nb - 1)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, Hkv, maxb),
        in_specs=[
            pl.BlockSpec((None, None, g, Dp),
                         lambda b, h, j, tbl, qpos: (b, h, 0, 0)),
            pl.BlockSpec((None, bs, Dp),
                         lambda b, h, j, tbl, qpos: (tbl[b, j], 0, h)),
            pl.BlockSpec((None, bs, Dp),
                         lambda b, h, j, tbl, qpos: (tbl[b, j], 0, h)),
        ],
        out_specs=pl.BlockSpec((None, None, g, Dp),
                               lambda b, h, j, tbl, qpos: (b, h, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((g, Dp), jnp.float32),
            pltpu.VMEM((g, 1), jnp.float32),
            pltpu.VMEM((g, 1), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        functools.partial(_paged_kernel, scale=scale, bs=bs, nk=maxb),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, Hkv, g, Dp), q.dtype),
        interpret=interpret,
        name="flash_decode_paged",
    )(tbl, qpos, qp4, kp, vp)
    return out[..., :D].reshape(B, Hq, D)


def _prefix_spec(shape):
    """One KV head's prefix slots: a bank shared by every row
    (Hkv, n_p, D) or one per row (B, Hkv, n_p, D)."""
    n_p, D = shape[-2:]
    if len(shape) == 3:
        return pl.BlockSpec((None, n_p, D),
                            lambda b, h, j, lay, qpos: (h, 0, 0))
    return pl.BlockSpec((None, None, n_p, D),
                        lambda b, h, j, lay, qpos: (b, h, 0, 0))


@functools.partial(jax.jit, static_argnames=(
    "window", "causal", "scale", "block_kv", "interpret"))
def flash_decode_pallas(q, k, v, layer, *, q_pos, kv_pos, prefix_k=None,
                        prefix_v=None, window: int = 0, causal: bool = True,
                        scale: Optional[float] = None, block_kv: int = 256,
                        interpret: bool = False):
    """Decode attention read in place from the resident cache.

    q: (B, Hq, D); k, v: the layer-stacked cache (L, B, Hkv, T, D), of
    which layer ``layer`` (a (1,) int32, scalar-prefetched into the K/V
    index_maps) is read; q_pos: () or (B,); kv_pos: (T,) or (B, T) that
    layer's slot positions; prefix_k/v: (Hkv, n_p, D) shared or
    (B, Hkv, n_p, D) per row, folded in as always-visible slots before the
    chunk sweep. Returns (B, Hq, D) in q.dtype."""
    B, Hq, D = q.shape
    _, _, Hkv, T, _ = k.shape
    g = Hq // Hkv
    scale = scale if scale is not None else D ** -0.5
    bkv = _kv_block(T, block_kv)
    nk = T // bkv
    prefix = prefix_k is not None

    with jax.named_scope("kv_cache"):
        q4 = q.reshape(B, Hkv, g, D)
        qpos = jnp.broadcast_to(jnp.asarray(q_pos, jnp.int32), (B,))
        kvpos = jnp.broadcast_to(jnp.asarray(kv_pos, jnp.int32),
                                 (B, T)).reshape(B, nk, bkv)

    kv_spec = pl.BlockSpec((None, None, None, bkv, D),
                           lambda b, h, j, lay, qpos: (lay[0], b, h, j, 0))
    in_specs = [
        pl.BlockSpec((None, nk, bkv), lambda b, h, j, lay, qpos: (b, 0, 0)),
        pl.BlockSpec((None, None, g, D),
                     lambda b, h, j, lay, qpos: (b, h, 0, 0)),
    ]
    operands = [kvpos, q4]
    if prefix:
        p_spec = _prefix_spec(prefix_k.shape)
        in_specs += [p_spec, p_spec]
        operands += [prefix_k, prefix_v]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, Hkv, nk),
        in_specs=in_specs + [kv_spec, kv_spec],
        out_specs=pl.BlockSpec((None, None, g, D),
                               lambda b, h, j, lay, qpos: (b, h, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((g, D), jnp.float32),
            pltpu.VMEM((g, 1), jnp.float32),
            pltpu.VMEM((g, 1), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        functools.partial(_kernel, scale=scale, causal=causal,
                          window=window, nk=nk, prefix=prefix),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, Hkv, g, D), q.dtype),
        interpret=interpret,
        name="flash_decode",
    )(layer, qpos, *operands, k, v)
    return out.reshape(B, Hq, D)
