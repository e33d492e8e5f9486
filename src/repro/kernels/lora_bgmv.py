"""Batched multi-LoRA (BGMV-style) Pallas kernels (TPU target).

Multi-tenant serving (S-LoRA / Punica layout): ONE frozen weight ``w`` is
shared by every request in a batch while each request selects its own
low-rank adapter pair out of a device-resident stack ``a: (n_slots, K, r)``,
``b: (n_slots, r, N)`` via an ``adapter_id``. This is what lets one decode
wave mix requests from different domains against the AdapterBank
(core/adapter_bank.py) instead of draining the engine once per domain.

Two shapes, two kernels:

- **Rows (decode)** — ``x: (M, K)`` with one ``adapter_id`` per row (BGMV:
  batched gather matrix-vector). Gathering ``(M, K, r)`` adapter copies per
  row would blow HBM traffic, so the kernel instead sweeps the slot dim with
  *masked accumulation*: per K step, ``u += (x masked to slot s) @ a[s]`` for
  each s — rows end up with exactly ``x_i @ a[id_i]`` because the row masks
  are disjoint, and every extra term is an exact 0. The rank-r intermediate
  and the dense accumulator live in VMEM scratch across the sequential K
  grid dim, so x/w are still read from HBM exactly once (the adapter stack
  is re-read per (i, j) block — it is rank-r sized, i.e. negligible).
- **Sequence (prefill)** — ``x: (B, S, K)`` with one ``adapter_id`` per
  sequence. Here the gather is free: the adapter id is *scalar-prefetched*
  and the BlockSpec index_map picks block ``a[ids[b]]`` directly, so each
  sequence's grid rows DMA only its own adapter (the gathered path).

Both produce bit-identical per-row results to the single-LoRA kernel run
with that row's adapter (the mixed-domain == per-domain serving parity the
engine tests assert). Dispatched from ops.py::lora_bgmv behind the usual
``xla|pallas|interpret`` switch. Block sizes follow lora_matmul.py; both
kernels compile for v5e at qwen2-7b widths (tests/test_tpu_compile.py), but
no block size has been tuned on the chip.
"""
# tracelint: kernel-op=lora_bgmv oracle=lora_bgmv
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _pad(x, axis, mult):
    p = (-x.shape[axis]) % mult
    if p == 0:
        return x
    w = [(0, 0)] * x.ndim
    w[axis] = (0, p)
    return jnp.pad(x, w)


# ---------------------------------------------------------------------------
# Rows variant (decode shape): one adapter_id per row, masked accumulation
# ---------------------------------------------------------------------------

def _rows_kernel(ids_ref, x_ref, w_ref, a_ref, b_ref, bias_ref, o_ref,
                 acc_ref, u_ref, *, nk: int, n_slots: int, scale: float,
                 has_bias: bool):
    kk = pl.program_id(2)

    @pl.when(kk == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        u_ref[...] = jnp.zeros_like(u_ref)

    x = x_ref[...].astype(jnp.float32)                     # (bm, bk)
    ids = ids_ref[...]                                     # (bm, 1) int32
    acc_ref[...] += jax.lax.dot(x, w_ref[...].astype(jnp.float32),
                                preferred_element_type=jnp.float32)
    for s in range(n_slots):                               # static, unrolled
        xs = jnp.where(ids == s, x, 0.0)
        u_ref[...] += jax.lax.dot(xs, a_ref[s].astype(jnp.float32),
                                  preferred_element_type=jnp.float32)

    @pl.when(kk == nk - 1)
    def _done():
        y = acc_ref[...]
        u = u_ref[...]                                     # (bm, rp): x_i @ a[id_i]
        for s in range(n_slots):
            us = jnp.where(ids == s, u, 0.0)
            y = y + scale * jax.lax.dot(us, b_ref[s].astype(jnp.float32),
                                        preferred_element_type=jnp.float32)
        if has_bias:
            y = y + bias_ref[0, :].astype(jnp.float32)[None, :]
        o_ref[...] = y.astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=(
    "scale", "block_m", "block_n", "block_k", "interpret"))
def lora_bgmv_rows_pallas(x, w, a, b, adapter_ids, scale: float = 1.0,
                          bias: Optional[jax.Array] = None, *,
                          block_m: int = 256, block_n: int = 512,
                          block_k: int = 512, interpret: bool = False):
    """x: (M, K); w: (K, N); a: (n_slots, K, r); b: (n_slots, r, N);
    adapter_ids: (M,) int32 in [0, n_slots). Returns (M, N) in x.dtype."""
    M, K = x.shape
    N = w.shape[1]
    n_slots, _, r = a.shape
    bm, bn, bk = min(block_m, M), min(block_n, N), min(block_k, K)
    rp = max(r + (-r) % 128, 128)                     # lane-align the rank dim

    xp, wp = _pad(_pad(x, 0, bm), 1, bk), _pad(_pad(w, 0, bk), 1, bn)
    ap = _pad(_pad(a, 1, bk), 2, rp)
    bp = _pad(_pad(b, 1, rp), 2, bn)
    idsp = _pad(adapter_ids.astype(jnp.int32)[:, None], 0, bm)
    has_bias = bias is not None
    biasp = _pad((bias if has_bias else jnp.zeros((N,), x.dtype))[None, :],
                 1, bn)
    Mp, Kp = xp.shape
    Np = wp.shape[1]
    nm, nn, nk = Mp // bm, Np // bn, Kp // bk

    out = pl.pallas_call(
        functools.partial(_rows_kernel, nk=nk, n_slots=n_slots, scale=scale,
                          has_bias=has_bias),
        grid=(nm, nn, nk),
        in_specs=[
            pl.BlockSpec((bm, 1), lambda i, j, k: (i, 0)),
            pl.BlockSpec((bm, bk), lambda i, j, k: (i, k)),
            pl.BlockSpec((bk, bn), lambda i, j, k: (k, j)),
            pl.BlockSpec((n_slots, bk, rp), lambda i, j, k: (0, k, 0)),
            pl.BlockSpec((n_slots, rp, bn), lambda i, j, k: (0, 0, j)),
            pl.BlockSpec((1, bn), lambda i, j, k: (0, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((Mp, Np), x.dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32),
                        pltpu.VMEM((bm, rp), jnp.float32)],
        interpret=interpret,
        name="lora_bgmv_rows",
    )(idsp, xp, wp, ap, bp, biasp)
    return out[:M, :N]


# ---------------------------------------------------------------------------
# Sequence variant (prefill shape): scalar-prefetched adapter gather
# ---------------------------------------------------------------------------

def _seq_kernel(ids_ref, x_ref, w_ref, a_ref, b_ref, bias_ref, o_ref,
                acc_ref, u_ref, *, nk: int, scale: float, has_bias: bool):
    # ids_ref was consumed by the index_maps; the a/b blocks arriving here
    # are already THIS sequence's adapter pair.
    kk = pl.program_id(3)

    @pl.when(kk == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        u_ref[...] = jnp.zeros_like(u_ref)

    x = x_ref[...].astype(jnp.float32)                      # (bs, bk)
    acc_ref[...] += jax.lax.dot(x, w_ref[...].astype(jnp.float32),
                                preferred_element_type=jnp.float32)
    u_ref[...] += jax.lax.dot(x, a_ref[0].astype(jnp.float32),
                              preferred_element_type=jnp.float32)

    @pl.when(kk == nk - 1)
    def _done():
        y = acc_ref[...] + scale * jax.lax.dot(
            u_ref[...], b_ref[0].astype(jnp.float32),
            preferred_element_type=jnp.float32)
        if has_bias:
            y = y + bias_ref[0, :].astype(jnp.float32)[None, :]
        o_ref[...] = y.astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=(
    "scale", "block_s", "block_n", "block_k", "interpret"))
def lora_bgmv_seq_pallas(x, w, a, b, adapter_ids, scale: float = 1.0,
                         bias: Optional[jax.Array] = None, *,
                         block_s: int = 512, block_n: int = 512,
                         block_k: int = 512, interpret: bool = False):
    """x: (B, S, K); w: (K, N); a: (n_slots, K, r); b: (n_slots, r, N);
    adapter_ids: (B,) int32. Returns (B, S, N) in x.dtype.

    The sequence is tiled into ``block_s`` row blocks (a grid dim), so the
    VMEM footprint is bounded by the tile sizes, not by the prompt length.
    """
    B, S, K = x.shape
    N = w.shape[1]
    n_slots, _, r = a.shape
    bn, bk = min(block_n, N), min(block_k, K)
    bs = min(block_s, S + (-S) % 8)
    rp = max(r + (-r) % 128, 128)

    xp = _pad(_pad(x, 1, bs), 2, bk)
    wp = _pad(_pad(w, 0, bk), 1, bn)
    ap = _pad(_pad(a, 1, bk), 2, rp)
    bp = _pad(_pad(b, 1, rp), 2, bn)
    has_bias = bias is not None
    biasp = _pad((bias if has_bias else jnp.zeros((N,), x.dtype))[None, :],
                 1, bn)
    Sp, Kp = xp.shape[1], xp.shape[2]
    Np = wp.shape[1]
    ns, nn, nk = Sp // bs, Np // bn, Kp // bk

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(B, ns, nn, nk),
        in_specs=[
            pl.BlockSpec((None, bs, bk),
                         lambda bi, si, j, k, ids: (bi, si, k)),
            pl.BlockSpec((bk, bn), lambda bi, si, j, k, ids: (k, j)),
            pl.BlockSpec((1, bk, rp),
                         lambda bi, si, j, k, ids: (ids[bi], k, 0)),
            pl.BlockSpec((1, rp, bn),
                         lambda bi, si, j, k, ids: (ids[bi], 0, j)),
            pl.BlockSpec((1, bn), lambda bi, si, j, k, ids: (0, j)),
        ],
        out_specs=pl.BlockSpec((None, bs, bn),
                               lambda bi, si, j, k, ids: (bi, si, j)),
        scratch_shapes=[pltpu.VMEM((bs, bn), jnp.float32),
                        pltpu.VMEM((bs, rp), jnp.float32)],
    )
    out = pl.pallas_call(
        functools.partial(_seq_kernel, nk=nk, scale=scale, has_bias=has_bias),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, Sp, Np), x.dtype),
        interpret=interpret,
        name="lora_bgmv_seq",
    )(adapter_ids.astype(jnp.int32), xp, wp, ap, bp, biasp)
    return out[:, :S, :N]
