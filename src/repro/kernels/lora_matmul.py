"""LoRA-fused matmul Pallas kernels (TPU target): forward + adapter backward.

The paper's parameter-efficient path makes ``y = x W + s (x A) B`` the hot
matmul of both fine-tuning and parameter-efficient inference. Fusing the
low-rank branch into the frozen-weight matmul reads ``x`` from HBM once and
keeps the rank-r intermediate entirely in VMEM scratch (r <= 64 << N), so the
branch costs no extra HBM traffic.

Forward grid: (M/bm, N/bn, K/bk) with the K dimension innermost/sequential;
f32 accumulators (bm, bn) and (bm, r) persist across K steps in VMEM scratch.

Backward (fine-tuning) only ever needs the *adapter* grads — the frozen
``dW = x^T dy`` is never formed (that would be a dense (K, N) matmul and a
dense gradient buffer per projection). ``lora_matmul_bwd_pallas`` computes

    dA = x^T (dy B^T) * s        (K, r)
    dB = (x A)^T dy * s          (r, N)

in ONE kernel: grid (M/bm,) sequential over row blocks, both rank-r
intermediates ``u = x A`` and ``g = s dy B^T`` are VMEM locals, and the two
adapter-sized outputs accumulate in their (revisited) output blocks — x and
dy are each read from HBM exactly once. ``dx`` reuses the *forward* kernel:
``dx = dy W^T + s (dy B^T) A^T`` is itself a LoRA-fused matmul with
``(W, A, B) -> (W^T, B^T, A^T)`` (see ops.py::lora_matmul's custom VJP).
"""
# tracelint: kernel-op=lora_matmul oracle=lora_matmul
# tracelint: kernel-op=lora_matmul oracle=lora_matmul_bwd
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(x_ref, w_ref, a_ref, b_ref, bias_ref, o_ref, acc_ref, u_ref, *,
            nk: int, scale: float, has_bias: bool):
    kk = pl.program_id(2)

    @pl.when(kk == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        u_ref[...] = jnp.zeros_like(u_ref)

    x = x_ref[...].astype(jnp.float32)
    acc_ref[...] += jax.lax.dot(x, w_ref[...].astype(jnp.float32),
                                preferred_element_type=jnp.float32)
    u_ref[...] += jax.lax.dot(x, a_ref[...].astype(jnp.float32),
                              preferred_element_type=jnp.float32)

    @pl.when(kk == nk - 1)
    def _done():
        y = acc_ref[...] + scale * jax.lax.dot(
            u_ref[...], b_ref[...].astype(jnp.float32),
            preferred_element_type=jnp.float32)
        if has_bias:
            y = y + bias_ref[0, :].astype(jnp.float32)[None, :]
        o_ref[...] = y.astype(o_ref.dtype)


def _pad(x, axis, mult):
    p = (-x.shape[axis]) % mult
    if p == 0:
        return x
    w = [(0, 0)] * x.ndim
    w[axis] = (0, p)
    return jnp.pad(x, w)


@functools.partial(jax.jit, static_argnames=(
    "scale", "block_m", "block_n", "block_k", "interpret"))
def lora_matmul_pallas(x, w, a, b, scale: float = 1.0,
                       bias: Optional[jax.Array] = None, *,
                       block_m: int = 256, block_n: int = 512,
                       block_k: int = 512, interpret: bool = False):
    """x: (M, K); w: (K, N); a: (K, r); b: (r, N); bias: (N,) or None."""
    M, K = x.shape
    N = w.shape[1]
    r = a.shape[1]
    bm, bn, bk = min(block_m, M), min(block_n, N), min(block_k, K)
    rp = max(r + (-r) % 128, 128)                     # lane-align the rank dim

    xp, wp = _pad(_pad(x, 0, bm), 1, bk), _pad(_pad(w, 0, bk), 1, bn)
    ap = _pad(_pad(a, 0, bk), 1, rp)
    bp = _pad(_pad(b, 0, rp), 1, bn)
    has_bias = bias is not None
    biasp = _pad((bias if has_bias else jnp.zeros((N,), x.dtype))[None, :], 1, bn)
    Mp, Kp = xp.shape
    Np = wp.shape[1]
    nm, nn, nk = Mp // bm, Np // bn, Kp // bk

    out = pl.pallas_call(
        functools.partial(_kernel, nk=nk, scale=scale, has_bias=has_bias),
        grid=(nm, nn, nk),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, k: (i, k)),
            pl.BlockSpec((bk, bn), lambda i, j, k: (k, j)),
            pl.BlockSpec((bk, rp), lambda i, j, k: (k, 0)),
            pl.BlockSpec((rp, bn), lambda i, j, k: (0, j)),
            pl.BlockSpec((1, bn), lambda i, j, k: (0, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((Mp, Np), x.dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32),
                        pltpu.VMEM((bm, rp), jnp.float32)],
        interpret=interpret,
        name="lora_matmul_fwd",
    )(xp, wp, ap, bp, biasp)
    return out[:M, :N]


# ---------------------------------------------------------------------------
# Backward: adapter grads dA, dB (never the frozen dW)
# ---------------------------------------------------------------------------

def _bwd_kernel(x_ref, dy_ref, a_ref, b_ref, da_ref, db_ref, *,
                scale: float):
    mm = pl.program_id(0)

    @pl.when(mm == 0)
    def _init():
        da_ref[...] = jnp.zeros_like(da_ref)
        db_ref[...] = jnp.zeros_like(db_ref)

    x = x_ref[...].astype(jnp.float32)                   # (bm, K)
    dy = dy_ref[...].astype(jnp.float32)                 # (bm, N)
    # rank-r intermediates never leave VMEM
    g = scale * jax.lax.dot_general(                     # s * dy @ b^T: (bm, r)
        dy, b_ref[...].astype(jnp.float32), (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)
    u = jax.lax.dot(x, a_ref[...].astype(jnp.float32),   # x @ a: (bm, r)
                    preferred_element_type=jnp.float32)
    da_ref[...] += jax.lax.dot_general(                  # x^T @ g: (K, r)
        x, g, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    db_ref[...] += scale * jax.lax.dot_general(          # s * u^T @ dy: (r, N)
        u, dy, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)


@functools.partial(jax.jit, static_argnames=("scale", "block_m", "interpret"))
def lora_matmul_bwd_pallas(x, dy, a, b, scale: float = 1.0, *,
                           block_m: int = 128, interpret: bool = False):
    """Adapter grads of the fused forward. x: (M, K); dy: (M, N);
    a: (K, r); b: (r, N). Returns (dA (K, r) f32, dB (r, N) f32).

    One sequential sweep over M row blocks; K and N stay whole per block, so
    VMEM holds bm*(K+N) activations plus the two adapter-sized outputs —
    shrink ``block_m`` for very wide projections.
    """
    M, K = x.shape
    N = dy.shape[1]
    r = a.shape[1]
    bm = min(block_m, M)
    rp = max(r + (-r) % 128, 128)                     # lane-align the rank dim
    Kp = K + (-K) % 128
    Np = N + (-N) % 128

    xp = _pad(_pad(x, 0, bm), 1, 128)
    dyp = _pad(_pad(dy, 0, bm), 1, 128)
    ap = _pad(_pad(a, 0, 128), 1, rp)
    bp = _pad(_pad(b, 0, rp), 1, 128)
    nm = xp.shape[0] // bm

    da, db = pl.pallas_call(
        functools.partial(_bwd_kernel, scale=scale),
        grid=(nm,),
        in_specs=[
            pl.BlockSpec((bm, Kp), lambda i: (i, 0)),
            pl.BlockSpec((bm, Np), lambda i: (i, 0)),
            pl.BlockSpec((Kp, rp), lambda i: (0, 0)),
            pl.BlockSpec((rp, Np), lambda i: (0, 0)),
        ],
        out_specs=[pl.BlockSpec((Kp, rp), lambda i: (0, 0)),
                   pl.BlockSpec((rp, Np), lambda i: (0, 0))],
        out_shape=[jax.ShapeDtypeStruct((Kp, rp), jnp.float32),
                   jax.ShapeDtypeStruct((rp, Np), jnp.float32)],
        interpret=interpret,
        name="lora_matmul_bwd",
    )(xp, dyp, ap, bp)
    return da[:K, :r], db[:r, :N]
