"""Mamba-1 selective scan Pallas kernel (TPU target).

TPU adaptation of the CUDA selective-scan: the channel dimension is tiled to
the 8x128 VPU lanes (block ``bd`` channels), the sequence is processed in
VMEM-resident chunks, and the (bd, N) state lives in f32 VMEM scratch that
persists across the sequential chunk grid dimension. All per-step math is
(bd, N)-vectorized; there is no cross-channel reduction except the final
C-contraction, which is an (bd, N) x (N,) elementwise-sum kept on the VPU
(N=16 is far below MXU utility).

Grid: (B, num_channel_blocks, num_seq_chunks) — chunks innermost/sequential.
"""
# tracelint: kernel-op=selective_scan oracle=selective_scan
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(x_ref, dt_ref, A_ref, B_ref, C_ref, D_ref, h0_ref,
            y_ref, hT_ref, h_ref, *, cs: int, n_chunks: int):
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        h_ref[...] = h0_ref[0].astype(jnp.float32)          # (bd, N)

    A = A_ref[...].astype(jnp.float32)                      # (bd, N)
    Dp = D_ref[:, 0].astype(jnp.float32)                    # (bd,)

    def step(t, h):
        xt = x_ref[0, t, :].astype(jnp.float32)             # (bd,)
        dtt = dt_ref[0, t, :].astype(jnp.float32)           # (bd,)
        bt = B_ref[0, t, :].astype(jnp.float32)             # (N,)
        ct = C_ref[0, t, :].astype(jnp.float32)             # (N,)
        dA = jnp.exp(dtt[:, None] * A)                      # (bd, N)
        h = h * dA + (dtt * xt)[:, None] * bt[None, :]
        y = jnp.sum(h * ct[None, :], axis=1) + Dp * xt      # (bd,)
        y_ref[0, t, :] = y.astype(y_ref.dtype)
        return h

    h = jax.lax.fori_loop(0, cs, step, h_ref[...])
    h_ref[...] = h

    @pl.when(j == n_chunks - 1)
    def _done():
        hT_ref[0] = h


@functools.partial(jax.jit, static_argnames=("chunk", "block_d", "interpret"))
def selective_scan_pallas(x, dt, A, Bm, C, D, h0=None, *,
                          chunk: int = 256, block_d: int = 512,
                          interpret: bool = False):
    """Shapes as kernels/ref.selective_scan. Returns (y, h_final)."""
    B, S, Di = x.shape
    N = A.shape[-1]
    if h0 is None:
        h0 = jnp.zeros((B, Di, N), jnp.float32)
    cs = min(chunk, S)
    bd = min(block_d, Di)
    if S % cs != 0 or Di % bd != 0:
        raise ValueError(f"selective_scan_pallas tiling must divide the "
                         f"operand: seq {S} % chunk {cs}, d_inner {Di} % "
                         f"block {bd}")
    n_chunks, n_db = S // cs, Di // bd
    D2 = D[:, None]

    grid = (B, n_db, n_chunks)
    y, hT = pl.pallas_call(
        functools.partial(_kernel, cs=cs, n_chunks=n_chunks),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, cs, bd), lambda b, d, j: (b, j, d)),   # x
            pl.BlockSpec((1, cs, bd), lambda b, d, j: (b, j, d)),   # dt
            pl.BlockSpec((bd, N), lambda b, d, j: (d, 0)),          # A
            pl.BlockSpec((1, cs, N), lambda b, d, j: (b, j, 0)),    # B
            pl.BlockSpec((1, cs, N), lambda b, d, j: (b, j, 0)),    # C
            pl.BlockSpec((bd, 1), lambda b, d, j: (d, 0)),          # D
            pl.BlockSpec((1, bd, N), lambda b, d, j: (b, d, 0)),    # h0
        ],
        out_specs=[
            pl.BlockSpec((1, cs, bd), lambda b, d, j: (b, j, d)),   # y
            pl.BlockSpec((1, bd, N), lambda b, d, j: (b, d, 0)),    # h_final
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, S, Di), x.dtype),
            jax.ShapeDtypeStruct((B, Di, N), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((bd, N), jnp.float32)],
        interpret=interpret,
        name="selective_scan",
    )(x, dt, A, Bm, C, D2, h0)
    return y, hT
