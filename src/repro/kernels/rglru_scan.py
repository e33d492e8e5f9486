"""RG-LRU recurrence Pallas kernel (TPU target).

Same chunked-sequential structure as the selective scan: channel blocks on
the VPU lanes, diagonal f32 state (1, bw) in VMEM scratch persisting across
sequence chunks. Gate nonlinearities are fused into the scan step so the HBM
traffic per token is exactly x/r/i in + h out.

Grid: (B, num_channel_blocks, num_seq_chunks), chunks innermost.
"""
# tracelint: kernel-op=rglru oracle=rglru
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(x_ref, r_ref, i_ref, a_ref, h0_ref, hs_ref, hT_ref, h_ref, *,
            cs: int, n_chunks: int, c: float):
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        h_ref[...] = h0_ref[...].astype(jnp.float32)        # (1, bw)

    a_param = a_ref[:, 0].astype(jnp.float32)               # (bw,)
    log_a = -c * jax.nn.softplus(-a_param)[None, :]         # (1, bw)

    def step(t, h):
        xt = x_ref[0, t, :].astype(jnp.float32)[None, :]
        rt = jax.nn.sigmoid(r_ref[0, t, :].astype(jnp.float32))[None, :]
        it = jax.nn.sigmoid(i_ref[0, t, :].astype(jnp.float32))[None, :]
        a_t = jnp.exp(rt * log_a)
        h = a_t * h + jnp.sqrt(jnp.maximum(1.0 - a_t * a_t, 0.0)) * (it * xt)
        hs_ref[0, t, :] = h[0].astype(hs_ref.dtype)
        return h

    h = jax.lax.fori_loop(0, cs, step, h_ref[...])
    h_ref[...] = h

    @pl.when(j == n_chunks - 1)
    def _done():
        hT_ref[...] = h


@functools.partial(jax.jit, static_argnames=("c", "chunk", "block_w", "interpret"))
def rglru_pallas(x, r_gate, i_gate, a_param, h0=None, *, c: float = 8.0,
                 chunk: int = 256, block_w: int = 512,
                 interpret: bool = False):
    """Shapes as kernels/ref.rglru. Returns (h_seq, h_final)."""
    B, S, W = x.shape
    if h0 is None:
        h0 = jnp.zeros((B, W), jnp.float32)
    cs = min(chunk, S)
    bw = min(block_w, W)
    if S % cs != 0 or W % bw != 0:
        raise ValueError(f"rglru_pallas tiling must divide the operand: "
                         f"seq {S} % chunk {cs}, width {W} % block {bw}")
    n_chunks = S // cs
    a2 = a_param[:, None]

    grid = (B, W // bw, n_chunks)
    hs, hT = pl.pallas_call(
        functools.partial(_kernel, cs=cs, n_chunks=n_chunks, c=c),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, cs, bw), lambda b, d, j: (b, j, d)),
            pl.BlockSpec((1, cs, bw), lambda b, d, j: (b, j, d)),
            pl.BlockSpec((1, cs, bw), lambda b, d, j: (b, j, d)),
            pl.BlockSpec((bw, 1), lambda b, d, j: (d, 0)),
            pl.BlockSpec((1, bw), lambda b, d, j: (b, d)),
        ],
        out_specs=[
            pl.BlockSpec((1, cs, bw), lambda b, d, j: (b, j, d)),
            pl.BlockSpec((1, bw), lambda b, d, j: (b, d)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, S, W), x.dtype),
            jax.ShapeDtypeStruct((B, W), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((1, bw), jnp.float32)],
        interpret=interpret,
        name="rglru_scan",
    )(x, r_gate, i_gate, a2, h0)
    return hs, hT
