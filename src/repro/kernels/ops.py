"""Kernel dispatch layer.

Every hot-spot op has three interchangeable implementations:

- ``xla``       — pure-jnp *blocked* algorithm (same tiling/online-softmax
                  structure as the Pallas kernel). This is what the 512-way
                  CPU dry-run lowers, so the roofline reflects the intended
                  kernel structure (Mosaic only lowers on real TPUs).
- ``pallas``    — the TPU-target ``pl.pallas_call`` kernel. Selecting it
                  on any platform but ``tpu`` raises: there is no quiet
                  fallback.
- ``interpret`` — the same Pallas kernel with ``interpret=True`` (CPU
                  correctness path used by tests).

Unless :func:`set_backend` (or the :func:`backend` context) chose one, the
backend follows the platform: ``pallas`` on TPU, ``xla`` elsewhere — so
every entry point (serve, train, the engine, the integrated runtime) runs
the kernels on the chip without opting in. A per-call ``backend=``
overrides both. The choice is read while a jitted function traces.
"""
from __future__ import annotations

import contextlib
import math
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels import ref

_BACKENDS = ("xla", "pallas", "interpret")
_BACKEND: Optional[str] = None         # None: follow the platform
NEG_INF = -1e30
_FLASH_BQ, _FLASH_BKV = 512, 1024     # default tiles; perf knob below


def set_flash_blocks(bq: int, bkv: int) -> None:
    """Perf knob (EXPERIMENTS.md §Perf): flash attention tile sizes."""
    global _FLASH_BQ, _FLASH_BKV
    _FLASH_BQ, _FLASH_BKV = bq, bkv


def _check(name: str) -> str:
    if name not in _BACKENDS:
        raise ValueError(f"unknown kernel backend {name!r}: expected "
                         "'xla', 'pallas', or 'interpret'")
    if name == "pallas" and jax.default_backend() != "tpu":
        raise ValueError(
            f"kernel backend 'pallas' needs a TPU, but the platform is "
            f"{jax.default_backend()!r} (use 'interpret' to run the Pallas "
            "kernels on CPU, or 'xla')")
    return name


def set_backend(name: Optional[str]) -> None:
    """Pin the kernel backend; ``None`` returns to following the platform."""
    global _BACKEND
    _BACKEND = None if name is None else _check(name)


def get_backend() -> str:
    """The backend a dispatch traced now would use."""
    if _BACKEND is not None:
        return _BACKEND
    return "pallas" if jax.default_backend() == "tpu" else "xla"


@contextlib.contextmanager
def backend(name: str):
    prev = _BACKEND
    set_backend(name)
    try:
        yield
    finally:
        set_backend(prev)


def _pick(b: Optional[str], *, tpu_kernel: bool = True) -> str:
    """Resolve a call's backend. ``tpu_kernel=False`` marks an op whose
    Pallas kernel the TPU compiler still refuses (the scans): following
    the platform, it stays on ``xla``; an explicit choice is honoured."""
    if b:
        return _check(b)
    impl = get_backend()
    if impl == "pallas" and not tpu_kernel and _BACKEND is None:
        return "xla"
    return impl


def _on_mesh(name, kernel, args, in_axes, out_axes, psum=()):
    """Call the Pallas kernel ``name`` inside the active sharding context.

    XLA cannot partition a Mosaic kernel, so under a mesh (a
    ``rules.use_rules`` context: the engine's mesh waves, the mesh HFSL
    round) the call runs in a ``shard_map`` and each device runs the kernel
    on its own block. ``in_axes`` names each operand's dims: ``"batch"``
    for rows or sequences, ``"heads"`` for the tensor-parallel dim
    (attention heads; a projection's output or contracted columns), None
    for a dim every device holds whole. A name takes the mesh axes the
    active rules give it, unless a dim it names does not divide by them;
    then those dims are whole everywhere. ``out_axes`` names the result's
    dims, and ``psum`` the split dims the kernel contracts over, whose
    partial results are summed (a list of each for several results).
    Without a mesh this is a plain call.

    Each call under a mesh counts, as it is traced, in the process-wide
    telemetry: ``kernels.mesh_calls.<name>``, and ``kernels.unsplit.<name>``
    when a named dim did not divide and the kernel ran whole over that
    name's mesh axes.
    """
    from repro.sharding.rules import active_rules
    mesh, rules = active_rules()
    if mesh is None:
        return kernel(*args)
    from jax.sharding import PartitionSpec as P

    from repro.core import telemetry
    taken, split, whole = set(), {}, False
    for dim in dict.fromkeys(n for ax in in_axes for n in ax if n):
        tgt = rules.get(dim)
        tgt = () if tgt is None else (tgt,) if isinstance(tgt, str) else tgt
        tgt = tuple(a for a in tgt if a in mesh.axis_names and a not in taken)
        k = math.prod(mesh.shape[a] for a in tgt)
        if all(x.shape[i] % k == 0 for ax, x in zip(in_axes, args)
               for i, n in enumerate(ax) if n == dim):
            split[dim] = tgt
            taken.update(tgt)
        else:
            whole = True
    tel = telemetry.get()
    tel.count(f"kernels.mesh_calls.{name}")
    if whole:
        tel.count(f"kernels.unsplit.{name}")

    def spec(ax):
        return P(*(split.get(n) or None for n in ax))

    several = isinstance(out_axes, list)
    outs = out_axes if several else [out_axes]
    sums = psum if several else [psum]
    red = [tuple(a for n in names for a in split.get(n, ())) for names in sums]

    def body(*a):
        ys = kernel(*a)
        ys = [jax.lax.psum(y, r) if r else y
              for y, r in zip(ys if several else [ys], red)]
        return tuple(ys) if several else ys[0]

    return jax.shard_map(
        body, mesh=mesh, in_specs=tuple(spec(ax) for ax in in_axes),
        out_specs=tuple(spec(ax) for ax in outs) if several else spec(outs[0]),
        check_vma=False)(*args)


# ---------------------------------------------------------------------------
# Flash attention (GQA + prefix-KV + sliding window, position-based masking)
# ---------------------------------------------------------------------------

def _pad_to(x: jax.Array, axis: int, mult: int, value=0.0) -> jax.Array:
    n = x.shape[axis]
    pad = (-n) % mult
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths, constant_values=value)


def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    q_pos: jax.Array, kv_pos: jax.Array,
                    window: int = 0, causal: bool = True,
                    scale: Optional[float] = None,
                    block_q: Optional[int] = None,
                    block_kv: Optional[int] = None,
                    backend: Optional[str] = None) -> jax.Array:
    """Blocked online-softmax attention. Shapes as in :func:`ref.attention`."""
    block_q = block_q or _FLASH_BQ
    block_kv = block_kv or _FLASH_BKV
    impl = _pick(backend)
    if impl in ("pallas", "interpret"):
        return _flash_kernel(impl, window, causal, scale, block_q, block_kv,
                             q, k, v, q_pos, kv_pos)
    return _flash_xla(q, k, v, q_pos=q_pos, kv_pos=kv_pos, window=window,
                      causal=causal, scale=scale, block_q=block_q,
                      block_kv=block_kv)


@partial(jax.custom_vjp, nondiff_argnums=(0, 1, 2, 3, 4, 5))
def _flash_kernel(impl, window, causal, scale, block_q, block_kv,
                  q, k, v, q_pos, kv_pos):
    """Forward on the Pallas kernel; the backward (training through
    attention) differentiates the blocked XLA algorithm — the same
    semantics, recomputed — since the kernel has no backward of its own."""
    from repro.kernels import flash_attention as fk
    qp, kp = jnp.asarray(q_pos, jnp.int32), jnp.asarray(kv_pos, jnp.int32)
    bshd = ("batch", None, "heads", None)
    return _on_mesh(
        "flash_attention",
        lambda q, k, v, qp, kp: fk.flash_attention_pallas(
            q, k, v, q_pos=qp, kv_pos=kp, window=window, causal=causal,
            scale=scale, block_q=block_q, block_kv=block_kv,
            interpret=(impl == "interpret")),
        (q, k, v, qp, kp),
        (bshd, bshd, bshd, (None,) * qp.ndim, (None,) * kp.ndim), bshd)


def _flash_kernel_fwd(impl, window, causal, scale, block_q, block_kv,
                      q, k, v, q_pos, kv_pos):
    out = _flash_kernel(impl, window, causal, scale, block_q, block_kv,
                        q, k, v, q_pos, kv_pos)
    return out, (q, k, v, q_pos, kv_pos)


def _flash_kernel_bwd(impl, window, causal, scale, block_q, block_kv,
                      res, dout):
    q, k, v, q_pos, kv_pos = res
    _, vjp = jax.vjp(
        lambda q_, k_, v_: _flash_xla(
            q_, k_, v_, q_pos=q_pos, kv_pos=kv_pos, window=window,
            causal=causal, scale=scale, block_q=block_q, block_kv=block_kv),
        q, k, v)
    return (*vjp(dout), None, None)


_flash_kernel.defvjp(_flash_kernel_fwd, _flash_kernel_bwd)


def _flash_xla(q, k, v, *, q_pos, kv_pos, window, causal, scale,
               block_q, block_kv):
    """Blocked online-softmax attention, head-flat layout.

    GQA KV heads are repeated up to the full head count before blocking so
    every block tensor carries one `heads` dim — under tensor parallelism
    each device then holds exactly its heads' K/V slice (the standard TP
    layout; without this GSPMD invents pathological shardings for the
    (Hkv, group) split dims). Explicit constraints keep the scan carry
    head-sharded.
    """
    from repro.sharding.rules import shard

    B, S, Hq, D = q.shape
    _, T, Hkv, _ = k.shape
    g = Hq // Hkv
    scale = scale if scale is not None else D ** -0.5
    bq, bkv = min(block_q, S), min(block_kv, T)

    if g > 1:                                  # head-flat GQA (TP layout)
        k = jnp.repeat(k, g, axis=2)
        v = jnp.repeat(v, g, axis=2)
    k = shard(k, "batch", "attn_seq", "heads", "head_dim")
    v = shard(v, "batch", "attn_seq", "heads", "head_dim")

    qp = _pad_to(q, 1, bq)
    q_posp = _pad_to(q_pos, 0, bq, value=-(10 ** 9))      # padded q rows see nothing
    kp = _pad_to(k, 1, bkv)
    vp = _pad_to(v, 1, bkv)
    kv_posp = _pad_to(kv_pos, 0, bkv, value=10 ** 9)      # padded kv never visible
    Sp, Tp = qp.shape[1], kp.shape[1]
    nq, nk = Sp // bq, Tp // bkv

    qb = qp.reshape(B, nq, bq, Hq, D).astype(jnp.float32)
    qb = shard(qb, "batch", None, None, "heads", "head_dim")
    qpb = q_posp.reshape(nq, bq)
    kb = jnp.moveaxis(kp.reshape(B, nk, bkv, Hq, D), 1, 0)
    vb = jnp.moveaxis(vp.reshape(B, nk, bkv, Hq, D), 1, 0)
    kvb = kv_posp.reshape(nk, bkv)

    def blk_step(qi, qpi, carry, blk):
        """One (q block, kv block) online-softmax update."""
        acc, m, l = carry
        kj, vj, kvp = blk
        kj = shard(kj, "batch", None, "heads", "head_dim")
        vj = shard(vj, "batch", None, "heads", "head_dim")
        s = jnp.einsum("bsnd,btnd->bnst", qi, kj.astype(jnp.float32)) * scale
        qpos = qpi[None, None, :, None]
        kpos = kvp[None, None, None, :]
        vis = (kpos <= qpos) if causal else (kpos < 10 ** 8)  # mask padding
        if window and window > 0:
            vis = vis & ((qpos - kpos) < window)
        vis = vis | (kpos < 0)
        s = jnp.where(vis, s, NEG_INF)
        s = shard(s, "batch", "heads", None, None)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        alpha = jnp.exp(m - m_new)
        p = jnp.exp(s - m_new[..., None])
        l_new = l * alpha + jnp.sum(p, axis=-1)
        acc_new = acc * alpha[..., None] + jnp.einsum(
            "bnst,btnd->bnsd", p, vj.astype(jnp.float32))
        acc_new = shard(acc_new, "batch", "heads", None, "head_dim")
        return acc_new, m_new, l_new

    # Block pruning (EXPERIMENTS.md §Perf iter q2): a causal q block only
    # touches kv blocks covering positions <= its last row; a sliding-window
    # block additionally skips blocks older than the window. Prefix slots
    # occupy the first ceil(n_p/bkv) blocks and are never pruned. This cuts
    # score traffic/FLOPs ~2x for causal training and ~S/window for long
    # sliding prefill versus the dense nq x nk sweep.
    # static prefix length from shapes: kv rows = n_prefix + S for
    # (prefix-tuned) self-attention; cross-attention is non-causal.
    n_prefix = max(T - S, 0) if causal else 0

    outs = []
    for i in range(nq):
        qi = qb[:, i]
        qpi = qpb[i]
        if causal:
            hi = n_prefix + min((i + 1) * bq, Sp)          # last visible kv row
            j_hi = min((hi + bkv - 1) // bkv, nk)
            j_lo = 0
            if window and window > 0:
                lo = n_prefix + max(i * bq - window + 1, 0)
                j_lo = max(lo // bkv, 0)
        else:
            j_lo, j_hi = 0, nk
        acc = jnp.zeros((B, Hq, bq, D), jnp.float32)
        m = jnp.full((B, Hq, bq), NEG_INF, jnp.float32)
        l = jnp.zeros((B, Hq, bq), jnp.float32)
        acc = shard(acc, "batch", "heads", None, "head_dim")
        if causal and window and window > 0 and j_lo > 0 and n_prefix > 0:
            # prefix blocks are below j_lo but always visible: visit block 0..
            pre_hi = (n_prefix + bkv - 1) // bkv
            for j in range(0, min(pre_hi, j_lo)):
                acc, m, l = blk_step(qi, qpi, (acc, m, l),
                                     (kb[j], vb[j], kvb[j]))
        if j_hi > j_lo:
            (acc, m, l), _ = jax.lax.scan(
                lambda c, blk: (blk_step(qi, qpi, c, blk), None),
                (acc, m, l), (kb[j_lo:j_hi], vb[j_lo:j_hi], kvb[j_lo:j_hi]))
        out_i = acc / jnp.maximum(l[..., None], 1e-30)     # (B, Hq, bq, D)
        outs.append(out_i.transpose(0, 2, 1, 3))           # (B, bq, Hq, D)
    out = jnp.concatenate(outs, axis=1)
    return out[:, :S].astype(q.dtype)


# ---------------------------------------------------------------------------
# Flash decode (single-token attention against a padded KV cache)
# ---------------------------------------------------------------------------

_DECODE_BKV = 256                      # default split-KV chunk; perf knob


def set_decode_block(bkv: int) -> None:
    """Perf knob: flash-decode KV chunk size."""
    global _DECODE_BKV
    _DECODE_BKV = bkv


def decode_slots(n: int) -> int:
    """Slots to allocate for a decode cache that holds ``n`` positions:
    ``n`` when it fits one split-KV chunk, else ``n`` rounded up to a
    multiple of the chunk. The extra slots keep the ``+1e9`` sentinel, so
    the kernel reads the cache as it lies and no call pads it."""
    b = _DECODE_BKV
    return n if n <= b else -(-n // b) * b


def flash_decode(q: jax.Array, k: jax.Array, v: jax.Array, *,
                 q_pos: jax.Array, kv_pos: jax.Array,
                 layer: Optional[jax.Array] = None,
                 prefix_k: Optional[jax.Array] = None,
                 prefix_v: Optional[jax.Array] = None,
                 window: int = 0, causal: bool = True,
                 scale: Optional[float] = None,
                 block_kv: Optional[int] = None,
                 backend: Optional[str] = None) -> jax.Array:
    """One decode token per sequence against the resident KV cache.

    q: (B, Hq, D); k, v: one layer's cache (B, Hkv, T, D), or with
    ``layer`` (a scalar index) the layer-stacked cache (L, B, Hkv, T, D)
    read in place; q_pos: scalar or (B,); kv_pos: (T,), (B, T) or, with
    ``layer``, the stacked (L, B, T) slot positions (``+1e9`` sentinel
    marks unwritten slots — length-aware masking keeps them invisible).
    prefix_k/v: (n_p, Hkv, D) or (B, n_p, Hkv, D) always-visible learned
    slots (prefix-KV prompts; position < 0 in the shared semantics).
    Returns (B, Hq, D) in q.dtype.
    """
    block_kv = block_kv or _DECODE_BKV
    impl = _pick(backend)
    if layer is None:
        k, v, layer = k[None], v[None], 0
    kv_pos = jnp.asarray(kv_pos, jnp.int32)
    if kv_pos.ndim == 3:
        with jax.named_scope("kv_cache"):
            kv_pos = kv_pos[layer]
    if impl in ("pallas", "interpret"):
        from repro.kernels import flash_decode as fdk
        B, T = k.shape[1], k.shape[3]
        pk = pv = None
        with jax.named_scope("kv_cache"):
            qp = jnp.broadcast_to(jnp.asarray(q_pos, jnp.int32), (B,))
            kp = jnp.broadcast_to(kv_pos, (B, T))
            lay = jnp.asarray(layer, jnp.int32).reshape(1)
            if prefix_k is not None:       # (.., n_p, Hkv, D) -> head-major
                pk = jnp.swapaxes(prefix_k, -3, -2).astype(k.dtype)
                pv = jnp.swapaxes(prefix_v, -3, -2).astype(v.dtype)
        bhd = ("batch", "heads", None)
        cache = (None, "batch", "heads", None, None)
        args = [q, k, v, lay, qp, kp]
        axes = [bhd, cache, cache, (None,), ("batch",), ("batch", None)]
        if pk is not None:
            pax = ("heads", None, None) if pk.ndim == 3 \
                else ("batch", "heads", None, None)
            args += [pk, pv]
            axes += [pax, pax]

        def kernel(q, k, v, lay, qp, kp, pk=None, pv=None):
            return fdk.flash_decode_pallas(
                q, k, v, lay, q_pos=qp, kv_pos=kp, prefix_k=pk, prefix_v=pv,
                window=window, causal=causal, scale=scale,
                block_kv=block_kv, interpret=(impl == "interpret"))
        return _on_mesh("flash_decode", kernel, tuple(args), tuple(axes),
                        bhd)
    return _flash_decode_xla(q, k[layer], v[layer], q_pos=q_pos,
                             kv_pos=kv_pos, prefix_k=prefix_k,
                             prefix_v=prefix_v, window=window, causal=causal,
                             scale=scale)


def _broadcast_prefix(prefix_k, prefix_v, B):
    if prefix_k.ndim == 3:                       # (n_p, Hkv, D) -> batched
        prefix_k = jnp.broadcast_to(prefix_k[None], (B, *prefix_k.shape))
        prefix_v = jnp.broadcast_to(prefix_v[None], (B, *prefix_v.shape))
    return prefix_k, prefix_v


def _flash_decode_xla(q, k, v, *, q_pos, kv_pos, prefix_k, prefix_v,
                      window, causal, scale):
    """Decode attention in XLA: native-dtype dots with f32 accumulation.
    k, v: one layer's resident cache (B, Hkv, T, D).

    Prefix-KV slots are attended SEPARATELY and merged with an
    online-softmax combine (EXPERIMENTS.md §Perf d2): concatenating n_p
    slots onto the seq-sharded cache misaligns its tiling and makes GSPMD
    all-gather the whole cache every layer (measured: the dominant decode
    traffic).
    """
    from repro.sharding.rules import shard

    B, Hq, D = q.shape
    Hkv, T = k.shape[1], k.shape[2]
    g = Hq // Hkv
    scale = scale if scale is not None else D ** -0.5
    qf = q.reshape(B, Hkv, g, D)
    qp = jnp.broadcast_to(jnp.asarray(q_pos, jnp.int32), (B,))
    kp = jnp.broadcast_to(jnp.asarray(kv_pos, jnp.int32), (B, T))
    k = shard(k, "batch", "kv_heads", "kv_seq", "head_dim")
    v = shard(v, "batch", "kv_heads", "kv_seq", "head_dim")

    def scores(kk, prefix: bool):
        """Masked scores against one KV bank (casting the cache to f32
        before the dot doubles HBM traffic — keep native dtype)."""
        s = jnp.einsum("bngd,bntd->bngt", qf, kk.astype(qf.dtype),
                       preferred_element_type=jnp.float32) * scale
        if prefix:
            return s                              # always fully visible
        vis = (kp <= qp[:, None]) if causal else (kp < 10 ** 8)
        if window and window > 0:
            vis = vis & ((qp[:, None] - kp) < window)
        vis = vis | (kp < 0)
        return jnp.where(vis[:, None, None, :], s, NEG_INF)

    def pv(p, vv):
        return jnp.einsum("bngt,bntd->bngd", p.astype(vv.dtype), vv,
                          preferred_element_type=jnp.float32)

    s_main = scores(k, prefix=False)              # (B, Hkv, g, T)
    if prefix_k is not None:
        pk, pvv = (jnp.swapaxes(x, 1, 2)          # head-major (B, Hkv, n_p, D)
                   for x in _broadcast_prefix(prefix_k, prefix_v, B))
        s_pfx = scores(pk, prefix=True)           # (B, Hkv, g, n_p)
        m = jnp.maximum(jnp.max(s_main, -1), jnp.max(s_pfx, -1))
        e_main = jnp.exp(s_main - m[..., None])
        e_pfx = jnp.exp(s_pfx - m[..., None])
        l = jnp.sum(e_main, -1) + jnp.sum(e_pfx, -1)    # (B, Hkv, g)
        denom = jnp.maximum(l, 1e-30)[..., None]
        o = (pv(e_main, v) + pv(e_pfx, pvv.astype(v.dtype))) / denom
    else:
        p = jax.nn.softmax(s_main, axis=-1)
        o = pv(p, v)
    return o.reshape(B, Hq, D).astype(q.dtype)


def flash_decode_paged(q: jax.Array, k_pool: jax.Array, v_pool: jax.Array,
                       table: jax.Array, *, q_pos: jax.Array,
                       prefix_k: Optional[jax.Array] = None,
                       prefix_v: Optional[jax.Array] = None,
                       scale: Optional[float] = None,
                       backend: Optional[str] = None) -> jax.Array:
    """One decode token per sequence against a PAGED block-pool cache.

    q: (B, Hq, D); k_pool, v_pool: (n_blocks, bs, Hkv, D); table:
    (B, max_blocks) int32 block table — row b's logical token ``t``
    lives at ``pool[table[b, t // bs], t % bs]``, so kv positions are
    implicit slot indices (causal-only; sliding-window layers stay on
    the dense rolling buffer). On pallas|interpret without a prefix
    bank the block table is dereferenced inside the kernel's index_maps
    (scalar prefetch, one kv-chunk = one block); the xla path and the
    prefix-bank fallback gather ``pool[table]`` into the dense layout
    and reuse :func:`_flash_decode_xla` / the dense kernel — which is
    exactly what makes paged drains bit-identical to dense ones (same
    visible values, masked slots contribute an exact f32 zero either
    way). Returns (B, Hq, D) in q.dtype.
    """
    impl = _pick(backend)
    nb, bs, Hkv, D = k_pool.shape
    B, maxb = table.shape
    if impl in ("pallas", "interpret") and prefix_k is None:
        from repro.kernels import flash_decode as fdk
        qp = jnp.broadcast_to(jnp.asarray(q_pos, jnp.int32), (B,))
        bhd, pool = ("batch", "heads", None), (None, None, "heads", None)
        return _on_mesh(                 # any row may name any pool block
            "flash_decode_paged",
            lambda q, kp, vp, t, qp: fdk.flash_decode_paged_pallas(
                q, kp, vp, t, q_pos=qp, scale=scale,
                interpret=(impl == "interpret")),
            (q, k_pool, v_pool, table, qp),
            (bhd, pool, pool, ("batch", None), ("batch",)), bhd)
    with jax.named_scope("kv_cache"):        # gathered into the dense layout
        tbl = jnp.clip(table.astype(jnp.int32), 0, nb - 1)
        k = jnp.swapaxes(k_pool[tbl].reshape(B, maxb * bs, Hkv, D), 1, 2)
        v = jnp.swapaxes(v_pool[tbl].reshape(B, maxb * bs, Hkv, D), 1, 2)
    kv_pos = jnp.arange(maxb * bs, dtype=jnp.int32)
    if impl in ("pallas", "interpret"):           # prefix bank: dense kernel
        return flash_decode(q, k, v, q_pos=q_pos, kv_pos=kv_pos,
                            prefix_k=prefix_k, prefix_v=prefix_v,
                            window=0, causal=True, scale=scale, backend=impl)
    return _flash_decode_xla(q, k, v, q_pos=q_pos, kv_pos=kv_pos,
                             prefix_k=prefix_k, prefix_v=prefix_v,
                             window=0, causal=True, scale=scale)


# ---------------------------------------------------------------------------
# Selective scan (Mamba-1)
# ---------------------------------------------------------------------------

_SSM_XLA_IMPL = "assoc"     # "step" (naive scan) | "assoc" (chunked parallel)


def set_ssm_xla_impl(name: str) -> None:
    """Perf knob (EXPERIMENTS.md §Perf): XLA selective-scan algorithm."""
    global _SSM_XLA_IMPL
    if name not in ("step", "assoc"):
        raise ValueError(f"unknown selective-scan XLA impl {name!r}: "
                         "expected 'step' or 'assoc'")
    _SSM_XLA_IMPL = name


def selective_scan(x, dt, A, Bm, C, D, h0=None, *,
                   backend: Optional[str] = None):
    impl = _pick(backend, tpu_kernel=False)
    if impl in ("pallas", "interpret"):
        from repro.kernels import selective_scan as sk
        return sk.selective_scan_pallas(x, dt, A, Bm, C, D, h0,
                                        interpret=(impl == "interpret"))
    if _SSM_XLA_IMPL == "assoc":
        return _selective_scan_assoc(x, dt, A, Bm, C, D, h0)
    return ref.selective_scan(x, dt, A, Bm, C, D, h0)


def _selective_scan_assoc(x, dt, A, Bm, C, D, h0=None, chunk: int = 256):
    """Chunked parallel selective scan (the TPU kernel's dataflow in XLA).

    The recurrence h_t = a_t h_{t-1} + b_t is a first-order linear scan, so
    within a chunk we use `jax.lax.associative_scan` (log-depth, fully
    parallel on the VPU) and carry the state across chunks with an outer
    `lax.scan`. HBM traffic drops from O(S) state read/writes (the naive
    per-step scan) to O(S/chunk) state + streaming activations — matching
    what the Pallas kernel achieves with VMEM-resident state.
    """
    B, S, Di = x.shape
    N = A.shape[-1]
    cs = min(chunk, S)
    if S % cs:
        return ref.selective_scan(x, dt, A, Bm, C, D, h0)
    nchunks = S // cs

    xf = x.astype(jnp.float32)
    dtf = dt.astype(jnp.float32)
    Af = A.astype(jnp.float32)
    Bf = Bm.astype(jnp.float32)
    Cf = C.astype(jnp.float32)
    Df = D.astype(jnp.float32)

    # per-step coefficients: h = dA * h_prev + dBx,  (B, S, Di, N)
    h = jnp.zeros((B, Di, N), jnp.float32) if h0 is None \
        else h0.astype(jnp.float32)

    def chunk_body(h_in, blk):
        xc, dtc, bc, cc = blk                       # (B, cs, Di/N)
        dA = jnp.exp(dtc[..., None] * Af)           # (B, cs, Di, N)
        dBx = (dtc * xc)[..., None] * bc[:, :, None, :]
        # fold the incoming state into the first step's additive term
        dBx = dBx.at[:, 0].add(dA[:, 0] * h_in)

        def combine(a, b):
            # (a2, b2) o (a1, b1) = (a1*a2, a2*b1 + b2) along time
            return a[0] * b[0], b[0] * a[1] + b[1]

        _, hs = jax.lax.associative_scan(combine, (dA, dBx), axis=1)
        y = jnp.einsum("bsdn,bsn->bsd", hs, cc) + Df * xc
        return hs[:, -1], y

    xcs = xf.reshape(B, nchunks, cs, Di).swapaxes(0, 1)
    dtcs = dtf.reshape(B, nchunks, cs, Di).swapaxes(0, 1)
    bcs = Bf.reshape(B, nchunks, cs, N).swapaxes(0, 1)
    ccs = Cf.reshape(B, nchunks, cs, N).swapaxes(0, 1)
    hT, ys = jax.lax.scan(chunk_body, h, (xcs, dtcs, bcs, ccs))
    y = ys.swapaxes(0, 1).reshape(B, S, Di)
    return y.astype(x.dtype), hT


def selective_scan_step(x, dt, A, Bm, C, D, h):
    """Single decode step. x, dt: (B, Di); Bm, C: (B, N); h: (B, Di, N)."""
    dA = jnp.exp(dt.astype(jnp.float32)[..., None] * A.astype(jnp.float32))
    dBx = dt.astype(jnp.float32)[..., None] * Bm.astype(jnp.float32)[:, None, :] \
        * x.astype(jnp.float32)[..., None]
    h = h.astype(jnp.float32) * dA + dBx
    y = jnp.einsum("bdn,bn->bd", h, C.astype(jnp.float32)) \
        + D.astype(jnp.float32) * x.astype(jnp.float32)
    return y.astype(x.dtype), h


# ---------------------------------------------------------------------------
# RG-LRU
# ---------------------------------------------------------------------------

def rglru(x, r_gate, i_gate, a_param, h0=None, *, c: float = 8.0,
          backend: Optional[str] = None):
    impl = _pick(backend, tpu_kernel=False)
    if impl in ("pallas", "interpret"):
        from repro.kernels import rglru_scan as rk
        return rk.rglru_pallas(x, r_gate, i_gate, a_param, h0, c=c,
                               interpret=(impl == "interpret"))
    if _SSM_XLA_IMPL == "assoc":
        return _rglru_assoc(x, r_gate, i_gate, a_param, h0, c=c)
    return ref.rglru(x, r_gate, i_gate, a_param, h0, c=c)


def _rglru_assoc(x, r_gate, i_gate, a_param, h0=None, *, c: float = 8.0,
                 chunk: int = 256):
    """Chunked parallel RG-LRU (same first-order-linear-scan treatment as
    _selective_scan_assoc; diagonal state so no N blowup)."""
    B, S, W = x.shape
    cs = min(chunk, S)
    if S % cs:
        return ref.rglru(x, r_gate, i_gate, a_param, h0, c=c)
    nchunks = S // cs

    log_a = -c * jax.nn.softplus(-a_param.astype(jnp.float32))
    r = jax.nn.sigmoid(r_gate.astype(jnp.float32))
    a_t = jnp.exp(r * log_a)                                   # (B, S, W)
    gated = jax.nn.sigmoid(i_gate.astype(jnp.float32)) * x.astype(jnp.float32)
    b_t = jnp.sqrt(jnp.maximum(1.0 - a_t * a_t, 0.0)) * gated

    h = jnp.zeros((B, W), jnp.float32) if h0 is None else h0.astype(jnp.float32)

    def chunk_body(h_in, blk):
        ac, bc = blk
        bc = bc.at[:, 0].add(ac[:, 0] * h_in)

        def combine(p, q):
            return p[0] * q[0], q[0] * p[1] + q[1]

        _, hs = jax.lax.associative_scan(combine, (ac, bc), axis=1)
        return hs[:, -1], hs

    acs = a_t.reshape(B, nchunks, cs, W).swapaxes(0, 1)
    bcs = b_t.reshape(B, nchunks, cs, W).swapaxes(0, 1)
    hT, hs = jax.lax.scan(chunk_body, h, (acs, bcs))
    out = hs.swapaxes(0, 1).reshape(B, S, W)
    return out.astype(x.dtype), hT


def rglru_step(x, r_gate, i_gate, a_param, h, c: float = 8.0):
    """Single decode step; all (B, W)."""
    log_a = -c * jax.nn.softplus(-a_param.astype(jnp.float32))
    r = jax.nn.sigmoid(r_gate.astype(jnp.float32))
    a_t = jnp.exp(r * log_a)
    gated = jax.nn.sigmoid(i_gate.astype(jnp.float32)) * x.astype(jnp.float32)
    h = a_t * h.astype(jnp.float32) + jnp.sqrt(jnp.maximum(1 - a_t * a_t, 0.0)) * gated
    return h.astype(x.dtype), h


# ---------------------------------------------------------------------------
# LoRA-fused matmul (trainable: custom VJP so `grad` traverses the kernel)
# ---------------------------------------------------------------------------

def lora_matmul(x, w, a=None, b=None, scale: float = 1.0, bias=None, *,
                backend: Optional[str] = None):
    """y = x @ w (+ scale * (x@a)@b) (+ bias). Falls back to plain matmul.

    Differentiable on every backend: a custom VJP makes the fused Pallas
    forward usable under ``jax.grad``. On the PEFT hot path the backward
    costs only ``dx``/``dA``/``dB`` (+ ``dbias``) — adapter-only training
    (core/peft.py) never differentiates w, so the frozen-weight gradient
    ``dW = x^T dy`` is dead code under jit and never materializes; full
    fine-tuning (``trainable='all'``) still receives the exact dW.
    """
    if a is None:
        y = x @ w
        return (y + bias.astype(y.dtype)) if bias is not None else y
    return _lora_vjp(_pick(backend), float(scale), x, w, a, b, bias)


@partial(jax.custom_vjp, nondiff_argnums=(0, 1))
def _lora_vjp(impl, scale, x, w, a, b, bias):
    return _lora_forward(impl, scale, x, w, a, b, bias)


def _lora_forward(impl, scale, x, w, a, b, bias, contract_split=False):
    """Under a mesh the kernel splits w's output columns over the tensor-
    parallel axes; ``contract_split`` splits its contracted rows instead
    and sums the partial products (the backward's ``dy @ w^T``, where dy
    and w arrive split along that dim)."""
    if impl in ("pallas", "interpret") and x.ndim == 2:
        from repro.kernels import lora_matmul as lk
        interp = impl == "interpret"
        if contract_split:
            axes = [("batch", "heads"), ("heads", None), ("heads", None),
                    (None, None)]
            out, red = ("batch", None), ("heads",)
        else:
            axes = [("batch", None), (None, "heads"), (None, None),
                    (None, "heads")]
            out, red = ("batch", "heads"), ()
        ops_ = (x, w, a, b) + (() if bias is None else (bias,))
        return _on_mesh("lora_matmul_fwd", lambda *o: lk.lora_matmul_pallas(
            *o[:4], scale, *o[4:], interpret=interp),
            ops_, axes + [("heads",)][:len(ops_) - 4], out, red)
    return _lora_xla(x, w, a, b, scale, bias)


def _lora_fwd_rule(impl, scale, x, w, a, b, bias):
    y = _lora_forward(impl, scale, x, w, a, b, bias)
    return y, (x, w, a, b, bias)


def _lora_bwd_rule(impl, scale, res, dy):
    """dx reuses the *forward* fused kernel (dx = dy W^T + s (dy B^T) A^T is
    itself a LoRA matmul with (W, A, B) -> (W^T, B^T, A^T)); dA/dB go through
    the dedicated adapter-grad kernel (kernels/lora_matmul.py::_bwd_kernel).
    dW = x^T dy is exact for full fine-tuning (peft.py trainable='all'), and
    under the PEFT regime — where w is never a differentiation target — the
    jitted round drops the dense matmul as dead code, so adapter-only
    training never materializes it."""
    x, w, a, b, bias = res
    x2 = x.reshape(-1, x.shape[-1])
    dy2 = dy.reshape(-1, dy.shape[-1])
    dx = _lora_forward(impl, scale, dy2, w.T, b.T, a.T, None,
                       contract_split=True)
    if impl in ("pallas", "interpret"):
        from repro.kernels import lora_matmul as lk
        interp = impl == "interpret"
        da, db = _on_mesh(
            "lora_matmul_bwd",
            lambda *o: lk.lora_matmul_bwd_pallas(*o, scale, interpret=interp),
            (x2, dy2, a, b),
            (("batch", None), ("batch", "heads"), (None, None),
             (None, "heads")),
            [(None, None), (None, "heads")], [("batch", "heads"), ("batch",)])
    else:
        da, db = _lora_bwd_xla(x2, dy2, a, b, scale)
    dw = jax.lax.dot_general(x2, dy2, (((0,), (0,)), ((), ())),
                             preferred_element_type=jnp.float32)
    dbias = None if bias is None else \
        jnp.sum(dy2.astype(jnp.float32), axis=0).astype(bias.dtype)
    return (dx.reshape(x.shape).astype(x.dtype), dw.astype(w.dtype),
            da.astype(a.dtype), db.astype(b.dtype), dbias)


_lora_vjp.defvjp(_lora_fwd_rule, _lora_bwd_rule)


def _lora_bwd_xla(x, dy, a, b, scale):
    """Adapter grads, native-dtype dots with f32 accumulation (the kernel's
    dataflow in XLA): both rank-r intermediates are (M, r), so the extra HBM
    traffic over reading x/dy once is negligible."""
    g = jax.lax.dot_general(dy, b, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)   # dy @ b^T
    u = jax.lax.dot_general(x, a, (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)   # x @ a
    da = scale * jax.lax.dot_general(
        x, g.astype(x.dtype), (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)                       # x^T @ g
    db = scale * jax.lax.dot_general(
        u.astype(dy.dtype), dy, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)                       # u^T @ dy
    return da, db


def lora_bgmv(x, w, a, b, adapter_ids, scale: float = 1.0, bias=None, *,
              backend: Optional[str] = None):
    """Multi-tenant LoRA matmul: per-row adapter selection from a stacked
    bank (kernels/lora_bgmv.py; serving-only, no VJP).

    x: (M, K) with adapter_ids (M,), or (B, S, K) with adapter_ids (B,).
    a: (n_slots, K, r); b: (n_slots, r, N); ids in [0, n_slots).
    Row i gets ``x_i @ w + scale * (x_i @ a[id_i]) @ b[id_i]`` (+ bias) —
    bit-identical per row to :func:`lora_matmul` with that row's adapter,
    which is what makes mixed-domain waves match per-domain serving
    token-for-token.
    """
    ids = jnp.asarray(adapter_ids, jnp.int32)
    # ids address x's LEADING dim on every backend: rows for 2D x, whole
    # sequences for 3D x. Reject per-token ids for 3D x here — the XLA
    # fallback would happily broadcast them while the gathered Pallas path
    # reads only ids[0:B], a silent cross-backend divergence.
    if ids.shape != (x.shape[0],):
        raise ValueError(
            f"adapter_ids {ids.shape} must be ({x.shape[0]},): one id per "
            f"{'sequence' if x.ndim == 3 else 'row'} of x {x.shape}")
    impl = _pick(backend)
    if impl in ("pallas", "interpret"):
        from repro.kernels import lora_bgmv as bk
        interp = impl == "interpret"
        seq = x.ndim == 3 and x.shape[1] > 1      # prefill: gathered path
        kern = bk.lora_bgmv_seq_pallas if seq else bk.lora_bgmv_rows_pallas
        shp = x.shape                               # decode rows: BGMV path
        xk = x if seq else x.reshape(-1, shp[-1])
        rows = ("batch",) + (None,) * (xk.ndim - 1)
        ops_ = (xk, w, a, b, ids) + (() if bias is None else (bias,))
        axes = [rows, (None, "heads"), (None, None, None),
                (None, None, "heads"), ("batch",), ("heads",)]
        out = _on_mesh("lora_bgmv_seq" if seq else "lora_bgmv_rows",
                       lambda *o: kern(*o[:5], float(scale), *o[5:],
                                       interpret=interp),
                       ops_, axes[:len(ops_)], rows[:-1] + ("heads",))
        return out if seq else out.reshape(*shp[:-1], w.shape[-1])
    return _bgmv_xla(x, w, a, b, ids, float(scale), bias)


def _bgmv_xla(x, w, a, b, ids, scale, bias=None):
    """Segment-matmul fallback: sweep the (static) slot dim with disjoint
    row masks instead of gathering (M, K, r) adapter copies. Per-row math
    mirrors :func:`_lora_xla` exactly (native-dtype dots, f32 accumulation,
    same cast points) so single- and multi-tenant serving agree bitwise.
    """
    shp = x.shape
    x2 = x.reshape(-1, shp[-1])
    if ids.shape[0] != x2.shape[0]:                # per-sequence -> per-row
        ids = jnp.repeat(ids, shp[1])
    y = jax.lax.dot_general(x2, w, (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)
    mask = ids[:, None]
    for s in range(a.shape[0]):                    # static slot sweep
        xs = jnp.where(mask == s, x2, jnp.zeros((), x2.dtype))
        u = jax.lax.dot_general(xs, a[s], (((1,), (0,)), ((), ())),
                                preferred_element_type=jnp.float32)
        y = y + scale * jax.lax.dot_general(
            u.astype(x2.dtype), b[s], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
    if bias is not None:
        y = y + bias.astype(jnp.float32)
    return y.astype(x.dtype).reshape(*shp[:-1], w.shape[-1])


def _lora_xla(x, w, a, b, scale, bias=None):
    """Native-dtype dots with f32 accumulation (what the MXU does).

    The naive oracle upcasts x/w to f32 — on the XLA path that doubles HBM
    traffic for EVERY projection and drags f32 tensors through the backward
    collectives (EXPERIMENTS.md §Perf iter q4, found via the roofline
    profile)."""
    nd = x.ndim - 1
    y = jax.lax.dot_general(x, w, (((nd,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)
    u = jax.lax.dot_general(x, a, (((nd,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)
    y = y + scale * jax.lax.dot_general(
        u.astype(x.dtype), b, (((u.ndim - 1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    if bias is not None:
        y = y + bias.astype(jnp.float32)
    return y.astype(x.dtype)
