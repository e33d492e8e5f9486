"""Per-kernel allclose sweeps: Pallas (interpret=True) vs pure-jnp oracle."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref

KEY = jax.random.PRNGKey(42)


def tol(dtype):
    return dict(atol=2e-2, rtol=2e-2) if dtype == jnp.bfloat16 \
        else dict(atol=2e-5, rtol=2e-5)


# ---------------------------------------------------------------------------
# Flash attention
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("B,S,T,Hq,Hkv,D", [
    (1, 16, 16, 1, 1, 8),
    (2, 48, 56, 4, 2, 32),          # GQA + prefix slots
    (1, 64, 64, 4, 4, 64),
    (2, 33, 40, 2, 1, 16),          # ragged (padding path)
])
@pytest.mark.parametrize("window", [0, 16])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("backend", ["xla", "interpret"])
def test_flash_attention(B, S, T, Hq, Hkv, D, window, dtype, backend):
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (B, S, Hq, D), dtype)
    k = jax.random.normal(ks[1], (B, T, Hkv, D), dtype)
    v = jax.random.normal(ks[2], (B, T, Hkv, D), dtype)
    n_p = T - S
    q_pos = jnp.arange(S)
    kv_pos = jnp.arange(T) - n_p
    want = ref.attention(q, k, v, q_pos=q_pos, kv_pos=kv_pos, window=window)
    got = ops.flash_attention(q, k, v, q_pos=q_pos, kv_pos=kv_pos,
                              window=window, block_q=16, block_kv=16,
                              backend=backend)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), **tol(dtype))


def test_flash_attention_noncausal():
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (2, 24, 2, 16))
    k = jax.random.normal(ks[1], (2, 30, 2, 16))
    v = jax.random.normal(ks[2], (2, 30, 2, 16))
    qp, kp = jnp.arange(24), jnp.arange(30)
    want = ref.attention(q, k, v, q_pos=qp, kv_pos=kp, causal=False)
    for backend in ("xla", "interpret"):
        got = ops.flash_attention(q, k, v, q_pos=qp, kv_pos=kp, causal=False,
                                  block_q=8, block_kv=8, backend=backend)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=2e-5, rtol=2e-5)


# ---------------------------------------------------------------------------
# Selective scan
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("B,S,Di,N", [(1, 32, 128, 8), (2, 64, 256, 16),
                                      (2, 128, 512, 16)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("with_h0", [False, True])
def test_selective_scan(B, S, Di, N, dtype, with_h0):
    ks = jax.random.split(KEY, 6)
    x = (jax.random.normal(ks[0], (B, S, Di)) * 0.5).astype(dtype)
    dt = (jax.nn.softplus(jax.random.normal(ks[1], (B, S, Di))) * 0.1).astype(dtype)
    A = -jnp.exp(jax.random.normal(ks[2], (Di, N)) * 0.3)
    Bm = (jax.random.normal(ks[3], (B, S, N)) * 0.5).astype(dtype)
    C = (jax.random.normal(ks[4], (B, S, N)) * 0.5).astype(dtype)
    D = jnp.ones((Di,))
    h0 = jax.random.normal(ks[5], (B, Di, N)) * 0.1 if with_h0 else None
    y_ref, h_ref = ref.selective_scan(x, dt, A, Bm, C, D, h0)
    y, h = ops.selective_scan(x, dt, A, Bm, C, D, h0, backend="interpret")
    np.testing.assert_allclose(np.asarray(y, np.float32),
                               np.asarray(y_ref, np.float32), **tol(dtype))
    np.testing.assert_allclose(np.asarray(h), np.asarray(h_ref),
                               atol=1e-3, rtol=1e-3)


def test_selective_scan_step_matches_seq():
    """Decode step telescopes to the full scan."""
    B, S, Di, N = 2, 8, 64, 8
    ks = jax.random.split(KEY, 5)
    x = jax.random.normal(ks[0], (B, S, Di)) * 0.5
    dt = jax.nn.softplus(jax.random.normal(ks[1], (B, S, Di))) * 0.2
    A = -jnp.exp(jax.random.normal(ks[2], (Di, N)) * 0.3)
    Bm = jax.random.normal(ks[3], (B, S, N)) * 0.5
    C = jax.random.normal(ks[4], (B, S, N)) * 0.5
    D = jnp.ones((Di,))
    y_ref, h_ref = ref.selective_scan(x, dt, A, Bm, C, D)
    h = jnp.zeros((B, Di, N))
    ys = []
    for t in range(S):
        y, h = ops.selective_scan_step(x[:, t], dt[:, t], A, Bm[:, t],
                                       C[:, t], D, h)
        ys.append(y)
    np.testing.assert_allclose(np.stack(ys, 1), np.asarray(y_ref),
                               atol=1e-5, rtol=1e-5)
    np.testing.assert_allclose(np.asarray(h), np.asarray(h_ref),
                               atol=1e-5, rtol=1e-5)


# ---------------------------------------------------------------------------
# RG-LRU
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("B,S,W", [(1, 32, 128), (2, 64, 256), (2, 96, 512)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_rglru(B, S, W, dtype):
    ks = jax.random.split(KEY, 5)
    x = (jax.random.normal(ks[0], (B, S, W)) * 0.5).astype(dtype)
    r = jax.random.normal(ks[1], (B, S, W)).astype(dtype)
    i = jax.random.normal(ks[2], (B, S, W)).astype(dtype)
    a = jax.random.normal(ks[3], (W,))
    h0 = jax.random.normal(ks[4], (B, W)) * 0.1
    hs_ref, hT_ref = ref.rglru(x, r, i, a, h0)
    hs, hT = ops.rglru(x, r, i, a, h0, backend="interpret")
    np.testing.assert_allclose(np.asarray(hs, np.float32),
                               np.asarray(hs_ref, np.float32), **tol(dtype))
    np.testing.assert_allclose(np.asarray(hT), np.asarray(hT_ref),
                               atol=1e-3, rtol=1e-3)


def test_rglru_step_matches_seq():
    B, S, W = 2, 12, 64
    ks = jax.random.split(KEY, 4)
    x = jax.random.normal(ks[0], (B, S, W)) * 0.5
    r = jax.random.normal(ks[1], (B, S, W))
    i = jax.random.normal(ks[2], (B, S, W))
    a = jax.random.normal(ks[3], (W,))
    hs_ref, hT_ref = ref.rglru(x, r, i, a)
    h = jnp.zeros((B, W))
    for t in range(S):
        y, h = ops.rglru_step(x[:, t], r[:, t], i[:, t], a, h)
    np.testing.assert_allclose(np.asarray(h), np.asarray(hT_ref),
                               atol=1e-5, rtol=1e-5)


# ---------------------------------------------------------------------------
# LoRA matmul
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("M,K,N,r", [(32, 64, 48, 4), (100, 200, 300, 8),
                                     (256, 512, 512, 16)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("with_bias", [False, True])
def test_lora_matmul(M, K, N, r, dtype, with_bias):
    ks = jax.random.split(KEY, 5)
    x = jax.random.normal(ks[0], (M, K), dtype)
    w = (jax.random.normal(ks[1], (K, N)) * 0.05).astype(dtype)
    a = (jax.random.normal(ks[2], (K, r)) * 0.05).astype(dtype)
    b = (jax.random.normal(ks[3], (r, N)) * 0.05).astype(dtype)
    bias = jax.random.normal(ks[4], (N,)).astype(dtype) if with_bias else None
    want = ref.lora_matmul(x, w, a, b, 2.0, bias)
    got = ops.lora_matmul(x, w, a, b, 2.0, bias, backend="interpret")
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), **tol(dtype))


@pytest.mark.parametrize("M,K,N,r", [(32, 64, 48, 4), (100, 200, 144, 8)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("backend", ["xla", "interpret"])
def test_lora_matmul_custom_vjp(M, K, N, r, dtype, backend):
    """grad through the fused kernel == einsum oracle: dx, dA, dB, dbias
    (adapter grads only — the frozen dW is never formed)."""
    ks = jax.random.split(KEY, 6)
    x = jax.random.normal(ks[0], (M, K), dtype)
    w = (jax.random.normal(ks[1], (K, N)) * 0.05).astype(dtype)
    a = (jax.random.normal(ks[2], (K, r)) * 0.05).astype(dtype)
    b = (jax.random.normal(ks[3], (r, N)) * 0.05).astype(dtype)
    bias = jax.random.normal(ks[4], (N,)).astype(dtype)
    dy = jax.random.normal(ks[5], (M, N), dtype)

    def f(x_, a_, b_, bias_):
        y = ops.lora_matmul(x_, w, a_, b_, 2.0, bias_, backend=backend)
        return jnp.sum(y.astype(jnp.float32) * dy.astype(jnp.float32))

    dx, da, db, dbias = jax.grad(f, argnums=(0, 1, 2, 3))(x, a, b, bias)
    rdx, rda, rdb = ref.lora_matmul_bwd(x, w, a, b, 2.0, dy)
    # grads accumulate over M rows — bf16 native-dtype dots round harder
    # than the single forward pass
    t = dict(atol=1e-1, rtol=5e-2) if dtype == jnp.bfloat16 else tol(dtype)
    np.testing.assert_allclose(np.asarray(dx, np.float32),
                               np.asarray(rdx, np.float32), **t)
    np.testing.assert_allclose(np.asarray(da, np.float32),
                               np.asarray(rda, np.float32), **t)
    np.testing.assert_allclose(np.asarray(db, np.float32),
                               np.asarray(rdb, np.float32), **t)
    np.testing.assert_allclose(
        np.asarray(dbias, np.float32),
        np.asarray(jnp.sum(dy.astype(jnp.float32), 0)), **t)


@pytest.mark.parametrize("backend", ["xla", "interpret"])
def test_lora_matmul_vjp_full_ft_dw(backend):
    """Full fine-tuning (peft trainable='all') must still receive the exact
    frozen-weight grad dW = x^T dy through the custom VJP."""
    ks = jax.random.split(KEY, 5)
    M, K, N, r = 24, 32, 40, 4
    x = jax.random.normal(ks[0], (M, K))
    w = jax.random.normal(ks[1], (K, N)) * 0.05
    a = jax.random.normal(ks[2], (K, r)) * 0.05
    b = jax.random.normal(ks[3], (r, N)) * 0.05
    dy = jax.random.normal(ks[4], (M, N))

    def f(w_):
        return jnp.vdot(ops.lora_matmul(x, w_, a, b, 2.0, backend=backend),
                        dy)

    dw = jax.grad(f)(w)
    np.testing.assert_allclose(np.asarray(dw), np.asarray(x.T @ dy),
                               atol=2e-5, rtol=2e-5)


def test_lora_matmul_vjp_under_vmap():
    """The HFSL shape: per-cluster adapters vmapped over the cluster dim."""
    ks = jax.random.split(KEY, 5)
    M, K, N, r, C = 16, 32, 24, 4, 3
    x = jax.random.normal(ks[0], (M, K))
    w = jax.random.normal(ks[1], (K, N)) * 0.05
    av = jax.random.normal(ks[2], (C, K, r)) * 0.05
    bv = jax.random.normal(ks[3], (C, r, N)) * 0.05
    dy = jax.random.normal(ks[4], (M, N))

    def f(a_, b_):
        return jnp.vdot(ops.lora_matmul(x, w, a_, b_, 2.0,
                                        backend="interpret"), dy)

    da, db = jax.vmap(jax.grad(f, argnums=(0, 1)))(av, bv)
    for c in range(C):
        _, rda, rdb = ref.lora_matmul_bwd(x, w, av[c], bv[c], 2.0, dy)
        np.testing.assert_allclose(np.asarray(da[c]), np.asarray(rda),
                                   atol=2e-5, rtol=2e-5)
        np.testing.assert_allclose(np.asarray(db[c]), np.asarray(rdb),
                                   atol=2e-5, rtol=2e-5)


# ---------------------------------------------------------------------------
# Batched multi-LoRA (BGMV, multi-tenant serving)
# ---------------------------------------------------------------------------

def _bgmv_operands(M, K, N, r, n_slots, dtype, with_bias, seq=None):
    ks = jax.random.split(KEY, 6)
    shape = (M, K) if seq is None else (M, seq, K)
    x = jax.random.normal(ks[0], shape, dtype)
    w = (jax.random.normal(ks[1], (K, N)) * 0.05).astype(dtype)
    a = (jax.random.normal(ks[2], (n_slots, K, r)) * 0.05).astype(dtype)
    b = (jax.random.normal(ks[3], (n_slots, r, N)) * 0.05).astype(dtype)
    bias = jax.random.normal(ks[4], (N,)).astype(dtype) if with_bias else None
    ids = jax.random.randint(ks[5], (M,), 0, n_slots, dtype=jnp.int32)
    return x, w, a, b, bias, ids


@pytest.mark.parametrize("M,K,N,r,n_slots", [
    (16, 32, 24, 4, 3),
    (100, 200, 144, 8, 5),           # padding path
    (8, 64, 48, 4, 1),               # degenerate single tenant
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("with_bias", [False, True])
@pytest.mark.parametrize("backend", ["xla", "interpret"])
def test_lora_bgmv_rows(M, K, N, r, n_slots, dtype, with_bias, backend):
    """Decode shape: one adapter_id per row, vs the gather oracle."""
    x, w, a, b, bias, ids = _bgmv_operands(M, K, N, r, n_slots, dtype,
                                           with_bias)
    want = ref.lora_bgmv(x, w, a, b, ids, 2.0, bias)
    got = ops.lora_bgmv(x, w, a, b, ids, 2.0, bias, backend=backend)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), **tol(dtype))


@pytest.mark.parametrize("B,S,K,N,r,n_slots", [
    (4, 12, 32, 24, 4, 3),
    (3, 9, 96, 80, 8, 4),            # padding path
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("backend", ["xla", "interpret"])
def test_lora_bgmv_seq(B, S, K, N, r, n_slots, dtype, backend):
    """Prefill shape: one adapter_id per sequence (gathered path)."""
    x, w, a, b, bias, ids = _bgmv_operands(B, K, N, r, n_slots, dtype,
                                           True, seq=S)
    want = ref.lora_bgmv(x, w, a, b, ids, 2.0, bias)
    got = ops.lora_bgmv(x, w, a, b, ids, 2.0, bias, backend=backend)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), **tol(dtype))


def test_lora_bgmv_matches_single_lora_per_row():
    """The multi-tenant == single-tenant parity the engine relies on:
    every row's result is bit-identical to the single-LoRA fast path run
    with that row's adapter pair (XLA backends share the same dot
    structure and cast points)."""
    M, K, N, r, n_slots = 24, 32, 40, 4, 3
    x, w, a, b, bias, ids = _bgmv_operands(M, K, N, r, n_slots,
                                           jnp.float32, True)
    got = np.asarray(ops.lora_bgmv(x, w, a, b, ids, 2.0, bias,
                                   backend="xla"))
    for s in range(n_slots):
        rows = np.asarray(ids) == s
        want = ops.lora_matmul(x[rows], w, a[s], b[s], 2.0, bias,
                               backend="xla")
        np.testing.assert_array_equal(got[rows], np.asarray(want))


# ---------------------------------------------------------------------------
# Paged flash decode (block-table indirection)
# ---------------------------------------------------------------------------

def _paged_operands(B, maxb, bs, Hq, Hkv, D, n_blocks, dtype, seed=0):
    """A random block pool plus per-row tables of distinct live blocks."""
    ks = jax.random.split(jax.random.fold_in(KEY, seed), 3)
    q = jax.random.normal(ks[0], (B, Hq, D), dtype)
    k_pool = jax.random.normal(ks[1], (n_blocks, bs, Hkv, D), dtype)
    v_pool = jax.random.normal(ks[2], (n_blocks, bs, Hkv, D), dtype)
    rng = np.random.default_rng(seed)
    table = np.stack([rng.choice(n_blocks, maxb, replace=False)
                      for _ in range(B)]).astype(np.int32)
    return q, k_pool, v_pool, jnp.asarray(table)


@pytest.mark.parametrize("B,maxb,bs,Hq,Hkv,D", [
    (1, 2, 16, 1, 1, 8),
    (2, 4, 8, 4, 2, 32),            # GQA + ragged q_pos
    (3, 3, 16, 2, 1, 16),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("backend", ["xla", "interpret"])
def test_paged_flash_decode_matches_ref(B, maxb, bs, Hq, Hkv, D, dtype,
                                        backend):
    """Block-table-indirected decode == the pure-jnp paged oracle, with
    ragged per-row positions leaving trailing pool slots invisible."""
    q, k_pool, v_pool, table = _paged_operands(B, maxb, bs, Hq, Hkv, D,
                                               n_blocks=maxb * B + 3,
                                               dtype=dtype)
    q_pos = jnp.asarray([(maxb * bs - 1 - 3 * i) % (maxb * bs)
                         for i in range(B)], jnp.int32)
    want = ref.paged_decode_attention(q, k_pool, v_pool, table, q_pos=q_pos)
    got = ops.flash_decode_paged(q, k_pool, v_pool, table, q_pos=q_pos,
                                 backend=backend)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), **tol(dtype))


@pytest.mark.parametrize("backend", ["xla", "interpret"])
def test_paged_flash_decode_bit_parity_with_dense(backend):
    """fp32 paged-vs-dense: gathering the pool through the table into the
    dense layout and running the dense decode path sees the SAME visible
    values, so on xla (identical accumulation order — the path engine
    drains take) the outputs are BITWISE equal; the pallas kernels chunk
    kv differently (one chunk per block vs block_kv), so interpret holds
    to fp32 tolerance instead."""
    B, maxb, bs, Hq, Hkv, D = 2, 4, 8, 4, 2, 32
    q, k_pool, v_pool, table = _paged_operands(B, maxb, bs, Hq, Hkv, D,
                                               n_blocks=16, dtype=jnp.float32)
    q_pos = jnp.asarray([maxb * bs - 1, maxb * bs - 9], jnp.int32)
    # the dense resident layout: head-major (B, Hkv, T, D)
    k = jnp.swapaxes(k_pool[table].reshape(B, maxb * bs, Hkv, D), 1, 2)
    v = jnp.swapaxes(v_pool[table].reshape(B, maxb * bs, Hkv, D), 1, 2)
    kv_pos = jnp.arange(maxb * bs, dtype=jnp.int32)
    dense = ops.flash_decode(q, k, v, q_pos=q_pos, kv_pos=kv_pos,
                             window=0, causal=True, backend=backend)
    paged = ops.flash_decode_paged(q, k_pool, v_pool, table, q_pos=q_pos,
                                   backend=backend)
    if backend == "xla":
        np.testing.assert_array_equal(np.asarray(paged), np.asarray(dense))
    else:
        np.testing.assert_allclose(np.asarray(paged), np.asarray(dense),
                                   **tol(jnp.float32))


# ---------------------------------------------------------------------------
# Backend selection and the training path through the attention kernel
# ---------------------------------------------------------------------------

def test_backend_follows_platform_and_pallas_needs_tpu():
    """Off TPU the default is xla; choosing pallas raises (no quiet
    fallback), globally and per call."""
    assert jax.default_backend() != "tpu"
    assert ops.get_backend() == "xla"
    with pytest.raises(ValueError, match="needs a TPU"):
        ops.set_backend("pallas")
    assert ops.get_backend() == "xla"
    q = jnp.ones((1, 8, 1, 8))
    with pytest.raises(ValueError, match="needs a TPU"):
        ops.flash_attention(q, q, q, q_pos=jnp.arange(8),
                            kv_pos=jnp.arange(8), backend="pallas")


def test_flash_attention_kernel_grad_is_the_xla_grad():
    """The kernel's custom VJP differentiates the blocked XLA algorithm at
    the same inputs, so gradients equal the xla backend's exactly."""
    ks = jax.random.split(KEY, 4)
    q = jax.random.normal(ks[0], (2, 24, 4, 16))
    k = jax.random.normal(ks[1], (2, 28, 2, 16))
    v = jax.random.normal(ks[2], (2, 28, 2, 16))
    w = jax.random.normal(ks[3], (2, 24, 4, 16))
    qp, kp = jnp.arange(24), jnp.arange(28) - 4

    def loss(backend):
        return lambda q, k, v: jnp.vdot(w, ops.flash_attention(
            q, k, v, q_pos=qp, kv_pos=kp, block_q=8, block_kv=8,
            backend=backend))

    want = jax.grad(loss("xla"), argnums=(0, 1, 2))(q, k, v)
    got = jax.grad(loss("interpret"), argnums=(0, 1, 2))(q, k, v)
    for g, h in zip(got, want):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(h))


@pytest.mark.parametrize("S,block_s", [(20, 8), (33, 16)])
def test_lora_bgmv_seq_tiles_the_sequence(S, block_s):
    """Prompts longer than one sequence tile run over an S grid dim."""
    from repro.kernels.lora_bgmv import lora_bgmv_seq_pallas
    x, w, a, b, bias, ids = _bgmv_operands(2, 64, 48, 4, 3, jnp.float32,
                                           True, seq=S)
    want = ref.lora_bgmv(x, w, a, b, ids, 2.0, bias)
    got = lora_bgmv_seq_pallas(x, w, a, b, ids, 2.0, bias, block_s=block_s,
                               interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               **tol(jnp.float32))
