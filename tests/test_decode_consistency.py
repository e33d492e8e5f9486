"""Serving-path invariants: prefill + decode == teacher-forced full forward;
scan generation == legacy per-token loop; flash-decode kernel == dense
cache-attention oracle."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import get_config
from repro.kernels import ops, ref
from repro.models import model as M

KEY = jax.random.PRNGKey(1)
B, S = 2, 12

ARCHS = ["qwen2-7b", "falcon-mamba-7b", "recurrentgemma-2b",
         "granite-moe-1b-a400m", "llava-next-mistral-7b", "whisper-small"]


@pytest.mark.parametrize("arch", ARCHS)
def test_prefill_decode_matches_forward(arch):
    cfg = get_config(arch).reduced().with_(dtype="float32")
    if cfg.family == "moe":   # disable token dropping for exactness
        cfg = cfg.with_(moe=dataclasses.replace(cfg.moe, capacity_factor=-1.0))
    params = M.init(cfg, KEY)
    toks = jax.random.randint(KEY, (B, S + 1), 0, cfg.vocab_size)
    batch = {"tokens": toks[:, :S]}
    full = {"tokens": toks}
    n_vis = 0
    if cfg.family == "vlm":
        vis = jax.random.normal(KEY, (B, cfg.vlm.n_vis_tokens, cfg.d_model)) * 0.1
        batch["vision_embeds"] = vis
        full["vision_embeds"] = vis
        n_vis = cfg.vlm.n_vis_tokens
    if cfg.family == "audio":
        fr = jax.random.normal(KEY, (B, cfg.audio.n_audio_frames, cfg.d_model)) * 0.1
        batch["frames"] = fr
        full["frames"] = fr

    logits_pf, caches = M.prefill(params, batch, cfg, max_len=S + n_vis + 8)
    logits_dec, _ = M.decode_step(params, toks[:, S:S + 1], caches,
                                  jnp.asarray(S + n_vis, jnp.int32), cfg)
    ref = M.forward(params, full, cfg, mode="eval", remat=False)["logits"]
    np.testing.assert_allclose(np.asarray(logits_pf),
                               np.asarray(ref[:, -2:-1]), atol=3e-4, rtol=3e-4)
    np.testing.assert_allclose(np.asarray(logits_dec),
                               np.asarray(ref[:, -1:]), atol=3e-4, rtol=3e-4)


def test_multi_step_decode_matches_forward():
    """Five sequential decode steps stay consistent (cache reuse)."""
    cfg = get_config("qwen2-7b").reduced().with_(dtype="float32")
    params = M.init(cfg, KEY)
    T = 5
    toks = jax.random.randint(KEY, (B, S + T), 0, cfg.vocab_size)
    _, caches = M.prefill(params, {"tokens": toks[:, :S]}, cfg,
                          max_len=S + T + 1)
    ref = M.forward(params, {"tokens": toks}, cfg, mode="eval",
                    remat=False)["logits"]
    for t in range(T):
        logits, caches = M.decode_step(params, toks[:, S + t:S + t + 1],
                                       caches, jnp.asarray(S + t, jnp.int32),
                                       cfg)
        np.testing.assert_allclose(np.asarray(logits),
                                   np.asarray(ref[:, S + t:S + t + 1]),
                                   atol=3e-4, rtol=3e-4)


def test_sliding_window_decode_rolls_over():
    """Decode past the window: rolling cache matches full forward."""
    cfg = get_config("llava-next-mistral-7b").reduced().with_(
        dtype="float32", sliding_window=8)
    cfg = cfg.with_(vlm=dataclasses.replace(cfg.vlm, n_vis_tokens=4))
    params = M.init(cfg, KEY)
    T = 6                                  # S=12 > window=8, then 6 more
    toks = jax.random.randint(KEY, (B, S + T), 0, cfg.vocab_size)
    vis = jax.random.normal(KEY, (B, 4, cfg.d_model)) * 0.1
    _, caches = M.prefill(params, {"tokens": toks[:, :S],
                                   "vision_embeds": vis}, cfg)
    ref = M.forward(params, {"tokens": toks, "vision_embeds": vis}, cfg,
                    mode="eval", remat=False)["logits"]
    n_vis = 4
    for t in range(T):
        logits, caches = M.decode_step(params, toks[:, S + t:S + t + 1],
                                       caches,
                                       jnp.asarray(S + t + n_vis, jnp.int32),
                                       cfg)
        # full-forward logits carry the vision prefix: text token S+t sits
        # at index n_vis + S + t
        np.testing.assert_allclose(
            np.asarray(logits),
            np.asarray(ref[:, n_vis + S + t:n_vis + S + t + 1]),
            atol=3e-4, rtol=3e-4)


# ---------------------------------------------------------------------------
# Single-dispatch scan generation vs the legacy per-token loop
# ---------------------------------------------------------------------------

def _gen_setup(arch, seed=5):
    cfg = get_config(arch).reduced().with_(dtype="float32")
    params = M.init(cfg, KEY)
    key = jax.random.PRNGKey(seed)
    prompts = jax.random.randint(key, (B, S), 0, cfg.vocab_size,
                                 dtype=jnp.int32)
    extra = None
    if cfg.family == "vlm":
        extra = {"vision_embeds":
                 jax.random.normal(key, (B, cfg.vlm.n_vis_tokens,
                                         cfg.d_model)) * 0.1}
    return cfg, params, prompts, extra


@pytest.mark.parametrize("arch", ["qwen2-7b", "llava-next-mistral-7b"])
def test_generate_scan_matches_loop_greedy(arch):
    from repro.launch.serve import generate_loop
    cfg, params, prompts, extra = _gen_setup(arch)
    want = generate_loop(params, cfg, prompts, gen=6, extra_batch=extra)
    got = M.generate_scan(params, cfg, prompts, gen=6, extra_batch=extra)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_generate_scan_matches_loop_sampled():
    """Same key => identical samples (per-step key splits line up)."""
    from repro.launch.serve import generate_loop
    cfg, params, prompts, _ = _gen_setup("qwen2-7b")
    key = jax.random.PRNGKey(11)
    want = generate_loop(params, cfg, prompts, gen=8, greedy=False, key=key)
    got = M.generate_scan(params, cfg, prompts, gen=8, greedy=False, key=key)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


# ---------------------------------------------------------------------------
# Flash-decode kernel vs dense cache-attention oracle
# ---------------------------------------------------------------------------

def _resident(k, L, layer, key):
    """Lay a (B, T, Hkv, D) cache out as the resident layer-stacked
    (L, B, Hkv, T, D) buffer at ``layer``, other layers filled with noise
    the kernel must not read."""
    hm = jnp.swapaxes(k, 1, 2)
    stack = jax.random.normal(key, (L, *hm.shape), hm.dtype)
    return stack.at[layer].set(hm)


@pytest.mark.parametrize("Hq,Hkv", [(4, 4), (4, 2), (4, 1)])   # MHA + GQA
@pytest.mark.parametrize("window", [0, 8, 12])
@pytest.mark.parametrize("n_prefix", [0, 3])
@pytest.mark.parametrize("backend", ["xla", "interpret"])
def test_flash_decode_matches_dense_reference(Hq, Hkv, window, n_prefix,
                                              backend):
    """Sweep causal / sliding-window / prefix-KV / GQA; the cache has
    unwritten (+1e9 sentinel) slots that must never be read.

    The cache is the resident layer-stacked buffer (L, B, Hkv, T, D), read
    in place at layer 0 (MHA) or L-1 (GQA); the prefix bank is per row with
    two KV heads, else shared. Window 12 is a rolling buffer of 12 slots
    allocated as 16 (not a multiple of the window; block 8), per-row
    positions, its last four slots sentinels."""
    Bq, T, D, written = 2, 40, 32, 30
    L, layer = 3, (0 if Hq == Hkv else 2)
    block = 16
    ks = jax.random.split(jax.random.PRNGKey(Hq * 10 + window + n_prefix), 7)
    q = jax.random.normal(ks[0], (Bq, Hq, D))
    q_pos = jnp.asarray([written - 1, written - 8])      # per-row positions
    if window == 12:                 # rolling buffer: slot s holds p % 12
        T, block = 16, 8
        s_idx = jnp.arange(T)
        p = s_idx[None] + window * ((q_pos[:, None] - s_idx[None]) // window)
        kv_pos = jnp.where((s_idx[None] < window) & (p >= 0), p, 10 ** 9)
    else:
        kv_pos = jnp.where(jnp.arange(T) < written, jnp.arange(T), 10 ** 9)
    k = jax.random.normal(ks[1], (Bq, T, Hkv, D))
    v = jax.random.normal(ks[2], (Bq, T, Hkv, D))
    pk = pv = None
    kcat, vcat = k, v
    pcat = jnp.broadcast_to(kv_pos, (Bq, T))
    if n_prefix:
        shape = (Bq, n_prefix, Hkv, D) if Hkv == 2 else (n_prefix, Hkv, D)
        pk = jax.random.normal(ks[3], shape)
        pv = jax.random.normal(ks[4], shape)
        kcat = jnp.concatenate(
            [jnp.broadcast_to(pk, (Bq, n_prefix, Hkv, D)), k], axis=1)
        vcat = jnp.concatenate(
            [jnp.broadcast_to(pv, (Bq, n_prefix, Hkv, D)), v], axis=1)
        pcat = jnp.concatenate([jnp.full((Bq, n_prefix), -1), pcat], axis=1)
    want = ref.decode_attention(q, kcat, vcat, q_pos=q_pos, kv_pos=pcat,
                                window=window)
    pos = jnp.broadcast_to(jnp.broadcast_to(kv_pos, (Bq, T)), (L, Bq, T))
    got = ops.flash_decode(q, _resident(k, L, layer, ks[5]),
                           _resident(v, L, layer, ks[6]), layer=layer,
                           q_pos=q_pos, kv_pos=pos, prefix_k=pk, prefix_v=pv,
                           window=window, block_kv=block, backend=backend)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=1e-3, rtol=1e-3)


def test_flash_decode_noncausal_cross():
    """Cross-attention decode (audio): every encoder slot visible."""
    Bq, T, H, D = 2, 24, 2, 16
    ks = jax.random.split(jax.random.PRNGKey(9), 3)
    q = jax.random.normal(ks[0], (Bq, H, D))
    k = jax.random.normal(ks[1], (Bq, T, H, D))
    v = jax.random.normal(ks[2], (Bq, T, H, D))
    kv_pos = jnp.arange(T)
    want = ref.decode_attention(q, k, v, q_pos=5, kv_pos=kv_pos,
                                causal=False)
    k, v = jnp.swapaxes(k, 1, 2), jnp.swapaxes(v, 1, 2)    # head-major
    for backend in ("xla", "interpret"):
        got = ops.flash_decode(q, k, v, q_pos=5, kv_pos=kv_pos,
                               causal=False, block_kv=8, backend=backend)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=1e-3, rtol=1e-3)


@pytest.mark.parametrize("layer", [0, -1])
@pytest.mark.parametrize("backend", ["xla", "interpret"])
def test_attention_decode_writes_one_token_in_place(layer, backend):
    """Decode against the resident stacked cache (L, B, Hkv, T, D) writes
    each live row's new token (and its position) at [layer, row, :, slot]
    and nothing else: a retired row and every other layer stay bitwise as
    they were, and the output and the written layer equal a call on a
    stack of that layer's cache alone."""
    from repro.models import attention as A
    cfg = get_config("qwen2-7b").reduced().with_(dtype="float32")
    params = M.init(cfg, KEY)
    L = cfg.n_layers
    layer = layer % L
    lens = jnp.asarray([5, 9, 7], jnp.int32)
    toks = jax.random.randint(KEY, (3, 9), 0, cfg.vocab_size)
    _, caches = M.prefill(params, {"tokens": toks}, cfg, max_len=16,
                          prompt_lens=lens)
    cache = caches["g0"]["s0"]
    at = lambda t: jax.tree.map(lambda a: a[layer], t)
    p = at(params["backbone"]["layers"]["g0"]["s0"])["attn"]
    a = at(params["adapters"]["stack"]["g0"]["s0"])
    x = jax.random.normal(jax.random.PRNGKey(3), (3, 1, cfg.d_model))
    active = jnp.asarray([True, False, True])
    kw = dict(pos=lens, active=active)
    one = jax.tree.map(lambda c: c[layer][None], cache)
    with ops.backend(backend):
        y, new = A.attention_decode(p, a, x, cache, cfg, layer=layer, **kw)
        y1, new1 = A.attention_decode(p, a, x, one, cfg, layer=0, **kw)
    np.testing.assert_array_equal(np.asarray(y), np.asarray(y1))
    for key in ("k", "v", "pos"):
        np.testing.assert_array_equal(np.asarray(new[key][layer]),
                                      np.asarray(new1[key][0]))
        changed = np.asarray(new[key] != cache[key])
        if key != "pos":
            changed = changed.any(axis=(2, 4))           # (L, B, T)
        want = np.zeros_like(changed)
        for row in (0, 2):
            want[layer, row, int(lens[row])] = True
        np.testing.assert_array_equal(changed, want)

