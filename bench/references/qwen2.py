"""Plain reference of Qwen2 with the system's PEFT adapters, in float32.

Written from the published description (arXiv:2407.10671; the Qwen2
``config.json``), not from the program, and importing nothing of it:

- pre-norm decoder: RMSNorm (eps from the config), grouped-query attention
  with biases on q/k/v and rotary positions (rotate-half, theta from the
  config), SwiGLU MLP, final RMSNorm and an untied LM head;
- PEFT as the system defines it (GaisNet's prompt modules, paper §III-A):
  each layer has ``n_prefix`` learned key/value slots that every query sees
  and that carry no rotary position, and LoRA ``x @ A @ B * alpha / r`` on
  the projections named in ``lora_targets``.

Everything is computed in float32 with full-precision contractions. The
weights stay in their served dtype on the device and each layer's are cast
to float32 inside the layer scan, so the reference fits beside them.
``precision="fp8"`` is the control: every weight matrix is rounded to
float8 (e4m3, one scale per tensor) and contractions run at the default
precision, the step below the configuration's bfloat16.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

QCHUNK = 512            # query rows per attention block


def _cast(w, precision: str):
    w = w.astype(jnp.float32)
    if precision == "fp8":
        s = jnp.maximum(jnp.max(jnp.abs(w)), 1e-30) / 448.0
        w = (w / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s
    return w


def _rmsnorm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def _rope(x, pos, theta):
    half = x.shape[-1] // 2
    freq = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = pos[:, None].astype(jnp.float32) * freq           # (S, half)
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _attention(q, k, v, pk, pv):
    """q: (S, Hq, D); k, v: (S, Hkv, D); pk, pv: (P, Hkv, D) prefix slots,
    visible to every query. Causal over the sequence. Blocks of queries."""
    S, Hq, D = q.shape
    Hkv = k.shape[1]
    g = Hq // Hkv
    kk = jnp.repeat(jnp.concatenate([pk, k], 0), g, axis=1)  # (P+S, Hq, D)
    vv = jnp.repeat(jnp.concatenate([pv, v], 0), g, axis=1)
    P = pk.shape[0]
    C = min(QCHUNK, S)
    n = -(-S // C)
    qp = jnp.pad(q, ((0, n * C - S), (0, 0), (0, 0))).reshape(n, C, Hq, D)
    kpos = jnp.concatenate([jnp.full((P,), -1), jnp.arange(S)])

    def block(args):
        qc, i = args
        qpos = i * C + jnp.arange(C)
        s = jnp.einsum("chd,khd->hck", qc, kk) / jnp.sqrt(jnp.float32(D))
        vis = (kpos[None, :] < 0) | (kpos[None, :] <= qpos[:, None])
        s = jnp.where(vis[None], s, -jnp.inf)
        return jnp.einsum("hck,khd->chd", jax.nn.softmax(s, -1), vv)

    out = jax.lax.map(block, (qp, jnp.arange(n)))
    return out.reshape(n * C, Hq, D)[:S]


def _lora(h, ad, t, scale, precision):
    if t not in ad.get("lora", {}):
        return 0.0
    a = _cast(ad["lora"][t]["a"], precision)
    b = _cast(ad["lora"][t]["b"], precision)
    return (h @ a) @ b * scale


def hidden(conf: dict, backbone: dict, adapters: dict, tokens,
           precision: str = "f32"):
    """Final normed hidden states (S, d) of one row. ``adapters`` is one
    adapter tree ({'prefix': {k, v}, 'lora': {...}}, leaves (L, ...))."""
    eps, theta = conf["rms_norm_eps"], conf["rope_theta"]
    p = conf["peft"]
    scale = p["lora_alpha"] / p["lora_rank"]
    nh, nkv = conf["num_attention_heads"], conf["num_key_value_heads"]
    hd = conf["hidden_size"] // nh
    S = tokens.shape[0]
    pos = jnp.arange(S)
    x = jnp.take(_cast(backbone["embed"]["table"], precision), tokens, 0) \
        if precision == "fp8" else \
        jnp.take(backbone["embed"]["table"], tokens, 0).astype(jnp.float32)

    def layer(x, lw):
        w, ad = lw
        a = w["attn"]
        c = lambda t: _cast(t, precision)
        h = _rmsnorm(x, w["ln1"]["scale"], eps)
        q = h @ c(a["wq"]) + a["bq"].astype(jnp.float32) \
            + _lora(h, ad, "q", scale, precision)
        k = h @ c(a["wk"]) + a["bk"].astype(jnp.float32) \
            + _lora(h, ad, "k", scale, precision)
        v = h @ c(a["wv"]) + a["bv"].astype(jnp.float32) \
            + _lora(h, ad, "v", scale, precision)
        q = _rope(q.reshape(S, nh, hd), pos, theta)
        k = _rope(k.reshape(S, nkv, hd), pos, theta)
        v = v.reshape(S, nkv, hd)
        o = _attention(q, k, v, c(ad["prefix"]["k"]), c(ad["prefix"]["v"]))
        o = o.reshape(S, nh * hd)
        x = x + o @ c(a["wo"]) + _lora(o, ad, "o", scale, precision)
        m = w["mlp"]
        h2 = _rmsnorm(x, w["ln2"]["scale"], eps)
        x = x + (jax.nn.silu(h2 @ c(m["gate"])) * (h2 @ c(m["up"]))) \
            @ c(m["down"])
        return x, None

    stack = (backbone["layers"]["g0"]["s0"], adapters["stack"]["g0"]["s0"])

    def step(x, i):
        # the layer's weights are sliced inside the step: scanning over them
        # would keep a copy of every layer as residuals under grad
        return layer(x, jax.tree.map(lambda a: a[i], stack))

    x, _ = jax.lax.scan(jax.checkpoint(step), x,
                        jnp.arange(conf["num_hidden_layers"]))
    return _rmsnorm(x, backbone["final_norm"]["scale"], eps)


def logits(conf, backbone, h, precision: str = "f32"):
    return h @ _cast(backbone["lm_head"]["table"], precision).T


def _ctx(precision: str):
    return jax.default_matmul_precision(
        "highest" if precision == "f32" else "default")


def _ref_logits(conf, backbone, adapters, seq, start, n_gen):
    idx = start + jnp.arange(n_gen)
    with _ctx("f32"):
        h = hidden(conf, backbone, adapters, seq)[idx]
        return logits(conf, backbone, h)                    # (G, V)


@functools.partial(jax.jit, static_argnums=(0, 6))
def served_gaps(conf_items, backbone, adapters, seq, start, served, n_gen):
    """Teacher-forced check of one served row. ``seq`` (W,) is the prompt
    and its served tokens; served token j is predicted at position
    ``start + j``. Returns (G,) gaps: the reference maximum minus the
    reference logit of the served token, in standard deviations of that
    position's reference logits (so that the number does not depend on
    the scale of the logits)."""
    ref = _ref_logits(_thaw(conf_items), backbone, adapters, seq, start,
                      n_gen)
    gap = jnp.max(ref, -1) - jnp.take_along_axis(ref, served[:, None],
                                                 -1)[:, 0]
    return gap / jnp.std(ref, -1)


@functools.partial(jax.jit, static_argnums=(0, 6))
def control_gaps(conf_items, backbone, adapters, seq, start, served, n_gen):
    """The control at the same positions: the gap of the token that the
    fp8 reference puts first."""
    conf = _thaw(conf_items)
    ref = _ref_logits(conf, backbone, adapters, seq, start, n_gen)
    idx = start + jnp.arange(n_gen)
    with _ctx("fp8"):
        hc = hidden(conf, backbone, adapters, seq, "fp8")[idx]
        top = jnp.argmax(logits(conf, backbone, hc, "fp8"), -1)
    gap = jnp.max(ref, -1) - jnp.take_along_axis(ref, top[:, None], -1)[:, 0]
    return gap / jnp.std(ref, -1)


def freeze(conf: dict) -> tuple:
    """Hashable form of the configuration (a static jit argument)."""
    return tuple(sorted((k, _freeze_v(v)) for k, v in conf.items()))


def _freeze_v(v):
    if isinstance(v, dict):
        return ("__d", freeze(v))
    if isinstance(v, list):
        return ("__l", tuple(_freeze_v(x) for x in v))
    return v


def _thaw(items) -> dict:
    def t(v):
        if isinstance(v, tuple) and v and v[0] == "__d":
            return _thaw(v[1])
        if isinstance(v, tuple) and v and v[0] == "__l":
            return [t(x) for x in v[1]]
        return v
    return {k: t(v) for k, v in items}


# -- training ----------------------------------------------------------------

def lm_loss(conf, backbone, adapters, tokens, labels, precision="f32"):
    """Mean next-token cross-entropy over (B, S) rows of one cluster."""
    def row(t, lab):
        h = hidden(conf, backbone, adapters, t, precision)
        lg = logits(conf, backbone, h, precision)
        lse = jax.nn.logsumexp(lg, -1)
        return jnp.mean(lse - jnp.take_along_axis(lg, lab[:, None], -1)[:, 0])
    # one row at a time, its forward recomputed in the backward: only the
    # row's tokens are kept between the two
    return jnp.mean(jax.lax.map(jax.checkpoint(lambda a: row(*a)),
                                (tokens, labels)))


@functools.partial(jax.jit, static_argnums=(0, 5, 6, 7))
def hfsl_round(conf_items, backbone, adapters_c, bank, offset, steps,
               sync_every, hp_items):
    """The HFSL round in float32: per cluster, next-token loss and its
    gradient with respect to the adapters, AdamW (no decay), FedAvg over
    the clusters every ``sync_every`` steps. The adapters are stored in
    the configuration's dtype between steps (``torch_dtype``), the moments
    in float32. ``adapters_c`` leaves carry a
    leading cluster axis; ``bank`` leaves are (E, C, B, S). Returns
    (per-step mean losses, adapters after the round, AdamW first moments)."""
    conf, hp = _thaw(conf_items), dict(hp_items)
    precision = hp["precision"]
    lr, b1, b2, eps = hp["lr"], hp["b1"], hp["b2"], hp["eps"]
    a = jax.tree.map(lambda x: x.astype(jnp.float32), adapters_c)
    # the adapters are held in the configuration's dtype between steps, as
    # the configuration states for every parameter; all arithmetic is f32
    dt = jnp.dtype(conf["torch_dtype"])
    store = lambda x: x.astype(dt).astype(jnp.float32)
    zeros = jax.tree.map(jnp.zeros_like, a)
    E = bank["tokens"].shape[0]

    def cluster(args):
        ad, toks, labs = args
        return jax.value_and_grad(lambda ad_: lm_loss(
            conf, backbone, ad_, toks, labs, precision))(ad)

    def step(carry, s):
        a, m, v = carry
        row = (offset + s) % E
        loss_c, g = jax.lax.map(cluster, (a, bank["tokens"][row],
                                          bank["labels"][row]))
        t = (s + 1).astype(jnp.float32)
        m = jax.tree.map(lambda m_, g_: b1 * m_ + (1 - b1) * g_, m, g)
        v = jax.tree.map(lambda v_, g_: b2 * v_ + (1 - b2) * g_ * g_, v, g)
        a = jax.tree.map(
            lambda p_, m_, v_: store(p_ - lr * (m_ / (1 - b1 ** t))
                                     / (jnp.sqrt(v_ / (1 - b2 ** t)) + eps)),
            a, m, v)
        sync = (s + 1) % sync_every == 0
        a = jax.tree.map(lambda x: jnp.where(sync, store(jnp.broadcast_to(
            jnp.mean(x, 0, keepdims=True), x.shape)), x), a)
        return (a, m, v), jnp.mean(loss_c)

    with _ctx(precision):
        (a, m, _), losses = jax.lax.scan(step, (a, zeros, zeros),
                                         jnp.arange(steps))
    return losses, a, m
