"""Seeded weights, made on the device in one jitted call.

The benchmark makes the weights itself, in the layout the program's
parameter tree has (``models/model.py``: ``backbone`` and ``adapters``),
so that the reference can use the same values without taking anything the
program made. :func:`check_layout` holds the tree against the program's
own spec, leaf for leaf.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from bench.model import head_dim


def seed_key(seed: int) -> jax.Array:
    """A PRNG key for any non-negative seed, also beyond 32 bits."""
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0xFFFFFFFF)


def shapes(conf: dict, n_adapters: int) -> dict:
    """{path: (shape, dtype, init)} of every leaf."""
    d, ff, V = conf["hidden_size"], conf["intermediate_size"], conf["vocab_size"]
    nh, nkv, hd = (conf["num_attention_heads"], conf["num_key_value_heads"],
                   head_dim(conf))
    L, p = conf["num_hidden_layers"], conf["peft"]
    dt = conf["torch_dtype"]
    out = {
        "backbone/embed/table": ((V, d), dt, "embed"),
        "backbone/final_norm/scale": ((d,), "float32", "norm"),
        "backbone/lm_head/table": ((V, d), dt, "embed"),
        "backbone/layers/g0/s0/ln1/scale": ((L, d), "float32", "norm"),
        "backbone/layers/g0/s0/ln2/scale": ((L, d), "float32", "norm"),
        "backbone/layers/g0/s0/attn/wq": ((L, d, nh * hd), dt, "fan_in"),
        "backbone/layers/g0/s0/attn/wk": ((L, d, nkv * hd), dt, "fan_in"),
        "backbone/layers/g0/s0/attn/wv": ((L, d, nkv * hd), dt, "fan_in"),
        "backbone/layers/g0/s0/attn/wo": ((L, nh * hd, d), dt, "fan_in"),
        "backbone/layers/g0/s0/attn/bq": ((L, nh * hd), dt, "bias"),
        "backbone/layers/g0/s0/attn/bk": ((L, nkv * hd), dt, "bias"),
        "backbone/layers/g0/s0/attn/bv": ((L, nkv * hd), dt, "bias"),
        "backbone/layers/g0/s0/mlp/gate": ((L, d, ff), dt, "fan_in"),
        "backbone/layers/g0/s0/mlp/up": ((L, d, ff), dt, "fan_in"),
        "backbone/layers/g0/s0/mlp/down": ((L, ff, d), dt, "fan_in"),
    }
    n = (n_adapters,)
    a = "adapters/stack/g0/s0/"
    out[a + "prefix/k"] = (n + (L, p["n_prefix"], nkv, hd), dt, "prefix")
    out[a + "prefix/v"] = (n + (L, p["n_prefix"], nkv, hd), dt, "prefix")
    width = {"q": nh * hd, "k": nkv * hd, "v": nkv * hd, "o": d}
    for t in p["lora_targets"]:
        n_in = d if t != "o" else nh * hd
        out[a + f"lora/{t}/a"] = (n + (L, n_in, p["lora_rank"]), dt, "fan_in")
        out[a + f"lora/{t}/b"] = (n + (L, p["lora_rank"], width[t]), dt,
                                  "lora_b")
    return out


def _draw(key, shape, dtype, init):
    z = jax.random.normal(key, shape, jnp.float32)
    if init == "fan_in":
        z = z / jnp.sqrt(jnp.float32(shape[-2]))
    elif init == "norm":
        z = 1.0 + 0.02 * z
    elif init in ("embed", "bias", "lora_b"):
        z = 0.02 * z
    return z.astype(dtype)


@functools.lru_cache(maxsize=8)
def _maker(spec: tuple):
    def make(key):
        keys = jax.random.split(key, len(spec))
        return tuple(_draw(k, s, dt, init)
                     for k, (_, s, dt, init) in zip(keys, spec))
    return jax.jit(make)


def _nest(flat: dict) -> dict:
    out: dict = {}
    for path, v in flat.items():
        node = out
        parts = path.split("/")
        for p in parts[:-1]:
            node = node.setdefault(p, {})
        node[parts[-1]] = v
    return out


def make(conf: dict, seed: int, n_adapters: int = 1):
    """(backbone, adapters): ``adapters`` carries a leading axis of
    ``n_adapters`` distinct adapter trees. One jitted program."""
    spec = tuple((k, tuple(s), dt, init)
                 for k, (s, dt, init) in sorted(shapes(conf, n_adapters).items()))
    leaves = _maker(spec)(jax.random.fold_in(seed_key(seed), 0x7E1))
    tree = _nest({k: v for (k, *_), v in zip(spec, leaves)})
    return tree["backbone"], tree["adapters"]


def adapter(adapters: dict, i: int) -> dict:
    """The ``i``-th adapter tree of a stacked set."""
    return jax.tree.map(lambda x: x[i], adapters)


def check_layout(cfg, backbone: dict, adapters: dict) -> None:
    """Hold the benchmark's tree against the program's parameter spec."""
    from repro.models import model as M
    from repro.sharding.rules import ParamSpec
    spec = M.model_spec(cfg)
    want = jax.tree.map(lambda s: (tuple(s.shape), jnp.dtype(s.dtype)), spec,
                        is_leaf=lambda x: isinstance(x, ParamSpec))
    got = jax.tree.map(lambda x: (tuple(x.shape), x.dtype),
                       {"backbone": backbone, "adapters": adapter(adapters, 0)})
    if want != got:
        raise ValueError("the benchmark's weights do not have the layout of "
                         "the program's parameter tree")
