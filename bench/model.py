"""Configurations: one JSON file of sizes per configuration.

A configuration file keeps the published ``config.json`` keys of the model
(``reduced`` lists the ones changed) plus the PEFT settings the system
serves and trains. :func:`program_config` maps it onto the program's own
``ModelConfig`` and refuses any width that disagrees.
"""
from __future__ import annotations

import dataclasses
import json
from pathlib import Path

HERE = Path(__file__).resolve().parent

# published key -> the program's ModelConfig field
_KEYS = {"hidden_size": "d_model", "intermediate_size": "d_ff",
         "num_attention_heads": "n_heads",
         "num_key_value_heads": "n_kv_heads",
         "num_hidden_layers": "n_layers", "vocab_size": "vocab_size",
         "rms_norm_eps": "rms_norm_eps", "rope_theta": "rope_theta",
         "tie_word_embeddings": "tie_embeddings"}


def load_config(name: str) -> dict:
    return json.loads((HERE / "configs" / f"{name}.json").read_text())


def head_dim(conf: dict) -> int:
    return conf["hidden_size"] // conf["num_attention_heads"]


def program_config(conf: dict, *, registered: bool = True):
    """The program's ModelConfig for ``conf``: its registered architecture
    with the file's sizes and PEFT settings. ``registered`` (the default)
    also holds every size against the program's registered configuration,
    cut only in depth; tests pass False to run the same code at toy
    widths."""
    from repro.configs.base import get_config
    base = get_config(conf["program_arch"])
    cfg = base.with_depth(conf["num_hidden_layers"]) if registered else \
        base.with_(**{f: conf[k] for k, f in _KEYS.items()},
                   head_dim=head_dim(conf))
    p = conf["peft"]
    cfg = cfg.with_(dtype=conf["torch_dtype"], peft=dataclasses.replace(
        cfg.peft, n_prefix=p["n_prefix"], lora_rank=p["lora_rank"],
        lora_alpha=p["lora_alpha"], lora_targets=tuple(p["lora_targets"]),
        head_dim_out=0))
    for k, field in _KEYS.items():
        if getattr(cfg, field) != conf[k]:
            raise ValueError(f"{conf['name']}: {k}={conf[k]} but the program's "
                             f"{conf['program_arch']} has {field}="
                             f"{getattr(cfg, field)}")
    if cfg.head_dim_ != head_dim(conf) or not cfg.qkv_bias:
        raise ValueError(f"{conf['name']}: head size or QKV bias differs "
                         "from the program's configuration")
    return cfg
