"""The one generator of traffic: reads a mix's parameters from
``traffic/<name>.json`` and makes its requests or batches from the seed.

Every seed gets the same multiset of sizes and gaps, in another order:
lengths are the distribution's quantiles at i / (n - 1), so the shortest
and the longest always occur, the gaps of an open loop are the
exponential's quantiles at (i + 0.5) / n scaled to fill the window, and
domains are counted out by their Zipf shares. An open loop may start with
a ``backlog`` of requests due at once, the state of a system that has
been offered more than it serves for a while. The seed permutes them and
draws the token ids. So a seed changes which request is which, never how
much work a run holds.
"""
from __future__ import annotations

import json
import math
from pathlib import Path

import numpy as np

HERE = Path(__file__).resolve().parent


def load(name: str) -> dict:
    return json.loads((HERE / "traffic" / f"{name}.json").read_text())


def quantiles(dist: dict, n: int) -> np.ndarray:
    """n integer sizes at the distribution's i / (n - 1) quantiles (both
    ends clipped to the mix's bounds)."""
    u = np.arange(n) / max(n - 1, 1)
    lo, hi = dist["min"], dist["max"]
    if dist["dist"] == "lognormal":
        from statistics import NormalDist
        z = np.array([NormalDist().inv_cdf(float(np.clip(x, 1e-12, 1 - 1e-12)))
                      for x in u])
        v = dist["median"] * np.exp(dist["sigma"] * z)
    elif dist["dist"] == "uniform":
        v = lo + u * (hi - lo)
    else:
        raise ValueError(f"unknown distribution {dist['dist']!r}")
    return np.clip(np.floor(v), lo, hi).astype(np.int64)


def zipf_counts(k: int, s: float, n: int) -> np.ndarray:
    """Requests per domain: n split by Zipf(s) shares, largest remainder."""
    w = 1.0 / np.arange(1, k + 1) ** s
    share = w / w.sum() * n
    c = np.floor(share).astype(np.int64)
    for i in np.argsort(-(share - c))[:n - c.sum()]:
        c[i] += 1
    return c


def request_count(traffic: dict, seconds: float) -> int:
    if "rate_rps" in traffic:
        return traffic.get("backlog", 0) + max(
            1, round(traffic["rate_rps"] * seconds))
    return max(traffic["min_requests"],
               math.ceil(traffic["backlog_per_s"] * seconds))


def serve_requests(traffic: dict, seed: int, seconds: float,
                   vocab: int) -> list[dict]:
    """Requests of a serving mix: {'t' (due offset, s), 'prompt', 'gen',
    'domain'}, in order of due time."""
    rng = np.random.default_rng(seed)
    n = request_count(traffic, seconds)
    prompt = rng.permutation(quantiles(traffic["prompt_len"], n))
    gen = rng.permutation(quantiles(traffic["output_len"], n))
    if "rate_rps" in traffic:
        k = traffic.get("backlog", 0)
        u = (np.arange(n - k) + 0.5) / (n - k)
        gaps = rng.permutation(-np.log1p(-u))
        t = np.concatenate([np.zeros(k), np.cumsum(gaps / gaps.sum()
                                                  * seconds)])
    else:
        t = np.zeros(n)
    dom = traffic.get("domains")
    if dom:
        ids = np.repeat(np.arange(dom["count"]),
                        zipf_counts(dom["count"], dom["zipf"], n))
        doms = [f"d{i}" for i in rng.permutation(ids)]
    else:
        doms = [None] * n
    return [{"t": float(t[i]), "prompt": rng.integers(0, vocab, int(prompt[i]),
                                                    dtype=np.int32),
             "gen": int(gen[i]), "domain": doms[i]} for i in range(n)]


def train_bank(traffic: dict, seed: int, vocab: int) -> dict:
    """An epoch bank of distinct rows: tokens/labels (E, C, B, S)."""
    rng = np.random.default_rng(seed)
    shape = (traffic["epoch_rows"], traffic["clusters"], traffic["batch"],
             traffic["seq"] + 1)
    toks = rng.integers(0, vocab, size=shape, dtype=np.int32)
    return {"tokens": toks[..., :-1], "labels": toks[..., 1:]}
