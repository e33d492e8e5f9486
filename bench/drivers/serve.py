"""Serving driver: a mix of requests through ``DecodeEngine``.

Open loop (``rate_rps``): requests are due on a schedule over the window
and handed to ``DecodeEngine.serve_trace``, which takes each into its
queue when it is due. Closed loop (``backlog_per_s``): a backlog larger
than the window can serve is queued at the start.

Either way the window ends with a cut: each request carries a deadline at
the window's end, set when the engine takes it in, so the engine retires
the rows still decoding at its next segment boundary (their tokens count)
and those still queued with none. Output tok/s is every token served over
the whole window, to that boundary.

Set-up warms every program shape the mix can reach, through the engine's
own entry points: for each pow2 prompt width the initial wave and the
refills of 1, 2, 4, ... rows, and every pow2 decode segment up to the
longest output. A request whose prompt + budget reaches the window's
cache capacity and whose deadline is 0 rides along in each warm-up drain,
so that every drain runs at the window's capacity without decoding it.
"""
from __future__ import annotations

import gc
import time

import numpy as np

from bench import traffic as TR
from bench import weights as W


def pow2ceil(n: int) -> int:
    return 1 << max(int(n) - 1, 0).bit_length()


def pow2floor(n: int) -> int:
    return 1 << (int(n).bit_length() - 1)


def cut_metrics(rec: list, t0: float) -> tuple[dict, int, float]:
    """Output tok/s: every token served from the window's start to the
    segment boundary that ended it, over that whole time. Returns
    (metrics, tokens, end of the window)."""
    served = [r for r in rec if r["n"] > 0]
    t_end = max(r["last"] for r in served)
    tokens = sum(r["n"] for r in served)
    return {"output_tok_s": tokens / (t_end - t0)}, tokens, t_end


def warmup_drains(slots: int, prompt_len: dict, output_len: dict, cap: int,
                  vocab: int, domain) -> list[list[tuple]]:
    """Arrival lists (all due at 0) that drive every shape the engine can
    use for this mix. ``slots`` must be a power of two."""
    if slots & (slots - 1):
        raise ValueError(f"slots must be a power of two, got {slots}")
    m = slots.bit_length() - 1
    rng = np.random.default_rng(0)
    kw = {} if domain is None else {"domain": domain}
    lo, hi = prompt_len["min"], prompt_len["max"]

    def req(n, gen, **extra):
        return (0.0, rng.integers(0, vocab, n, dtype=np.int32), gen,
                {**kw, **extra})

    cap_row = req(lo, cap - lo, deadline_s=0.0)
    drains = []
    S = pow2ceil(lo)
    while S <= pow2ceil(hi):
        P = min(max(S, lo), hi)
        # initial wave: groups of 1, 2, ... 2^(m-1) rows retire after steps
        # 1..m, the last row after m+1; each group's slots are refilled
        # by as many queued rows, which all retire at step m+1 with the
        # last row; then one refill of every slot
        wave = []
        for j in range(m):
            wave += [req(P, j + 1) for _ in range(1 << j)]
        wave.append(req(P, m + 1))
        refills = []
        for j in range(m):
            refills += [req(P, m - j) for _ in range(1 << j)]
        final = [req(P, 1) for _ in range(slots)]
        drains.append(wave + refills + final + [cap_row])
        S *= 2
    longest = 2 * pow2floor(output_len["max"]) - 1
    drains.append([req(lo, longest), cap_row])
    return drains


def _stamp_submits(engine, log: list, cut_at: list):
    """Record when the engine takes each request into its queue, and give
    it its deadline at ``cut_at[0]`` (when set) from that moment."""
    submit = engine.submit

    def stamped(*a, **k):
        if cut_at[0] is not None:
            k["deadline_s"] = max(0.0, cut_at[0] - time.perf_counter())
        uid = submit(*a, **k)
        log.append((uid, time.perf_counter()))
        return uid

    engine.submit = stamped


def run(ctx) -> dict:
    import jax
    from repro.core.adapter_bank import AdapterBank
    from repro.core.telemetry import Telemetry
    from repro.launch.engine import DecodeEngine
    from repro.analysis.guards import compile_guard

    conf, tr, cfg = ctx.conf, ctx.traffic, ctx.cfg
    dom = tr.get("domains")
    n_ad = dom["count"] if dom else 1
    backbone, adapters = W.make(conf, ctx.seed, n_ad)
    W.check_layout(cfg, backbone, adapters)
    bank = None
    if dom:
        bank = AdapterBank.create({f"d{i}": W.adapter(adapters, i)
                                   for i in range(n_ad)})
        params = bank.serving_params(backbone)
    else:
        params = {"backbone": backbone, "adapters": W.adapter(adapters, 0)}
    jax.block_until_ready(params)
    tel = Telemetry(enabled=bool(ctx.trace))
    engine = DecodeEngine(cfg, slots=tr["slots"], bank=bank, tel=tel)

    reqs = TR.serve_requests(tr, ctx.seed, ctx.seconds, conf["vocab_size"])
    cap = pow2ceil(tr["prompt_len"]["max"] + tr["output_len"]["max"])
    got = pow2ceil(max(len(r["prompt"]) + r["gen"] for r in reqs))
    if got != cap:
        raise ValueError(f"the mix's cache capacity {got} differs from the "
                         f"capacity {cap} that set-up warms")
    # a process that has already warmed this cell's programs (the
    # control's later seeds) may skip the drains
    for arrivals in ([] if not ctx.warm else warmup_drains(
            tr["slots"], tr["prompt_len"], tr["output_len"], cap,
            conf["vocab_size"], "d0" if dom else None)):
        engine.serve_trace(params, arrivals)
    tel.reset()
    epoch = time.perf_counter()      # the spans' zero, as reset() set it
    ctx.mark_setup_done()

    log: list = []
    cut_at = [None]
    _stamp_submits(engine, log, cut_at)
    ctx.start_trace()
    with compile_guard(max_compiles=None) as compiles:
        t0 = time.perf_counter()
        cut_at[0] = t0 + ctx.seconds
        if "rate_rps" in tr:
            arrivals = [(r["t"], r["prompt"], r["gen"],
                         {} if r["domain"] is None else
                         {"domain": r["domain"]}) for r in reqs]
            comps, stats = engine.serve_trace(params, arrivals)
        else:
            for r in reqs:
                engine.submit(r["prompt"], r["gen"],
                              **({} if r["domain"] is None else
                                 {"domain": r["domain"]}))
            comps, stats = engine.run(params)
        t_done = time.perf_counter()
    ctx.stop_trace()

    by_uid = {c.uid: c for c in comps}
    t_sub = dict(log)
    uids = [u for u, _ in log]
    rec = []
    for i, (r, uid) in enumerate(zip(reqs, uids)):
        c = by_uid.get(uid)
        n = 0 if c is None else len(c.tokens)
        row = {"uid": uid, "prompt": r["prompt"], "gen": r["gen"],
               "domain": r["domain"], "n": n,
               "tokens": None if c is None else np.asarray(c.tokens)}
        if c is not None and c.ttft_s is not None:
            row["first"] = t_sub[uid] + c.ttft_s
            row["last"] = t_sub[uid] + c.latency_s
        row["timed_out"] = bool(c is not None and c.timed_out)
        rec.append(row)

    out = {"requests": rec, "stats": stats, "t0": t0, "t_done": t_done,
           "compiles": compiles.count, "compiled": list(compiles.names),
           "spans": [(s.name, epoch + s.t0, epoch + s.t0 + s.dur, s.args)
                     for s in tel.spans], "cap": cap}
    out["end_to_end"], out["tokens"], out["window_end"] = \
        cut_metrics(rec, t0)
    # rows admitted before the cut: completed, or cut at the window's end
    # (neither completed nor failed); rows never admitted are not attempted
    served = [r for r in rec if r["n"] > 0]
    out["attempted"] = len(served)
    out["failed"] = sum(1 for r in served
                        if not r["timed_out"] and r["n"] != r["gen"])
    out["memory_peak_bytes"] = ctx.memory_peak()
    del engine, params, bank
    gc.collect()
    out["check"] = ctx.check_serving(backbone, adapters, rec)
    return out
