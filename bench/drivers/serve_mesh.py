"""Serving driver on a mesh: a mix of requests through one
``DecodeEngine`` whose waves are split over the chips of one host.

The window, its cut at the window's end, the warm-up drains and what is
recorded of each request are those of ``serve.py``, whose helpers this
driver imports. What differs:

- the mesh is the configuration's ``mesh`` (``{"data": d, "model": m}``)
  over the first d x m devices, and the engine is built with it;
- the seeded weights are drawn straight into their placed shardings: the
  values of ``weights.make``, leaf for leaf, each leaf placed where the
  program's serving rules put that parameter (``rules.named_shardings``
  over ``serving_rules``), so no device ever holds the whole model. The
  benchmark owns the values, the program the placement;
- ``check._info`` (the result line's ``info``) carries what the program
  reports of the mesh: the calls of each kernel traced under the mesh
  (``mesh_kernel_calls``) and how many of them ran whole on every device
  because a dim did not divide (``unsplit_kernel_calls``), from the
  process-wide telemetry; and, in a traced run, the resident cache's
  bytes, whole and on each device (the engine's gauges). A program that
  reports none of these leaves them out.

A closed loop with one adapter: a mix with ``rate_rps`` or ``domains``
is refused.
"""
from __future__ import annotations

import gc
import math
import time

import numpy as np

from bench import traffic as TR
from bench import weights as W
from bench.drivers.serve import (_stamp_submits, cut_metrics, pow2ceil,
                                 warmup_drains)


def make_mesh(conf: dict):
    """The configuration's mesh over the first devices JAX sees."""
    import jax
    from jax.sharding import Mesh
    axes = tuple(conf["mesh"])
    shape = tuple(conf["mesh"][a] for a in axes)
    devs = jax.devices()
    if len(devs) < math.prod(shape):
        raise ValueError(f"the mesh {conf['mesh']} needs {math.prod(shape)} "
                         f"devices; JAX sees {len(devs)}")
    return Mesh(np.asarray(devs[:math.prod(shape)]).reshape(shape), axes)


def placements(cfg, mesh, n_adapters: int) -> dict:
    """{path of ``weights.shapes``: sharding}: each parameter where the
    program's serving rules place it; a stacked adapter leaf keeps its
    leading axis of ``n_adapters`` whole."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.models import model as M
    from repro.sharding import rules as R
    sh = R.named_shardings(M.model_spec(cfg), mesh, R.serving_rules())
    out = {}
    for path, s in jax.tree_util.tree_flatten_with_path(sh)[0]:
        key = "/".join(str(p.key) for p in path)
        if key.startswith("adapters/"):
            s = NamedSharding(mesh, P(None, *s.spec))
        out[key] = s
    return out


def make_placed(conf: dict, seed: int, n_adapters: int, shardings: dict):
    """(backbone, adapters) with the values of ``weights.make(conf, seed,
    n_adapters)``, each leaf drawn on the devices of its sharding. One
    jitted program."""
    import jax
    spec = tuple((k, tuple(s), dt, init) for k, (s, dt, init)
                 in sorted(W.shapes(conf, n_adapters).items()))

    def make(key):
        keys = jax.random.split(key, len(spec))
        return tuple(W._draw(k, s, dt, init)
                     for k, (_, s, dt, init) in zip(keys, spec))

    leaves = jax.jit(make, out_shardings=tuple(
        shardings[k] for k, *_ in spec))(
        jax.random.fold_in(W.seed_key(seed), 0x7E1))
    tree = W._nest({k: v for (k, *_), v in zip(spec, leaves)})
    return tree["backbone"], tree["adapters"]


def mesh_info(kernel_tel, engine_tel) -> dict:
    """What the program reported of the mesh (see the module docstring)."""
    pre = "kernels.mesh_calls."
    calls = {k[len(pre):]: int(v) for k, v in kernel_tel.counters.items()
             if k.startswith(pre)}
    info = {}
    if calls:
        info["mesh_kernel_calls"] = calls
        info["unsplit_kernel_calls"] = int(sum(
            kernel_tel.counters.get(f"kernels.unsplit.{k}", 0)
            for k in calls))
    for g in ("cache_bytes", "cache_bytes_per_device"):
        if f"engine.{g}" in engine_tel.gauges:
            info[g] = int(engine_tel.gauges[f"engine.{g}"])
    return info


def run(ctx) -> dict:
    import jax
    from repro.analysis.guards import compile_guard
    from repro.core import telemetry
    from repro.core.telemetry import Telemetry
    from repro.launch.engine import DecodeEngine
    from repro.models import model as M

    conf, tr, cfg = ctx.conf, ctx.traffic, ctx.cfg
    if "rate_rps" in tr or tr.get("domains"):
        raise ValueError("the mesh driver serves a closed loop with one "
                         "adapter; the mix has an arrival rate or domains")
    # kernels count their mesh calls as they are traced: switch the
    # process-wide telemetry on before the first trace, keeping what an
    # earlier seed of this process counted
    kernel_tel = telemetry.enable(fresh=False)
    mesh = make_mesh(conf)
    backbone, adapters = make_placed(conf, ctx.seed, 1,
                                     placements(cfg, mesh, 1))
    W.check_layout(cfg, backbone, adapters)
    params = M.place_params({"backbone": backbone,
                             "adapters": W.adapter(adapters, 0)}, cfg, mesh)
    jax.block_until_ready(params)
    tel = Telemetry(enabled=bool(ctx.trace))
    engine = DecodeEngine(cfg, slots=tr["slots"], mesh=mesh, tel=tel)

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
            conf["vocab_size"], None)):
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
        for r in reqs:
            engine.submit(r["prompt"], r["gen"])
        comps, stats = engine.run(params)
        t_done = time.perf_counter()
    ctx.stop_trace()

    by_uid = {c.uid: c for c in comps}
    t_sub = dict(log)
    rec = []
    for r, (uid, _) in zip(reqs, log):
        c = by_uid.get(uid)
        n = 0 if c is None else len(c.tokens)
        row = {"uid": uid, "prompt": r["prompt"], "gen": r["gen"],
               "domain": None, "n": n,
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
    served = [r for r in rec if r["n"] > 0]
    out["attempted"] = len(served)
    out["failed"] = sum(1 for r in served
                        if not r["timed_out"] and r["n"] != r["gen"])
    out["memory_peak_bytes"] = ctx.memory_peak()
    info = mesh_info(kernel_tel, tel)
    del engine, params
    gc.collect()
    out["check"] = ctx.check_serving(backbone, adapters, rec)
    out["check"].setdefault("_info", {}).update(info)
    return out
