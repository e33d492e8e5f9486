#!/usr/bin/env python3
"""Run one benchmark cell once on the chip and print one JSON line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell (``BENCHMARK.json``: ``workloads``) names a configuration
(``bench/configs/<name>.json``) and a traffic mix
(``bench/traffic/<name>.json``); the mix names the driver that runs it
(``bench/drivers/<driver>.py``). ``--trace 0`` measures the cell's
end-to-end metrics with the profiler off; ``--trace 1`` traces the window
and reports the cell's per-layer metrics, each read by its own reader
(``bench/metrics/<name>.py``). Every run checks what the timed path
produced against the configuration's plain reference (``check.py``) and
prints each number compared beside its limit, as the last lines of
standard error and under ``checks``, the last key of the result line.

Refuses to run (exit 3, no result) without a TPU or with fewer chips than
the cell asks for. JAX's compilation cache is kept at ``.jax_cache/`` in
the checkout, with no minimum compile time, so only the first run of a
cell in a checkout compiles.
"""
from __future__ import annotations

import argparse
import gc
import importlib.util
import json
import os
import shutil
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
HERE = ROOT / "bench"
CACHE_DIR = ROOT / ".jax_cache"
TRACE_DIR = ROOT / ".bench_trace"


def process_start() -> float:
    """perf_counter() reading at which this process started (Linux)."""
    now = time.perf_counter()
    try:
        start_ticks = int(Path("/proc/self/stat").read_text()
                          .rsplit(")", 1)[1].split()[19])
        uptime = float(Path("/proc/uptime").read_text().split()[0])
        return now - (uptime - start_ticks / os.sysconf("SC_CLK_TCK"))
    except (OSError, ValueError, IndexError):
        return now


T_PROCESS = process_start()


def setup_compile_cache() -> None:
    """JAX's persistent cache at one fixed path in the checkout, every
    program kept (no minimum compile time, no eviction)."""
    import jax
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_compilation_cache_max_size", -1)


def load_spec() -> dict:
    return json.loads((ROOT / "BENCHMARK.json").read_text())


def cell_metrics(spec: dict, workload: str, per_layer: bool) -> list:
    """The metrics a run of ``workload`` reports."""
    e2e = [m for m in spec["end_to_end"]
           if workload in m.get("workloads", [workload])]
    if not per_layer:
        return e2e
    names = {m["name"] for m in e2e}
    return [m for m in spec["per_layer"]
            if workload in m.get("workloads", [workload] if m["moves"]
                                 in names else [])]


def load_reader(name: str):
    path = HERE / "metrics" / f"{name}.py"
    mod_spec = importlib.util.spec_from_file_location(
        f"bench_metric_{name.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    return mod.read


class Context:
    """What a driver needs from the harness, and what readers read."""

    def __init__(self, args, spec, cell, conf, traffic, cfg, peak):
        self.seed, self.seconds, self.trace = args.seed, args.seconds, \
            args.trace
        self.spec, self.cell, self.workload = spec, cell, cell["name"]
        self.conf, self.traffic, self.cfg, self.peak = conf, traffic, cfg, \
            peak
        self.chips = cell["chips"]
        self.t_setup_done = None
        self.trace_path = None
        self._anno = None
        self.t_anchor = None
        self.warm = True

    def mark_setup_done(self) -> None:
        self.t_setup_done = time.perf_counter()

    def start_trace(self) -> None:
        if not self.trace:
            return
        import jax
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        TRACE_DIR.mkdir(parents=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(str(TRACE_DIR), profiler_options=opts)
        self._anno = jax.profiler.TraceAnnotation("bench.window")
        self._anno.__enter__()
        self.t_anchor = time.perf_counter()

    def stop_trace(self) -> None:
        if not self.trace:
            return
        import jax
        self._anno.__exit__(None, None, None)
        self.t_anchor_end = time.perf_counter()
        jax.profiler.stop_trace()
        found = sorted(TRACE_DIR.glob("plugins/profile/*/*.xplane.pb"))
        self.trace_path = found[-1] if found else None

    def memory_peak(self) -> int:
        import jax
        devs = jax.devices()[:self.chips]
        return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
                   for d in devs)

    def check_serving(self, backbone, adapters, rec) -> dict:
        from bench import check
        return check.serving(self, backbone, adapters, rec)

    def check_training(self, backbone, before, bank_np, first) -> dict:
        from bench import check
        return check.training(self, backbone, before, bank_np, first)


def device_summary(chips: int) -> dict:
    import jax
    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": min(len(devs), chips)}


def main(argv=None, *, require_tpu: bool = True, conf=None,
         traffic=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if args.seed < 0:
        ap.error("--seed must be a non-negative whole number")

    spec = load_spec()
    cells = {w["name"]: w for w in spec["workloads"]}
    if args.workload not in cells:
        ap.error(f"unknown workload {args.workload!r}; known: {sorted(cells)}")
    cell = cells[args.workload]

    sys.path.insert(0, str(ROOT / "src"))
    sys.path.insert(0, str(ROOT))
    import jax
    setup_compile_cache()
    devs = jax.devices()
    if require_tpu and (devs[0].platform != "tpu" or
                        len(devs) < cell["chips"]):
        print(f"bench: the cell {cell['name']} needs {cell['chips']} TPU "
              f"chip(s); JAX sees {len(devs)} {devs[0].platform} device(s)",
              file=sys.stderr)
        return 3
    peaks = json.loads((HERE / "peaks.json").read_text())["devices"]
    kind = devs[0].device_kind
    if require_tpu and kind not in peaks:
        print(f"bench: no peaks for device kind {kind!r} in bench/peaks.json",
              file=sys.stderr)
        return 3
    peak = peaks.get(kind, peaks["TPU v5 lite"])

    from bench import model as BM
    from bench import traffic as TR
    conf = conf or BM.load_config(cell["config"])
    traffic = traffic or TR.load(cell["traffic"])
    cfg = BM.program_config(conf, registered=require_tpu)
    ctx = Context(args, spec, cell, conf, traffic, cfg, peak)
    driver = importlib.import_module(f"bench.drivers.{traffic['driver']}")
    res = driver.run(ctx)
    ctx.result = res
    if res["compiles"]:
        print(f"bench: {res['compiles']} program(s) compiled inside the "
              f"window: {res['compiled']}", file=sys.stderr)
        return 1

    setup_s = ctx.t_setup_done - T_PROCESS
    dev = {**device_summary(cell["chips"]),
           "memory_peak_bytes": res["memory_peak_bytes"]}
    metrics = {}
    breakdown = None
    if not args.trace:
        vals = {**res["end_to_end"], "setup_s": setup_s}
        for m in cell_metrics(spec, cell["name"], per_layer=False):
            metrics[m["name"]] = {"value": vals[m["name"]], "unit": m["unit"]}
    else:
        from bench import trace_reduce
        ctx.reduced = trace_reduce.reduce(
            ctx.trace_path, anchor="bench.window", chips=cell["chips"],
            host_spans=ctx_spans(ctx, res)) if ctx.trace_path else None
        if ctx.reduced:
            dev["busy_s"] = ctx.reduced["busy_s"]
            dev["window_s"] = ctx.reduced["window_s"]
            breakdown = ctx.reduced["breakdown"]
        for m in cell_metrics(spec, cell["name"], per_layer=True):
            v = load_reader(m["name"])(ctx)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}

    checks = {k: v for k, v in res["check"].items() if not k.startswith("_")}
    correct = all(v["value"] <= v["limit"] for v in checks.values())
    line = {"correct": correct, "attempted": res["attempted"],
            "failed": res["failed"], "metrics": metrics, "device": dev}
    if breakdown:
        line["breakdown"] = breakdown
    line["info"] = {k: v for k, v in res.items() if k in (
        "rounds", "tokens", "steps", "cap")}
    line["info"].update(res["check"].get("_info", {}))
    line["checks"] = checks
    gc.collect()
    for k, v in checks.items():
        print(f"check {k} {v['value']!r} limit {v['limit']!r} "
              f"{'ok' if v['value'] <= v['limit'] else 'FAILED'}",
              file=sys.stderr)
    print(json.dumps(line), flush=True)
    return 0


def ctx_spans(ctx, res) -> list:
    """Host spans on the profiler's clock: the engine's own spans (read from
    its telemetry) shifted by the window anchor, as (name, t0, t1) in s."""
    spans = [("bench.window", ctx.t_anchor, ctx.t_anchor_end)]
    spans += [(name, t0, t1) for name, t0, t1, _ in res.get("spans", [])]
    return spans


if __name__ == "__main__":
    sys.exit(main())
