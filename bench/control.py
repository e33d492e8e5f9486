#!/usr/bin/env python3
"""Readings that set each limit of ``correct`` (``limits/<cell>.json``).

For every seed, in one process on the chip, at the cell's own sizes:

- serving cells: a short window of the cell's traffic through the program,
  then the program's widest gap (the number a run compares) and the
  control's: the fp8 reference put in the program's place, read at the
  same positions of the same prompts and served tokens;
- training cells: the set-up round through the program and its three
  numbers, the same numbers for the fp8 control, and for the reference
  with half of each cluster's batch left out (a fault a training cell can
  have). A round that returns its state unchanged reads 1 by
  ``update_gap``'s measure and needs no run.

    python3 bench/control.py --workload <cell> --seeds 1 2 3 --seconds 10

Prints one JSON line per seed and writes them to ``--out``. The
benchmark's own runs never run the control.
"""
from __future__ import annotations

import argparse
import importlib
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))
sys.path.insert(0, str(ROOT / "src"))

from bench import run as RUN  # noqa: E402


class _Args:
    def __init__(self, seed, seconds):
        self.seed, self.seconds, self.trace = seed, seconds, 0


def main(argv=None, *, require_tpu: bool = True, conf=None,
         traffic=None) -> list:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--control-seeds", type=int, default=None,
                    help="read the control and the faults on only the first "
                         "N seeds (the program's readings on all)")
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)
    import jax
    RUN.setup_compile_cache()
    if require_tpu and jax.devices()[0].platform != "tpu":
        print("control: needs a TPU", file=sys.stderr)
        sys.exit(3)
    from bench import check
    from bench import model as BM
    from bench import traffic as TR
    spec = RUN.load_spec()
    cell = {w["name"]: w for w in spec["workloads"]}[args.workload]
    conf = conf or BM.load_config(cell["config"])
    traffic = traffic or TR.load(cell["traffic"])
    cfg = BM.program_config(conf, registered=require_tpu)
    peaks = json.loads((RUN.HERE / "peaks.json").read_text())["devices"]
    peak = peaks.get(jax.devices()[0].device_kind, peaks["TPU v5 lite"])
    driver = importlib.import_module(f"bench.drivers.{traffic['driver']}")
    rows = []
    for seed in args.seeds:
        t = time.perf_counter()
        ctx = RUN.Context(_Args(seed, args.seconds), spec, cell, conf,
                          traffic, cfg, peak)
        readings = {}
        with_control = args.control_seeds is None or \
            len(rows) < args.control_seeds

        def serving(backbone, adapters, rec, ctx=ctx, readings=readings,
                    with_control=with_control):
            rows_ = check.sample(rec, traffic["check_requests"], ctx.seed)
            readings["program"] = {"max_gap": max(check.serving_gaps(
                conf, traffic, backbone, adapters, rows_))}
            if with_control:
                readings["control"] = {"max_gap": max(check.serving_gaps(
                    conf, traffic, backbone, adapters, rows_, control=True))}
            return {}

        def training(backbone, before, bank_np, first, readings=readings,
                     with_control=with_control):
            ref = check.reference_round(conf, traffic, backbone, before,
                                        bank_np)
            readings["program"] = check.compare_rounds(first, ref, before)
            if not with_control:
                return {}
            readings["control"] = check.compare_rounds(check.reference_round(
                conf, traffic, backbone, before, bank_np, "fp8"), ref, before)
            half = {k: v[:, :, :traffic["batch"] // 2]
                    for k, v in bank_np.items()}
            readings["half_batch"] = check.compare_rounds(
                check.reference_round(conf, traffic, backbone, before, half),
                ref, before)
            return {}

        ctx.check_serving, ctx.check_training = serving, training
        ctx.warm = not rows
        res = driver.run(ctx)
        row = {"seed": seed, "s": round(time.perf_counter() - t, 1),
               "compiles": res["compiles"], **readings}
        print(json.dumps(row), flush=True)
        rows.append(row)
    if args.out:
        Path(args.out).parent.mkdir(parents=True, exist_ok=True)
        Path(args.out).write_text("".join(json.dumps(r) + "\n" for r in rows))
    return rows


if __name__ == "__main__":
    main()
