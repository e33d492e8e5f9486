"""Benchmark driver — one entry per paper table/figure (+ kernels, roofline).

Prints ``name,us_per_call,derived`` CSV lines (benchmarks/common.emit).
With ``--json PATH`` the same results are also written machine-readable:
one record per bench with name/status/wall seconds plus every CSV metric
line the bench emitted (for dashboards and regression diffing — the CSV
stream on stdout is unchanged).

Usage: PYTHONPATH=src python -m benchmarks.run [--only fig6,table5]
       [--json results.json]
"""
from __future__ import annotations

import argparse
import contextlib
import io
import json
import sys
import time
import traceback

ALL = ["table5_scheduler", "fig2_comm", "kernels_bench", "decode_bench",
       "serve_bench", "ragged_bench", "latency_bench", "spec_bench",
       "finetune_bench", "shard_bench", "chaos_bench", "telemetry_bench",
       "fig6_pretraining", "fig7_peft", "table3_noniid", "table4_clusters",
       "roofline_report"]


def _parse_metrics(text: str) -> list[dict]:
    """Pick the ``name,us_per_call,derived`` lines out of a bench's stdout."""
    out = []
    for line in text.splitlines():
        parts = line.split(",", 2)
        if len(parts) != 3:
            continue
        try:
            us = float(parts[1])
        except ValueError:
            continue
        out.append({"name": parts[0], "us_per_call": us,
                    "derived": parts[2]})
    return out


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None,
                    help="comma-separated module prefixes")
    ap.add_argument("--json", default=None, metavar="PATH",
                    help="also write per-bench results as JSON")
    args = ap.parse_args()
    from repro.launch.compile_cache import setup_compile_cache
    setup_compile_cache()
    mods = ALL if not args.only else [
        m for m in ALL if any(m.startswith(p) for p in args.only.split(","))]
    print("name,us_per_call,derived")
    records = []
    failures = 0
    for name in mods:
        t0 = time.time()
        status, error = "ok", None
        # tee: the bench's stdout still streams to the console CSV, and the
        # captured copy is parsed into the JSON record's metric list
        buf = io.StringIO()

        class _Tee:
            def write(self, s):
                buf.write(s)
                return sys.__stdout__.write(s)

            def flush(self):
                sys.__stdout__.flush()

        try:
            with contextlib.redirect_stdout(_Tee()):
                mod = __import__(f"benchmarks.{name}", fromlist=["main"])
                mod.main()
            wall = time.time() - t0
            print(f"bench_{name}_total,{wall * 1e6:.0f},ok")
        except Exception as e:
            failures += 1
            wall = time.time() - t0
            status, error = "failed", f"{type(e).__name__}: {e}"
            traceback.print_exc()
            print(f"bench_{name}_total,{wall * 1e6:.0f},"
                  f"FAILED:{type(e).__name__}")
        records.append({"name": name, "status": status, "wall_s": wall,
                        "error": error,
                        "metrics": _parse_metrics(buf.getvalue())})
    if args.json:
        with open(args.json, "w") as f:
            json.dump({"benches": records,
                       "failures": failures,
                       "wall_s": sum(r["wall_s"] for r in records)},
                      f, indent=1)
        print(f"# wrote {len(records)} bench records to {args.json}",
              file=sys.stderr)
    if failures:
        sys.exit(1)


if __name__ == "__main__":
    main()
