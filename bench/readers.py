"""Arithmetic the per-layer readers share (``metrics/<name>.py``).

A reader gets the run's context: ``ctx.result`` (what the driver
measured), ``ctx.reduced`` (the reduced profiler trace, or None) and the
configuration, mix and chip peaks. It returns a number, or None where the
run holds nothing to read.
"""
from __future__ import annotations

from bench.costs import bound_s
from bench.costs import model_step as MS


def idle_share(ctx):
    red = ctx.reduced
    if not red or red["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - red["busy_s"] / red["window_s"])


def kernel_seconds(ctx, kernel: str):
    """(seconds, events) of one kernel in the traced window (chip 0)."""
    red = ctx.reduced
    if not red:
        return 0.0, 0
    k = red["kernels"].get(kernel)
    return (k[0], k[1]) if k else (0.0, 0)


def roofline(ctx, kernel: str, least_s: float):
    """Share (%) of the kernel's traced time that the least time it needs
    accounts for; None where the trace holds no such kernel."""
    t, n = kernel_seconds(ctx, kernel)
    if n == 0 or t <= 0 or least_s <= 0:
        return None
    return 100.0 * least_s / t


def served_rows(ctx):
    """(prompt length, served tokens) of every request that decoded."""
    return [(len(r["prompt"]), r["n"]) for r in ctx.result["requests"]
            if r["n"] > 0]


def module_busy(ctx, kernel: str):
    """Device-busy seconds of the programs that ran ``kernel``."""
    red = ctx.reduced
    if not red:
        return 0.0
    return sum(m["busy_s"] for m in red["modules"] if kernel in m["kernels"])


def least(cost_pairs, peak) -> float:
    return sum(bound_s(f, b, peak)[0] for f, b in cost_pairs)


def model_sizes(ctx) -> dict:
    return MS.sizes(ctx.conf)
