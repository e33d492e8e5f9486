"""Reduce a ``jax.profiler`` trace (``.xplane.pb``) to what the per-layer
readers and the ``breakdown`` need.

- The window is the host span named by ``anchor`` (the harness opens a
  ``TraceAnnotation`` around the timed window); everything is clipped to it.
- Busy time of a chip is the union of the intervals of the operations on
  its ``XLA Ops`` line; ``busy_s`` is the mean over the chips used.
- Kernel time is the summed duration of the operations that are a given
  Pallas kernel, told apart by :func:`kernel_of` (chip 0).
- Programs are the events of the ``XLA Modules`` line; each records its
  busy seconds and the kernels that ran inside it, so a reader can split
  busy time between prefill and decode programs.
- ``breakdown``: the ten device operations that took most time (numbered
  HLO suffixes merged; loops and calls, whose events enclose the ops they
  run, left out), and the idle time of chip 0 summed by what the
  host was doing in each gap: the innermost host span (the engine's own
  spans, shifted onto the profiler's clock by the anchor) that covers the
  gap's middle, or ``host`` where none does.
"""
from __future__ import annotations

import re
from collections import defaultdict

import numpy as np

# A Pallas call shows in the ``XLA Ops`` line as an HLO instruction named
# after the jitted function that wraps the kernel, ``%<wrapper>.<n> =
# ... custom_call_target="tpu_custom_call"``; the wrapper names the kernel.
KERNELS = {"flash_attention_pallas": "flash_attention",
           "flash_decode_pallas": "flash_decode",
           "flash_decode_paged_pallas": "flash_decode_paged",
           "lora_bgmv_rows_pallas": "lora_bgmv_rows",
           "lora_bgmv_seq_pallas": "lora_bgmv_seq",
           "lora_matmul_pallas": "lora_matmul_fwd",
           "lora_matmul_bwd_pallas": "lora_matmul_bwd"}
_INSTR = re.compile(r"^%?([A-Za-z_][\w\-]*?)(?:\.\d+)?\s*=")
_SUFFIX = re.compile(r"\.\d+$")
# ops whose events enclose the ops they run (left out of the op totals)
_CONTAINERS = {"while", "conditional", "call"}


_KIND = re.compile(r"kind=(k\w+)")


def op_name(name: str) -> str:
    """The HLO instruction's name without its number; a fusion also names
    its kind (kOutput fusions hold the matrix products, kLoop ones are
    elementwise)."""
    m = _INSTR.match(name)
    base = m.group(1) if m else _SUFFIX.sub("", name)
    k = _KIND.search(name) if base.endswith("fusion") else None
    return f"{base}:{k.group(1)}" if k else base


def kernel_of(name: str):
    """The Pallas kernel an op is, or None."""
    if "tpu_custom_call" not in name:
        return None
    return KERNELS.get(op_name(name), op_name(name))


def _union(iv: np.ndarray) -> float:
    if not len(iv):
        return 0.0
    iv = iv[np.argsort(iv[:, 0])]
    total, cs, ce = 0.0, iv[0, 0], iv[0, 1]
    for s, e in iv[1:]:
        if s > ce:
            total += ce - cs
            cs, ce = s, e
        else:
            ce = max(ce, e)
    return total + ce - cs


def _gaps(iv: np.ndarray, a0: float, a1: float) -> list:
    if not len(iv):
        return [(a0, a1)]
    iv = iv[np.argsort(iv[:, 0])]
    out, cur = [], a0
    for s, e in iv:
        if s > cur:
            out.append((cur, s))
        cur = max(cur, e)
    if a1 > cur:
        out.append((cur, a1))
    return out


def load_events(path):
    """{'host': [(name, start, end)], 'devices': {id: {'ops': [...],
    'modules': [...]}}} in ns on the profiler's clock."""
    import jax
    pd = jax.profiler.ProfileData.from_file(str(path))
    host, devices = [], {}
    for plane in pd.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    host.append((ev.name, ev.start_ns,
                                 ev.start_ns + ev.duration_ns))
            continue
        m = re.match(r"/device:TPU:(\d+)", plane.name)
        if not m:
            continue
        dev = devices.setdefault(int(m.group(1)), {"ops": [], "modules": []})
        for line in plane.lines:
            if line.name == "XLA Ops":
                for ev in line.events:
                    dev["ops"].append((ev.name, ev.start_ns,
                                       ev.start_ns + ev.duration_ns,
                                       kernel_of(ev.name)))
            elif line.name == "XLA Modules":
                for ev in line.events:
                    dev["modules"].append((ev.name, ev.start_ns,
                                           ev.start_ns + ev.duration_ns))
    return {"host": host, "devices": devices}


def reduce(path, *, anchor: str, chips: int, host_spans=(),
           events=None) -> dict | None:
    ev = events or load_events(path)
    win = [(s, e) for n, s, e in ev["host"] if n == anchor]
    if not win or not ev["devices"]:
        return None
    a0, a1 = win[0]
    ids = sorted(ev["devices"])[:chips]
    clip = lambda s, e: (max(s, a0), min(e, a1))
    busy = []
    for d in ids:
        iv = np.array([clip(s, e) for _, s, e, _ in ev["devices"][d]["ops"]
                       if e > a0 and s < a1], dtype=np.float64).reshape(-1, 2)
        busy.append(_union(iv))
    dev0 = ev["devices"][ids[0]]
    ops = [(n, *clip(s, e), k) for n, s, e, k in dev0["ops"]
           if e > a0 and s < a1]
    kernels = defaultdict(lambda: [0.0, 0])
    by_op = defaultdict(float)
    for n, s, e, k in ops:
        if k:
            kernels[k][0] += (e - s) / 1e9
            kernels[k][1] += 1
        if op_name(n) not in _CONTAINERS:
            by_op[k or op_name(n)] += (e - s) / 1e9
    starts = np.array([s for _, s, _, _ in ops])
    order = np.argsort(starts)
    ops_sorted = [ops[i] for i in order]
    starts = starts[order]
    modules = []
    for n, s, e in dev0["modules"]:
        if e <= a0 or s >= a1:
            continue
        lo, hi = np.searchsorted(starts, s), np.searchsorted(starts, e)
        inner = ops_sorted[lo:hi]
        iv = np.array([(max(x[1], s), min(x[2], e)) for x in inner],
                      dtype=np.float64).reshape(-1, 2)
        modules.append({"name": n, "busy_s": _union(iv) / 1e9,
                        "kernels": sorted({x[3] for x in inner if x[3]})})
    iv0 = np.array([(s, e) for _, s, e, _ in ops],
                   dtype=np.float64).reshape(-1, 2)
    # host spans onto the profiler's clock: the anchor span starts at a0
    t_anchor = next((t0 for n, t0, _ in host_spans if n == anchor), None)
    spans = [] if t_anchor is None else [
        (n, a0 + (t0 - t_anchor) * 1e9, a0 + (t1 - t_anchor) * 1e9)
        for n, t0, t1 in host_spans if n != anchor]
    idle = defaultdict(float)
    for g0, g1 in _gaps(iv0, a0, a1):
        mid = (g0 + g1) / 2
        cover = [(t1 - t0, n) for n, t0, t1 in spans if t0 <= mid <= t1]
        idle[min(cover)[1] if cover else "host"] += (g1 - g0) / 1e9
    top = lambda d: [[k, v] for k, v in sorted(d.items(),
                                               key=lambda kv: -kv[1])[:10]]
    return {"busy_s": float(np.mean(busy)) / 1e9,
            "window_s": (a1 - a0) / 1e9,
            "kernels": {k: tuple(v) for k, v in kernels.items()},
            "modules": modules,
            "breakdown": {"device_ops": top(by_op), "idle_gaps": top(idle)}}
