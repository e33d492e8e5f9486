"""Device time by program and named scope, from a traced window.

Every jitted step of the program is named for what it runs
(``jit_decode_segment``, ``jit_refill``, ``jit_hfsl_round``, ...), and its
ops carry their named scopes in their source path (``xplane.load_ops``):

- ``embed``; ``layers`` around the layer scan and ``layer`` inside its
  body; in a layer ``attn`` (in it ``kv_cache``: the cache write, the
  prefix gather and concatenation, the padding and reshapes into a
  kernel's layout; and ``lora``: the LoRA-fused projections) and ``mlp``;
  ``lm_head`` (final norm, unembedding and the loss) and ``sample``;
  ``steps`` around a decode segment's step loop;
- in the HFSL round ``fedavg`` and ``optimizer``.

A backward op names its forward scope inside a transform
(``transpose(jvp(lm_head))``), so a scope is matched as a component of
the path with ``/ ; ( )`` as separators. An op XLA inserts itself (the
copies between memory spaces around a loop) has no source; it takes the
path of the innermost loop whose event encloses it. An op's top-level
scope is its outermost scope other than ``steps``, which holds only the
step loop's own ops.

    python3 -m bench.scopes <trace.xplane.pb>

prints, per program of the whole trace, its device seconds by top-level
scope with the unscoped share, by chain of scopes, and the layer scan's
own slicing by output shape (weights and caches told apart by shape).
"""
from __future__ import annotations

import re
import sys
from collections import defaultdict

import numpy as np

from bench.trace_reduce import KERNELS, _CONTAINERS, _union, op_name

ANCHOR = "bench.window"
SCOPES = ("embed", "layers", "layer", "attn", "kv_cache", "lora", "mlp",
          "lm_head", "sample", "steps", "fedavg", "optimizer")
_COPIES = ("copy", "copy-start", "copy-done")
_SPLIT = re.compile(r"[/;()]+")
_PROGRAM = re.compile(r"^jit_(.+?)(?:\(\d+\))?$")
_WRAPPERS = {f"jit({w})" for w in KERNELS}


def parts(path: str) -> list:
    """The components of an op's source path (the ``:type`` suffix of the
    profiler's ``tf_op`` dropped)."""
    return [p for p in _SPLIT.split(path.rsplit(":", 1)[0]) if p]


def top_scope(path: str):
    """The outermost named scope on an op's path other than ``steps``;
    ``steps`` where that is the only one; or None."""
    found = [p for p in parts(path) if p in SCOPES]
    return next((p for p in found if p != "steps"),
                found[0] if found else None)


def program_name(module: str) -> str:
    """``jit_decode_segment(123)`` -> ``decode_segment``."""
    m = _PROGRAM.match(module)
    return m.group(1) if m else module


def _opcode(op) -> str:
    return op_name(op[0]).split(":")[0]


def is_container(op) -> bool:
    """A loop or call, whose event encloses the ops it runs."""
    return _opcode(op) in _CONTAINERS


def _enclosed(ops: list) -> list:
    """``ops`` (sorted by start) with each source-less op given the path of
    the innermost container whose interval holds it."""
    loops = [o for o in ops if is_container(o)]
    starts = [o[1] for o in loops]
    out = []
    for o in ops:
        if not o[4]:
            j = int(np.searchsorted(starts, o[1], side="right")) - 1
            while j >= 0 and loops[j][2] < o[2]:
                j -= 1
            if j >= 0:
                o = (*o[:4], loops[j][4])
        out.append(o)
    return out


def programs(events, *, anchor: str = ANCHOR, whole: bool = False) -> list:
    """[(program name, busy ns, ops)] of each program run on the lowest-
    numbered chip inside the window (the host span ``anchor``; the whole
    trace with ``whole``), ops clipped to the window and to their program.
    Busy is the union of all the program's op intervals."""
    if not events["devices"]:
        return []
    if whole:
        a0, a1 = -np.inf, np.inf
    else:
        win = [(s, e) for n, s, e in events["host"] if n == anchor]
        if not win:
            return []
        a0, a1 = win[0]
    dev = events["devices"][min(events["devices"])]
    ops = sorted(dev["ops"], key=lambda o: o[1])
    starts = np.array([o[1] for o in ops])
    out = []
    for name, s, e in dev["modules"]:
        s, e = max(s, a0), min(e, a1)
        if e <= s:
            continue
        lo, hi = np.searchsorted(starts, s), np.searchsorted(starts, e)
        inner = [(o[0], max(o[1], s), min(o[2], e), *o[3:])
                 for o in ops[lo:hi]]
        inner = _enclosed([o for o in inner if o[2] > o[1]])
        busy = _union(np.array([o[1:3] for o in inner],
                               dtype=np.float64).reshape(-1, 2))
        out.append((program_name(name), busy, inner))
    return out


def share(events, program: str, pred, *, anchor: str = ANCHOR):
    """% of the busy time of the programs named ``program`` taken by the
    ops for which ``pred(op)`` holds (the union of their intervals); None
    where the window ran no such program."""
    busy, iv = 0.0, []
    for name, b, ops in programs(events, anchor=anchor):
        if name != program:
            continue
        busy += b
        iv += [o[1:3] for o in ops if pred(o)]
    if busy <= 0:
        return None
    hit = _union(np.array(iv, dtype=np.float64).reshape(-1, 2))
    return 100.0 * hit / busy


def is_relayout(op) -> bool:
    """An op that moves or re-lays out data rather than computing: under
    ``kv_cache``; the layer scan's own slicing and write-back of its
    stacked weights and caches (under ``layers``, not under ``layer``); a
    kernel wrapper's op other than the kernel; or a copy outside a layer's
    body (the step loop's copies of the whole stacked cache)."""
    p = parts(op[4])
    if "kv_cache" in p or ("layers" in p and "layer" not in p):
        return True
    if op[3] is None and any(w in op[4] for w in _WRAPPERS):
        return True
    return _opcode(op) in _COPIES and "layer" not in p


def is_lm_head(op) -> bool:
    """An op under ``lm_head``, forward or backward."""
    return "lm_head" in parts(op[4])


def by_scope(events, *, anchor: str = ANCHOR, whole: bool = False) -> dict:
    """{program: {'busy_s', 'ops_s' (non-loop op seconds), 'scopes':
    {top-level scope: s}, 'unscoped_s', 'unscoped_share' (% of ops_s)}},
    summed over every run of each program."""
    out: dict = {}
    for name, busy, ops in programs(events, anchor=anchor, whole=whole):
        r = out.setdefault(name, {"busy_s": 0.0, "ops_s": 0.0,
                                  "scopes": defaultdict(float),
                                  "unscoped_s": 0.0})
        r["busy_s"] += busy / 1e9
        for o in ops:
            if is_container(o):
                continue
            d = (o[2] - o[1]) / 1e9
            r["ops_s"] += d
            top = top_scope(o[4])
            if top is None:
                r["unscoped_s"] += d
            else:
                r["scopes"][top] += d
    for r in out.values():
        r["scopes"] = dict(r["scopes"])
        r["unscoped_share"] = (100.0 * r["unscoped_s"] / r["ops_s"]
                               if r["ops_s"] else 0.0)
    return out


_SHAPE = re.compile(r"=\s*\(?([a-z0-9]+\[[0-9,]*\])")


def layer_scan_shapes(events, program: str, *, anchor: str = ANCHOR,
                      whole: bool = False) -> dict:
    """Seconds of the layer scan's own ops (under ``layers``, not under
    ``layer``) in the programs named ``program``, by output shape: the
    stacked weights slice to a layer's weight shapes, the caches to
    (batch, slots, heads, dim) and (batch, slots)."""
    out: dict = defaultdict(float)
    for name, _, ops in programs(events, anchor=anchor, whole=whole):
        if name != program:
            continue
        for o in ops:
            p = parts(o[4])
            if "layers" in p and "layer" not in p and not is_container(o):
                m = _SHAPE.search(o[0])
                out[m.group(1) if m else "?"] += (o[2] - o[1]) / 1e9
    return dict(out)


def scope_chains(events, program: str, *, anchor: str = ANCHOR,
                 whole: bool = False) -> dict:
    """Non-loop seconds of the programs named ``program`` by the chain of
    scopes on each op's path (``layers/layer/attn/kv_cache``; ``""`` for
    none)."""
    out: dict = defaultdict(float)
    for name, _, ops in programs(events, anchor=anchor, whole=whole):
        if name != program:
            continue
        for o in ops:
            if not is_container(o):             # a fused op's first path
                chain = "/".join(p for p in parts(o[4].split(";")[0])
                                 if p in SCOPES)
                out[chain] += (o[2] - o[1]) / 1e9
    return dict(out)


def load(ctx):
    """The run's traced events with source paths, or None."""
    if not getattr(ctx, "trace_path", None):
        return None
    from bench import xplane
    return xplane.load_ops(ctx.trace_path)


def main(argv) -> int:
    from bench import xplane
    ev = xplane.load_ops(argv[0])
    for prog, r in sorted(by_scope(ev, whole=True).items(),
                          key=lambda kv: -kv[1]["busy_s"]):
        print(f"{prog}: busy {r['busy_s']:.6f} s, ops {r['ops_s']:.6f} s, "
              f"unscoped {r['unscoped_share']:.2f}%")
        for sc, s in sorted(r["scopes"].items(), key=lambda kv: -kv[1]):
            print(f"  {sc:<10} {s:.6f} s  {100 * s / r['ops_s']:.2f}%")
        chains = scope_chains(ev, prog, whole=True)
        for ch, s in sorted(chains.items(), key=lambda kv: -kv[1])[:12]:
            print(f"    {ch or '(none)':<40} {s:.6f} s")
        shapes = layer_scan_shapes(ev, prog, whole=True)
        for shp, s in sorted(shapes.items(), key=lambda kv: -kv[1])[:8]:
            print(f"    layer scan {shp:<28} {s:.6f} s")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
