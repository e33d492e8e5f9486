"""Device: share of the chips' busy time spent in collectives. Time of the
collective ops (``all-reduce``, ``all-gather``, ``reduce-scatter``,
``collective-permute``, ``all-to-all``, their ``-start`` / ``-done``
halves and the fusions named for them) over busy time, both summed over
the cell's chips inside the traced window."""
import re

import numpy as np

from bench import trace_reduce as TR

COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter",
               "collective-permute", "all-to-all")
_OPCODE = re.compile(r"=\s*\S+\s+([a-z][\w\-]*)\(")


def is_collective(name: str) -> bool:
    """Whether an ``XLA Ops`` event is a collective: by its instruction's
    name or its opcode."""
    m = _OPCODE.search(name)
    words = (TR.op_name(name).split(":")[0], m.group(1) if m else "")
    return any(c in w for c in COLLECTIVES for w in words)


def shares(events: dict, anchor: str, chips: int):
    """(collective seconds, busy seconds), each summed over the first
    ``chips`` devices and clipped to the ``anchor`` host span; None where
    the trace has no such span or no device."""
    win = [(s, e) for n, s, e in events["host"] if n == anchor]
    if not win or not events["devices"]:
        return None
    a0, a1 = win[0]
    coll = busy = 0.0
    for d in sorted(events["devices"])[:chips]:
        ops = [(n, max(s, a0), min(e, a1))
               for n, s, e, _ in events["devices"][d]["ops"]
               if e > a0 and s < a1]
        iv = lambda keep: np.array([(s, e) for n, s, e in ops if keep(n)],
                                   dtype=np.float64).reshape(-1, 2)
        busy += TR._union(iv(lambda n: True))
        coll += TR._union(iv(is_collective))
    return coll / 1e9, busy / 1e9


def read(ctx):
    if not ctx.trace_path:
        return None
    got = shares(TR.load_events(ctx.trace_path), "bench.window", ctx.chips)
    if not got or got[1] <= 0:
        return None
    return 100.0 * got[0] / got[1]
