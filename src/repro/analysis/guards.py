"""Runtime compile-count sentinel: the dynamic half of tracelint.

The static pass (R1) proves the fused-fn cache KEYS are complete; this
module proves the caches actually stay BOUNDED at runtime. The engine's
pow2 bucketing (segment lengths, refill row counts, prompt widths, cache
caps) promises that a drain compiles O(log) distinct programs and that a
repeat drain over the same envelope compiles NOTHING — promises only a
counter can enforce.

:func:`compile_guard` wraps ``jax.log_compiles()``: every XLA
compilation inside the context is counted (and its name recorded) via
the ``Compiling <name> with global shapes`` log line, the total is
exported as a telemetry counter, and exceeding ``max_compiles`` raises
:class:`CompileBudgetExceeded` listing exactly what compiled — so a
recompile storm fails the test that budgeted against it instead of
showing up as a latency mystery in production traces.

    with compile_guard(max_compiles=0):        # warm path: no compiles
        engine.run(params)

    with compile_guard(max_compiles=12,
                       match=r"prefill|refill|decode_segment") as log:
        first_drain()                          # fused fns only
    print(log.count, log.names)
"""
from __future__ import annotations

import contextlib
import logging
import re
from typing import Optional

import jax

from repro.core import telemetry

# jax logs one "Compiling jit(<name>) with global shapes and types [...]"
# line per actual XLA compilation (cache hits are silent) when
# log_compiles is on; tracing/lowering lines are deliberately NOT counted.
# The recorded name is the function's own, without the jit(...) wrapper.
_COMPILE_RE = re.compile(r"^Compiling jit\((.+?)\) with global shapes")


class CompileBudgetExceeded(RuntimeError):
    """More XLA compilations than the guarded region budgeted for."""


class CompileLog:
    """Mutable view yielded by :func:`compile_guard`."""

    def __init__(self) -> None:
        self.names: list[str] = []

    @property
    def count(self) -> int:
        return len(self.names)

    def __repr__(self) -> str:
        return f"CompileLog(count={self.count}, names={self.names!r})"


class _Capture(logging.Handler):
    def __init__(self, log: CompileLog, match: Optional[str]) -> None:
        super().__init__(level=logging.DEBUG)
        self._log = log
        self._match = re.compile(match) if match else None

    def emit(self, record: logging.LogRecord) -> None:
        m = _COMPILE_RE.match(record.getMessage())
        if not m:
            return
        name = m.group(1)
        if self._match is not None and not self._match.search(name):
            return
        self._log.names.append(name)


@contextlib.contextmanager
def compile_guard(max_compiles: Optional[int] = None, *,
                  match: Optional[str] = None,
                  counter: str = "analysis.compiles",
                  tel: Optional[telemetry.Telemetry] = None):
    """Count XLA compilations in the block; enforce a budget.

    - ``max_compiles=None`` only counts (and exports the counter);
      ``max_compiles=N`` raises :class:`CompileBudgetExceeded` when the
      block compiles more than N programs. ``max_compiles=0`` is the
      strongest form: the block must run entirely off warm jit caches.
    - ``match`` restricts counting to compiled-function names matching
      the regex (the repo's fused serving/training dispatches are named
      for what they run — ``wave_prefill``, ``refill``,
      ``decode_segment``, ``hfsl_round`` ... — so such a regex isolates
      them from one-off convert/broadcast micro-compiles).
    - counts are exported to ``tel`` (default: the global telemetry
      registry) as counter ``analysis.compiles`` plus
      ``analysis.compile_guard_trips`` on budget violations.

    The guard composes with nested guards (each counts independently)
    and leaves ``jax_log_compiles`` exactly as it found it.
    """
    log = CompileLog()
    handler = _Capture(log, match)
    jax_logger = logging.getLogger("jax")
    prev_level = jax_logger.level
    jax_logger.addHandler(handler)
    # log_compiles emits at WARNING; make sure an app-configured stricter
    # level cannot starve the counter
    if prev_level > logging.WARNING:
        jax_logger.setLevel(logging.WARNING)
    # log_compiles also floods "Finished tracing/lowering" lines from the
    # dispatch logger; those are not compilations — keep them off stderr
    noisy = logging.getLogger("jax._src.dispatch")
    prev_noisy = noisy.level
    noisy.setLevel(logging.ERROR)
    try:
        with jax.log_compiles():
            yield log
    finally:
        jax_logger.removeHandler(handler)
        jax_logger.setLevel(prev_level)
        noisy.setLevel(prev_noisy)
        t = tel if tel is not None else telemetry.get()
        t.count(counter, log.count)
    if max_compiles is not None and log.count > max_compiles:
        t.count("analysis.compile_guard_trips")
        raise CompileBudgetExceeded(
            f"{log.count} XLA compilation(s) inside a "
            f"compile_guard(max_compiles={max_compiles}) region"
            + (f" (match={match!r})" if match else "")
            + f": {log.names}")
