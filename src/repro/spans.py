"""Host spans and counters of the program.

``span(name)`` marks a stretch of host work.  While a profiler runs it
is a ``jax.profiler.TraceAnnotation`` (a ``StepTraceAnnotation`` when
``step`` is given), so the span lands in the trace on the same clock as
the device's operations.  Profiler or not, it adds its host seconds and
one count to a process-wide table, under ``<name>.s`` and ``<name>.n``.

A ``jax.monitoring`` listener, registered at import, adds the time jax
spends tracing, lowering and compiling to every span open at that
moment: ``<name>.compile_s`` (wall seconds, each counted once however
the compile events nest), ``<name>.compiles`` (backend compiles, cache
loads included) and ``<name>.traces`` (jaxpr traces, nested jits
included).  With no span open, nothing is counted.

``count(name, n)`` adds to a plain counter, ``counters()`` returns a
snapshot of the table and ``reset()`` empties it.
"""
from __future__ import annotations

import time
from typing import Dict, List, Optional

from jax import monitoring
from jax.profiler import StepTraceAnnotation, TraceAnnotation

TRACE_EVENT = "/jax/core/compile/jaxpr_trace_duration"
LOWER_EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
# a persistent-cache load; jax reports it from inside the backend
# compile that asked for it, and the union below counts it once
CACHE_EVENT = "/jax/compilation_cache/cache_retrieval_time_sec"
COMPILE_EVENTS = (TRACE_EVENT, LOWER_EVENT, COMPILE_EVENT, CACHE_EVENT)

_table: Dict[str, float] = {}
_open: List[str] = []
# disjoint [start, end] intervals of compile events seen while a span was
# open, by end time: an event that arrives covers the ones nested in it
_covered: List[List[float]] = []


def _add(key: str, v: float) -> None:
    _table[key] = _table.get(key, 0.0) + v


class span:
    """Time the block under ``name``; spans nest.  After the block,
    ``seconds`` holds its host seconds."""

    __slots__ = ("name", "step", "seconds", "_ann", "_t0")

    def __init__(self, name: str, step: Optional[int] = None):
        self.name, self.step, self.seconds = name, step, 0.0

    def __enter__(self) -> "span":
        self._ann = None
        if TraceAnnotation.is_enabled():
            self._ann = TraceAnnotation(self.name) if self.step is None \
                else StepTraceAnnotation(self.name, step_num=self.step)
            self._ann.__enter__()
        _open.append(self.name)
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc) -> None:
        self.seconds = time.perf_counter() - self._t0
        _open.pop()
        _add(self.name + ".s", self.seconds)
        _add(self.name + ".n", 1)
        if not _open:
            _covered.clear()
        if self._ann is not None:
            self._ann.__exit__(*exc)


def count(name: str, n: float = 1) -> None:
    _add(name, n)


def counters() -> Dict[str, float]:
    return dict(_table)


def reset() -> None:
    _table.clear()
    _covered.clear()


def _on_duration(event: str, secs: float, **_kw) -> None:
    if event not in COMPILE_EVENTS or not _open:
        return
    end = time.time()
    start = end - secs
    new, lo = secs, start
    # events nest (a jit traced inside another's trace, a cache load
    # inside a compile) and arrive in the order they end: the ones this
    # event covers sit at the top of the stack
    while _covered and _covered[-1][1] > start:
        s, e = _covered.pop()
        new -= max(0.0, min(e, end) - max(s, start))
        lo = min(lo, s)
    _covered.append([lo, end])
    for name in set(_open):
        _add(name + ".compile_s", max(new, 0.0))
        if event == COMPILE_EVENT:
            _add(name + ".compiles", 1)
        elif event == TRACE_EVENT:
            _add(name + ".traces", 1)


monitoring.register_event_duration_secs_listener(_on_duration)
