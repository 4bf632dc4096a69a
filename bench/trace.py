"""Reduction of a profiler trace to device busy time, kernel time and
the host's activity in device idle gaps.

:func:`load` reads the newest ``.xplane.pb`` under a directory through
``jax.profiler.ProfileData``; everything else works on plain lists of
``(name, start_ns, end_ns)`` so that it can be checked on synthetic
events.
"""
from __future__ import annotations

import glob
import os
import re
from typing import (Dict, Iterable, List, NamedTuple, Optional,
                    Sequence, Tuple)

Event = Tuple[str, float, float]          # (name, start_ns, end_ns)

# device lines that hold one event per executed operation
_OP_LINES = ("XLA Ops",)
# operations that only contain others: their bodies are listed apart
_CONTAINERS = ("while", "conditional", "call")
_OPCODE = re.compile(r" ([a-z][a-z0-9_-]*)\(")


def op_name(text: str) -> str:
    """``%fusion.12 = bf16[...] fusion(...), ...`` -> ``fusion.12 fusion``;
    a custom call also names its target."""
    head, _, rest = text.partition(" = ")
    m = _OPCODE.search(" " + rest)
    name = head.lstrip("%")
    if m:
        name += " " + m.group(1)
    t = re.search(r'custom_call_target="([^"]+)"', rest)
    if t:
        name += " " + t.group(1)
    return name


def _is_container(text: str) -> bool:
    m = _OPCODE.search(" " + text.partition(" = ")[2])
    return bool(m) and m.group(1) in _CONTAINERS


class Trace(NamedTuple):
    devices: Dict[str, List[Event]]       # plane name -> operations
    host: List[Event]                     # Python and runtime spans
    window: Tuple[float, float]           # traced interval, ns


def load(trace_dir: str, label: str = "") -> Trace:
    """The newest trace under ``trace_dir``.  Its window is the host span
    named ``label`` where there is one; else from the profiler's start to
    its stop, as the Python tracer records them; else from the first
    event to the last."""
    import jax
    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    pd = jax.profiler.ProfileData.from_file(max(paths, key=os.path.getmtime))
    devices: Dict[str, List[Event]] = {}
    host: List[Event] = []
    for plane in pd.planes:
        lines = list(plane.lines)
        if plane.name.startswith("/device:"):
            ops = [ln for ln in lines if ln.name in _OP_LINES]
            devices[plane.name] = sorted(
                (e.name, e.start_ns, e.start_ns + e.duration_ns)
                for ln in ops for e in ln.events
                if not _is_container(e.name))
        elif plane.name.startswith("/host:"):
            host.extend((e.name, e.start_ns, e.start_ns + e.duration_ns)
                        for ln in lines for e in ln.events)
    devices = {k: v for k, v in devices.items() if v}
    marks = [e for e in host if label and e[0] == label]
    # the Python tracer's spans (``$file.py:line function``) say what the
    # program was doing; runtime spans only where there are none
    python = [e for e in host if e[0].startswith("$")]
    host = python or host
    spans = [e for evs in devices.values() for e in evs] + host
    if not spans:
        raise ValueError(f"trace under {trace_dir} holds no events")
    # the window runs from the profiler's start to its stop, without
    # the time those two calls take themselves
    starts = [e[2] for e in host if e[0].endswith(" start_trace")]
    stops = [e[1] for e in host if e[0].endswith(" stop_trace")]
    window = (max(starts) if starts else min(e[1] for e in spans),
              min(stops) if stops else max(e[2] for e in spans))
    if marks:
        window = (min(e[1] for e in marks), max(e[2] for e in marks))
    return Trace(devices, sorted(host, key=lambda e: e[1]), window)


def union(intervals: Iterable[Tuple[float, float]]) -> List[Tuple[float, float]]:
    """Merge overlapping intervals into disjoint sorted ones."""
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def _clip(intervals, window):
    lo, hi = window
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if min(e, hi) > max(s, lo)]


def busy_ns(ops: List[Event], window) -> float:
    """Length of the union of the operations' intervals inside the window."""
    return sum(e - s for s, e in union(_clip([(o[1], o[2]) for o in ops],
                                             window)))


def mean_busy_s(trace: Trace) -> float:
    if not trace.devices:
        return 0.0
    return sum(busy_ns(ops, trace.window) for ops in trace.devices.values()) \
        / len(trace.devices) / 1e9


def kernel_ns(ops: List[Event], pattern: str) -> Tuple[float, int]:
    """Summed duration and count of the operations whose name matches."""
    rx = re.compile(pattern)
    hits = [o for o in ops if rx.search(o[0])]
    return sum(o[2] - o[1] for o in hits), len(hits)


def top_ops(trace: Trace, n: int = 10) -> List[List]:
    """The device operations that took most time, summed by name over
    the devices and divided by their number, in seconds."""
    tot: Dict[str, float] = {}
    for ops in trace.devices.values():
        for text, s, e in ops:
            name = op_name(text)
            tot[name] = tot.get(name, 0.0) + (e - s)
    k = max(len(trace.devices), 1)
    best = sorted(tot.items(), key=lambda kv: -kv[1])[:n]
    return [[name, ns / k / 1e9] for name, ns in best]


def _source(name: str) -> str:
    """``$federation.py:311 _stack_round_batches`` -> ``federation.py``."""
    return name[1:].split(":", 1)[0] if name.startswith("$") else ""


def _host_label(host: List[Event], s: float, e: float,
                sources: Sequence[str] = ()) -> str:
    """The narrowest host span that covers at least half of [s, e]; where
    ``sources`` names files, the narrowest such span from them first."""
    best: Optional[Event] = None
    own: Optional[Event] = None
    for ev in host:
        if ev[1] > e:
            break
        cover = min(e, ev[2]) - max(s, ev[1])
        if cover < 0.5 * (e - s):
            continue
        if best is None or ev[2] - ev[1] < best[2] - best[1]:
            best = ev
        if _source(ev[0]) in sources and (
                own is None or ev[2] - ev[1] < own[2] - own[1]):
            own = ev
    if best is None:
        return "(no host span)"
    if own is None or own is best:
        return best[0]
    return f"{own[0]} > {best[0]}"


def idle_gaps(trace: Trace, n: int = 10,
              sources: Sequence[str] = ()) -> List[List]:
    """The longest device idle gaps (on the first device) inside the
    window, each named by what the host was doing then, in seconds:
    the narrowest span from the ``sources`` files, then the narrowest
    span of all."""
    if not trace.devices:
        return []
    ops = trace.devices[sorted(trace.devices)[0]]
    busy = union(_clip([(o[1], o[2]) for o in ops], trace.window))
    lo, hi = trace.window
    edges = [lo] + [x for iv in busy for x in iv] + [hi]
    gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
            if edges[i + 1] > edges[i]]
    gaps.sort(key=lambda g: g[0] - g[1])
    return [[_host_label(trace.host, s, e, sources), (e - s) / 1e9]
            for s, e in gaps[:n]]
