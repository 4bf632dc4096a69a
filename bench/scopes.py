"""Reduction of the ``plain`` trace by the program's own names.

Device time is split by the round program's scopes (``jax.named_scope``
names such as ``round.teacher``, carried by each device operation's
``op_name``); device idle time is split by the driver's host spans
(``repro.spans`` annotations such as ``fed.stage``).  Busy and idle use
the operations :func:`bench.trace.load` keeps, so the idle split adds up
to ``idle_share.round``.

:func:`load_ops` reads the operations with their names once per trace
file; everything else works on plain lists so that it can be checked on
synthetic events.  Where the trace holds no scope or span, the readers
return None: a program without them reports nothing here.
"""
from __future__ import annotations

import bisect
import functools
import glob
import os
import re
from typing import Dict, Iterable, List, NamedTuple, Optional, Sequence, Tuple

from bench import trace as T

# the stat of a device operation's event metadata that carries the HLO
# instruction's ``op_name``, and the device line of module executions
OP_NAME_STAT = "tf_op"
MODULE_LINE = "XLA Modules"
# the module of the stacked engine's round program
ROUND_MODULE = re.compile(r"^jit_round_fn\b")
# the round program's scopes and the driver spans that idle the device
ROUND_SCOPES = ("round.teacher", "round.student", "round.protos",
                "round.codec", "round.mix")
IDLE_SPANS = ("fed.stage", "fed.eval")


class Op(NamedTuple):
    start: float
    end: float
    op_name: str
    module: str


@functools.lru_cache(maxsize=1 << 16)
def has_scope(op_name: str, scope: str) -> bool:
    """``scope`` is a component of the name path, also as the argument
    of a transform: ``jit(f)/while/body/transpose(jvp(round.teacher))/mul``
    belongs to ``round.teacher``."""
    return re.search(r"(?:^|[/(])" + re.escape(scope) + r"(?=[/):]|$)",
                     op_name) is not None


def scoped_ns(ops: Sequence[Op], scopes: Sequence[str], window) -> float:
    """Length of the union of the intervals of the operations that belong
    to any of ``scopes``, inside the window: overlapping ones count once."""
    return T.busy_ns([("", o.start, o.end) for o in ops
                      if any(has_scope(o.op_name, s) for s in scopes)],
                     window)


def idle_gaps(ops: Iterable[Tuple], window) -> List[Tuple[float, float]]:
    """The stretches of the window in which none of ``ops``
    (``(name, start, end)``) runs, in order."""
    busy = T.union(T._clip([(o[1], o[2]) for o in ops], window))
    lo, hi = window
    edges = [lo] + [x for iv in busy for x in iv] + [hi]
    return [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
            if edges[i + 1] > edges[i]]


def meet_ns(a: Sequence[Tuple[float, float]],
            b: Sequence[Tuple[float, float]]) -> float:
    """Length of the intersection of two sorted lists of disjoint
    intervals."""
    out, i, j = 0.0, 0, 0
    while i < len(a) and j < len(b):
        out += max(0.0, min(a[i][1], b[j][1]) - max(a[i][0], b[j][0]))
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return out


def idle_split(trace: T.Trace, span_names: Sequence[str]
               ) -> Optional[Dict[str, float]]:
    """Device idle time by driver span, in % of the window (mean over
    the devices): each of ``span_names`` in turn takes the idle time
    under its host spans that an earlier name has not taken, ``other``
    the rest, so the parts add up to the idle share.  None where the
    trace holds none of these spans."""
    spans = {n: [(e[1], e[2]) for e in trace.host if e[0] == n]
             for n in span_names}
    if not trace.devices or not any(spans.values()):
        return None
    out = {n: 0.0 for n in span_names}
    idle = 0.0
    for ops in trace.devices.values():
        gaps = idle_gaps(ops, trace.window)
        idle += sum(e - s for s, e in gaps)
        taken: List[Tuple[float, float]] = []
        before = 0.0
        for n in span_names:
            taken = T.union(T._clip(taken + spans[n], trace.window))
            now = meet_ns(gaps, taken)
            out[n] += now - before
            before = now
    scale = 100.0 / len(trace.devices) / (trace.window[1] - trace.window[0])
    out = {n: v * scale for n, v in out.items()}
    out["other"] = idle * scale - sum(out.values())
    return out


def _varint(buf, i: int) -> Tuple[int, int]:
    out = shift = 0
    while True:
        c = buf[i]
        i += 1
        out |= (c & 0x7F) << shift
        if c < 0x80:
            return out, i
        shift += 7


def _fields(buf):
    """``(field number, value)`` of a protobuf message: an int for a
    varint, the bytes for a length-delimited field."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        kind = key & 7
        if kind == 0:
            v, i = _varint(buf, i)
        elif kind == 2:
            ln, i = _varint(buf, i)
            v, i = buf[i:i + ln], i + ln
        elif kind in (1, 5):
            step = 8 if kind == 1 else 4
            v, i = buf[i:i + step], i + step
        else:
            raise ValueError(f"protobuf wire type {kind}")
        yield key >> 3, v


def _text(v) -> str:
    return bytes(v).decode("utf-8", "replace")


def _plane_ops(plane) -> List[Op]:
    """The operations of one device plane of an ``XSpace`` (the messages
    of ``tsl/profiler/protobuf/xplane.proto``): the ``XLA Ops`` line, with
    each op's ``op_name`` from its event metadata's stats and its module
    from the ``XLA Modules`` line event that holds it."""
    stat_names: Dict[int, str] = {}
    meta: Dict[int, bytes] = {}
    lines = []
    for f, v in _fields(plane):
        if f == 5:                                   # stat_metadata
            entry = dict(_fields(v))
            sm = dict(_fields(entry.get(2, b"")))
            stat_names[entry.get(1, 0)] = _text(sm.get(2, b""))
        elif f == 4:                                 # event_metadata
            entry = dict(_fields(v))
            meta[entry.get(1, 0)] = entry.get(2, b"")
        elif f == 3:
            lines.append(v)
    names: Dict[int, Tuple[str, str]] = {}
    for mid, raw in meta.items():
        name, stats = "", {}
        for f, v in _fields(raw):
            if f == 2:
                name = _text(v)
            elif f == 5:
                st = dict(_fields(v))
                key = stat_names.get(st.get(1, 0), "")
                if 5 in st:
                    stats[key] = _text(st[5])
                elif 7 in st:                        # an interned string
                    stats[key] = stat_names.get(st[7], "")
        names[mid] = (name, stats.get(OP_NAME_STAT, ""))
    by_line: Dict[str, List[Tuple[float, float, str, str]]] = {}
    for raw in lines:
        line = dict((f, v) for f, v in _fields(raw) if f != 4)
        lname = _text(line.get(2, b""))
        if lname not in T._OP_LINES and lname != MODULE_LINE:
            continue
        t0 = line.get(3, 0)
        evs = by_line.setdefault(lname, [])
        for f, v in _fields(raw):
            if f != 4:
                continue
            ev = dict(_fields(v))
            name, op_name = names.get(ev.get(1, 0), ("", ""))
            # whole nanoseconds, as ``jax.profiler.ProfileData`` gives them
            start = float(t0 + ev.get(2, 0) // 1000)
            evs.append((start, start + ev.get(3, 0) // 1000, name, op_name))
    modules = sorted((s, e, name) for s, e, name, _ in
                     by_line.get(MODULE_LINE, []))
    starts = [m[0] for m in modules]
    ops = []
    for ln in T._OP_LINES:
        for s, e, name, op_name in by_line.get(ln, []):
            if T._is_container(name):
                continue
            k = bisect.bisect_right(starts, s) - 1
            module = modules[k][2] if k >= 0 and s < modules[k][1] else ""
            ops.append(Op(s, e, op_name, module))
    return sorted(ops)


_CACHE: Dict[tuple, Dict[str, List[Op]]] = {}


def load_ops(trace_dir: str) -> Dict[str, List[Op]]:
    """The operations of the newest trace under ``trace_dir``, per device,
    with their ``op_name`` and module: the op set of
    :func:`bench.trace.load`.  ``jax.profiler.ProfileData`` gives no
    event metadata, so the file is read as protobuf messages."""
    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        return {}
    path = max(paths, key=os.path.getmtime)
    key = (path, os.path.getmtime(path))
    if key not in _CACHE:
        with open(path, "rb") as fh:
            space = memoryview(fh.read())
        devices: Dict[str, List[Op]] = {}
        for f, plane in _fields(space):
            if f != 1:
                continue
            name = next((_text(v) for g, v in _fields(plane) if g == 2), "")
            if name.startswith("/device:"):
                ops = _plane_ops(plane)
                if ops:
                    devices[name] = ops
        _CACHE.clear()
        _CACHE[key] = devices
    return _CACHE[key]


def device_s(ctx, scopes: Sequence[str]) -> Optional[float]:
    """Device seconds per traced round of the operations in ``scopes``
    (mean over the devices); None where no operation carries a
    ``round.*`` scope."""
    from bench.harness import TRACE_DIR
    rounds = ctx.out.get("traced_rounds")
    devices = load_ops(str(TRACE_DIR / "plain"))
    if not rounds or not any(has_scope(o.op_name, s) for ops in
                             devices.values() for o in ops
                             for s in ROUND_SCOPES):
        return None
    ns = sum(scoped_ns(ops, scopes, ctx.trace.window)
             for ops in devices.values())
    return ns / len(devices) / 1e9 / rounds


def coverage(devices: Dict[str, List[Op]], window,
             scopes: Sequence[str], module=ROUND_MODULE) -> Optional[float]:
    """Share of the round program's device time (its module's operations)
    that the scopes cover, in %."""
    total = covered = 0.0
    for ops in devices.values():
        mine = [o for o in ops if module.search(o.module)]
        total += T.busy_ns([("", o.start, o.end) for o in mine], window)
        covered += scoped_ns(mine, scopes, window)
    return 100.0 * covered / total if total else None


def idle_share(ctx, part: str) -> Optional[float]:
    """Part ``part`` of :func:`idle_split` over :data:`IDLE_SPANS`."""
    split = idle_split(ctx.trace, IDLE_SPANS)
    return None if split is None else split[part]


def counter(name: str) -> Optional[float]:
    """A counter of the program's span table, None where the program
    keeps none or has not counted it."""
    try:
        from repro import spans
    except ImportError:
        return None
    return spans.counters().get(name)
