"""From a ``jax.profiler`` trace to busy and idle time, per-program and
collective device time, and the longest idle gaps, each named after the
span the host was in.

The trace is read with ``jax.profiler.ProfileData`` (JAX alone).  A device is
a plane named ``/device:<platform>:<n>``; its ``XLA Ops`` line holds one
event per executed operation and its ``XLA Modules`` line one per executed
program (``jit_<name>(<id>)``).  The harness writes its own spans into the
host planes with ``TraceAnnotation``; their names start with ``SPAN``.  The
program writes its own (``repro/obs/timeplane.py``), named from
``PROGRAM_SPAN``.  Every time is in nanoseconds on the trace's one clock.
"""
from __future__ import annotations

import dataclasses
import glob
import os
import re
from collections import defaultdict
from typing import Dict, List, Optional, Tuple

SPAN = "chipbench."
PROGRAM_SPAN = "repro."
PROGRAM_ROUND_SPAN = PROGRAM_SPAN + "serve.round"
WINDOW_SPAN = SPAN + "window"
ROUND_SPAN = SPAN + "round"
COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter",
               "collective-permute", "all-to-all")

Interval = Tuple[int, int]                       # [start, end) in ns


@dataclasses.dataclass
class Event:
    name: str
    start: int
    end: int


@dataclasses.dataclass
class Trace:
    """What the reduction needs of one trace: per device, its op and module
    events; the host's harness spans; and the program's own host spans."""
    ops: Dict[str, List[Event]]
    modules: Dict[str, List[Event]]
    spans: List[Event]
    program_spans: List[Event] = dataclasses.field(default_factory=list)


def _short(name: str) -> str:
    """An operation's event carries its whole HLO instruction
    (``%fusion.12 = bf16[...] fusion(...)``); keep its name (``fusion.12``)."""
    return name.split(" = ", 1)[0].lstrip("%")


def _events(line) -> List[Event]:
    return [Event(_short(e.name), int(e.start_ns),
                  int(e.start_ns + e.duration_ns))
            for e in line.events]


def load(path: str, device_prefix: str = "/device:TPU:") -> Trace:
    """Read the ``.xplane.pb`` under ``path`` (a file or the directory
    ``jax.profiler.trace`` wrote)."""
    from jax.profiler import ProfileData
    if os.path.isdir(path):
        found = glob.glob(os.path.join(path, "**", "*.xplane.pb"),
                          recursive=True)
        if len(found) != 1:
            raise FileNotFoundError(f"{len(found)} xplane files under {path}")
        path = found[0]
    data = ProfileData.from_file(path)
    ops, modules, spans, program_spans = {}, {}, [], []
    for plane in data.planes:
        if plane.name.startswith(device_prefix):
            for line in plane.lines:
                if line.name == "XLA Ops":
                    ops[plane.name] = _events(line)
                elif line.name == "XLA Modules":
                    modules[plane.name] = _events(line)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in _events(line):
                    if e.name.startswith(SPAN):
                        spans.append(e)
                    elif e.name.startswith(PROGRAM_SPAN):
                        program_spans.append(e)
    return Trace(ops=ops, modules=modules, spans=spans,
                 program_spans=program_spans)


def window(trace: Trace) -> Interval:
    """The measured window: the harness's window span."""
    w = [s for s in trace.spans if s.name == WINDOW_SPAN]
    if len(w) != 1:
        raise ValueError(f"{len(w)} window spans in the trace")
    return w[0].start, w[0].end


def _clip(events: List[Event], win: Interval) -> List[Interval]:
    lo, hi = win
    return [(max(e.start, lo), min(e.end, hi)) for e in events
            if e.end > lo and e.start < hi]


def union(intervals: List[Interval]) -> List[Interval]:
    out: List[List[int]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def busy_ns(events: List[Event], win: Interval) -> int:
    """Length of the union of the events' intervals inside the window."""
    return sum(b - a for a, b in union(_clip(events, win)))


def device_busy_s(trace: Trace, win: Interval) -> float:
    """Busy seconds averaged over the devices in the trace."""
    if not trace.ops:
        return 0.0
    return (sum(busy_ns(ev, win) for ev in trace.ops.values())
            / len(trace.ops) / 1e9)


def program_name(event_name: str) -> str:
    """``jit_megastep(1234)`` -> ``megastep``."""
    name = re.sub(r"\(\d+\)$", "", event_name)
    return name[4:] if name.startswith("jit_") else name


def program_time(trace: Trace, win: Interval, name: str
                 ) -> Tuple[float, float]:
    """(seconds, executions) of the program ``name`` inside the window,
    averaged over the devices."""
    if not trace.modules:
        return 0.0, 0.0
    secs = execs = 0
    for ev in trace.modules.values():
        mine = [e for e in ev if program_name(e.name) == name
                and win[0] <= e.start < win[1]]
        secs += sum(e.end - e.start for e in mine)
        execs += len(mine)
    n = len(trace.modules)
    return secs / n / 1e9, execs / n


def collective_s(trace: Trace, win: Interval) -> float:
    """Device seconds in collective operations, averaged over devices."""
    if not trace.ops:
        return 0.0
    total = 0
    for ev in trace.ops.values():
        coll = [e for e in ev
                if any(c in e.name for c in COLLECTIVES)]
        total += busy_ns(coll, win)
    return total / len(trace.ops) / 1e9


# control flow: their events span the operations they run
CONTAINERS = ("while", "conditional", "call")


def top_ops(trace: Trace, win: Interval, n: int = 10
            ) -> List[Tuple[str, float]]:
    """The ``n`` operations with the most device time (seconds, averaged
    over devices), loops and calls left out: their time is that of the
    operations inside them."""
    acc: Dict[str, int] = defaultdict(int)
    for ev in trace.ops.values():
        for e in ev:
            if e.name.startswith(CONTAINERS):
                continue
            if e.end > win[0] and e.start < win[1]:
                acc[e.name] += min(e.end, win[1]) - max(e.start, win[0])
    k = max(len(trace.ops), 1)
    ranked = sorted(acc.items(), key=lambda kv: -kv[1])[:n]
    return [(name, ns / k / 1e9) for name, ns in ranked]


def _innermost(spans: List[Event], t: int) -> Optional[str]:
    inside = [s for s in spans if s.start <= t < s.end
              and s.name != WINDOW_SPAN]
    if not inside:
        return None
    return min(inside, key=_inner_first).name


def _inner_first(s: Event) -> Tuple[bool, int]:
    """The order in which open spans name an instant: the program's spans
    before the harness's, the shortest (innermost) first."""
    return not s.name.startswith(PROGRAM_SPAN), s.end - s.start


def idle_intervals(trace: Trace, win: Interval) -> List[Interval]:
    """The intervals of the window with no operation on the first device."""
    dev = sorted(trace.ops)[0]
    busy = union(_clip(trace.ops[dev], win))
    edges = [win[0]] + [x for iv in busy for x in iv] + [win[1]]
    return [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
            if edges[i + 1] > edges[i]]


def idle_gaps(trace: Trace, win: Interval, n: int = 10
              ) -> List[Tuple[str, float]]:
    """The ``n`` longest gaps with no operation on the first device, each
    named after the innermost program span open at the gap's middle, else
    the innermost harness span (``host`` where none is)."""
    if not trace.ops:
        return []
    gaps = sorted(idle_intervals(trace, win), key=lambda g: g[0] - g[1])
    spans = trace.program_spans + trace.spans
    return [(_innermost(spans, (a + b) // 2) or "host", (b - a) / 1e9)
            for a, b in gaps[:n]]


def idle_by_span(gaps: List[Interval], spans: List[Event]
                 ) -> Dict[str, float]:
    """Idle seconds by the span that names each instant of them, as
    ``idle_gaps`` names a gap (``host`` where none is open), by one sweep
    over every span's edges."""
    marks = []
    for k, s in enumerate(spans):
        if s.name != WINDOW_SPAN:
            marks += [(s.start, 1, k), (s.end, 0, k)]
    for a, b in gaps:
        marks += [(a, 3, -1), (b, 2, -1)]
    marks.sort()
    out: Dict[str, float] = defaultdict(float)
    open_spans: Dict[int, Event] = {}
    idle = 0
    t0 = None
    for t, kind, k in marks:
        if idle and t0 is not None and t > t0:
            inner = (min(open_spans.values(), key=_inner_first).name
                     if open_spans else "host")
            out[inner] += (t - t0) / 1e9
        t0 = t
        if kind == 1:
            open_spans[k] = spans[k]
        elif kind == 0:
            open_spans.pop(k, None)
        else:
            idle += 1 if kind == 3 else -1
    return dict(out)


def span_s(trace: Trace, win: Interval, names) -> float:
    """Seconds of host spans, the harness's or the program's, named in
    ``names`` that start in the window."""
    return sum(s.end - s.start for s in trace.spans + trace.program_spans
               if s.name in names and win[0] <= s.start < win[1]) / 1e9


def span_count(trace: Trace, win: Interval, name: str) -> int:
    return sum(1 for s in trace.spans + trace.program_spans
               if s.name == name and win[0] <= s.start < win[1])


def program_ms_per_round(trace: Trace, win: Interval, name: str
                         ) -> Optional[float]:
    """ms of the program's spans ``name`` per serving round, over the
    rounds whose ``PROGRAM_ROUND_SPAN`` starts in the window; None where
    there is none (a program that writes no spans)."""
    n = span_count(trace, win, PROGRAM_ROUND_SPAN)
    return 1e3 * span_s(trace, win, (name,)) / n if n else None
