"""Put device time and idle time down to the program's own names: the
named scopes of the serving megastep and the program's host spans
(``repro.*``), both on the trace's one clock (``repro/obs/timeplane.py``).

The per-layer readers of scoped device time call ``scope_ms_per_execution``.
As a one-process tool for the chip,

    python3 -m chipbench.attribution --workload <cell> --seed <n> --seconds <s>

runs one traced window of a cell as a ``--trace 1`` run does, and prints one
JSON object: each program span's time per round, the device's idle time
split by the innermost span the host was in, the longest idle gaps, the
compiles in the window, and the megastep's device time per execution split
by scope, with its unscoped share and the unscoped operations that lead it.
"""
from __future__ import annotations

import argparse
import bisect
import glob
import json
import os
import shutil
import tempfile
from collections import defaultdict
from typing import Dict, List, Optional, Tuple

from chipbench import reduce
from chipbench.reduce import Event, Interval, Trace

PROGRAM_SPAN = "repro."
PROGRAM = "megastep"


def program_scopes(program: str = PROGRAM) -> Optional[Dict[str, str]]:
    """The live program's map from HLO instruction name to named scope, or
    None where the program publishes none (one that predates its time
    plane among them)."""
    try:
        from repro.obs import timeplane
    except ImportError:
        return None
    return timeplane.program_scopes(program)


def program_ops(trace: Trace, win: Interval, program: str
                ) -> Tuple[List[Event], float]:
    """The operations that run inside the executions of ``program`` that
    start in the window, loops and calls left out as in ``reduce.top_ops``,
    over every device; and the executions, averaged over the devices.
    Operations of other programs that share an instruction name are not
    among them."""
    ops: List[Event] = []
    execs = 0
    for dev, mods in trace.modules.items():
        mine = sorted((e.start, e.end) for e in mods
                      if reduce.program_name(e.name) == program
                      and win[0] <= e.start < win[1])
        execs += len(mine)
        starts = [a for a, _ in mine]
        for e in trace.ops.get(dev, []):
            if e.name.startswith(reduce.CONTAINERS):
                continue
            i = bisect.bisect_right(starts, e.start) - 1
            if i >= 0 and e.start < mine[i][1]:
                ops.append(e)
    return ops, execs / max(len(trace.modules), 1)


def scoped_s(trace: Trace, win: Interval, program: str,
             scopes: Dict[str, str]) -> Tuple[Dict[Optional[str], float],
                                              float]:
    """Device seconds by scope (None: unscoped) of ``program_ops``,
    averaged over the devices, and the executions."""
    ops, execs = program_ops(trace, win, program)
    acc: Dict[Optional[str], int] = defaultdict(int)
    for e in ops:
        acc[scopes.get(e.name)] += e.end - e.start
    n = max(len(trace.modules), 1)
    return {k: v / n / 1e9 for k, v in acc.items()}, execs


def scope_ms_per_execution(ctx, scope: str,
                           program: str = PROGRAM) -> Optional[float]:
    """Device ms per execution of ``program`` in the operations of one named
    scope; None where the trace has no device operations, the program
    publishes no scope map or has no such scope, or never ran."""
    if not ctx.trace.ops:
        return None
    scopes = program_scopes(program)
    if not scopes or scope not in scopes.values():
        return None
    secs, execs = scoped_s(ctx.trace, ctx.win, program, scopes)
    if not execs:
        return None
    return 1e3 * secs.get(scope, 0.0) / execs


# -- the tool ---------------------------------------------------------------

def load(path: str, device_prefix: str = "/device:TPU:") -> Trace:
    """``reduce.load``, keeping the program's spans beside the harness's."""
    from jax.profiler import ProfileData
    tr = reduce.load(path, device_prefix)
    found = glob.glob(os.path.join(path, "**", "*.xplane.pb"),
                      recursive=True)
    for plane in ProfileData.from_file(found[0]).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                tr.spans.extend(e for e in reduce._events(line)
                                if e.name.startswith(PROGRAM_SPAN))
    return tr


def idle_gaps(trace: Trace, win: Interval) -> List[Interval]:
    """The intervals of the window with no operation on the first device."""
    dev = sorted(trace.ops)[0]
    busy = reduce.union(reduce._clip(trace.ops[dev], win))
    edges = [win[0]] + [x for iv in busy for x in iv] + [win[1]]
    return [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
            if edges[i + 1] > edges[i]]


def idle_by_span(gaps: List[Interval], spans: List[Event]
                 ) -> Dict[str, float]:
    """Idle seconds by the innermost span open at each instant of them
    (``host`` where none is), by one sweep over every span's edges."""
    marks = []
    for k, s in enumerate(spans):
        if s.name != reduce.WINDOW_SPAN:
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
            inner = (min(open_spans.values(),
                         key=lambda s: s.end - s.start).name
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


def span_ms_per_round(spans: List[Event], win: Interval, rounds: int
                      ) -> Dict[str, float]:
    acc: Dict[str, int] = defaultdict(int)
    for s in spans:
        if win[0] <= s.start < win[1]:
            acc[s.name] += s.end - s.start
    return {k: v / 1e6 / rounds for k, v in sorted(acc.items())}


def report(tr: Trace, rounds: int, compiles: int) -> dict:
    win = reduce.window(tr)
    spans = [s for s in tr.spans if s.name.startswith(PROGRAM_SPAN)]
    per_round = span_ms_per_round(spans, win, rounds)
    out = {"rounds": rounds, "window_s": (win[1] - win[0]) / 1e9,
           "compiles_per_round": compiles / rounds,
           "span_ms_per_round": per_round,
           "host_critical_ms_per_round":
               per_round.get("repro.serve.round", 0.0)
               - per_round.get("repro.serve.wait", 0.0),
           "free_ms_per_round": per_round.get("repro.serve.free", 0.0)}
    if not tr.ops:
        return out
    gaps = idle_gaps(tr, win)
    out["idle_s"] = sum(b - a for a, b in gaps) / 1e9
    out["idle_s_by_span"] = dict(sorted(
        idle_by_span(gaps, tr.spans).items(), key=lambda kv: -kv[1]))
    out["longest_idle_gaps"] = [
        [reduce._innermost(spans, (a + b) // 2) or "host", (b - a) / 1e9]
        for a, b in sorted(gaps, key=lambda g: g[0] - g[1])[:10]]
    scopes = program_scopes() or {}
    secs, execs = scoped_s(tr, win, PROGRAM, scopes)
    if execs:
        total, _ = reduce.program_time(tr, win, PROGRAM)
        out["megastep_ms"] = 1e3 * total / execs
        out["megastep_scope_ms"] = {
            str(k): 1e3 * v / execs
            for k, v in sorted(secs.items(), key=lambda kv: -kv[1])}
        out["megastep_unscoped_share"] = (secs.get(None, 0.0)
                                          / max(sum(secs.values()), 1e-12))
        out["megastep_unscoped_ops"] = _unscoped_ops(tr, win, scopes, execs)
    return out


def _unscoped_ops(tr: Trace, win: Interval, scopes: Dict[str, str],
                  execs: float, n: int = 10) -> List[list]:
    """The ``n`` unscoped operations of the megastep with the most device
    ms per execution."""
    acc: Dict[str, int] = defaultdict(int)
    for e in program_ops(tr, win, PROGRAM)[0]:
        if e.name not in scopes:
            acc[e.name] += e.end - e.start
    k = max(len(tr.modules), 1) * execs * 1e6
    return [[name, ns / k] for name, ns in
            sorted(acc.items(), key=lambda kv: -kv[1])[:n]]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)

    import numpy as np
    from chipbench import harness, spec
    s = harness.setup(spec.ROOT, args.workload)
    from repro.obs import timeplane
    params = harness.draw_weights(s, args.seed)
    server = harness.Server(s.cfg, params, s.conf, s.rules, args.seed)
    server.warm_up(np.random.default_rng([args.seed, 2]))
    stats = timeplane.listen_compiles()
    marks = {}
    trace_dir = tempfile.mkdtemp(prefix="chipbench-attribution-")
    try:
        rep = harness.drive(
            server, s.mix, args.seed, args.seconds, trace_dir,
            on_window_start=lambda: marks.setdefault(
                "start", stats["compiles"]),
            on_window_end=lambda: marks.setdefault("end", stats["compiles"]),
            drain_cap=0.0)
        tr = load(trace_dir)
    finally:
        shutil.rmtree(trace_dir, ignore_errors=True)
    out = {"workload": args.workload, "seed": args.seed}
    out.update(report(tr, rep["rounds"], marks["end"] - marks["start"]))
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
