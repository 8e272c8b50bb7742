"""Put device time and idle time down to the program's own names: the
named scopes of the serving megastep and the program's host spans
(``repro.*``), both on the trace's one clock (``repro/obs/timeplane.py``).

The per-layer readers of scoped device time call ``scope_ms_per_execution``.
As a one-process tool for the chip,

    python3 -m chipbench.attribution --workload <cell> --seed <n> --seconds <s>

runs one traced window of a cell as a ``--trace 1`` run does, and prints one
JSON object: each program span's time per round; what the readers
``host_critical_ms_per_round``, ``free_ms_per_round`` and
``compiles_per_round`` read; the device's idle time split by the span that
names each instant (``reduce.idle_by_span``); the longest idle gaps; the
megastep's device time per execution split by scope, with its unscoped
share and the unscoped operations that lead it; and the batcher's attention
page reads (``ContinuousBatcher.attention_reads``).
"""
from __future__ import annotations

import argparse
import bisect
import json
from collections import defaultdict
from typing import Dict, List, Optional, Tuple

from chipbench import reduce, spec
from chipbench.reduce import Event, Interval, Trace

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
    published no scope map (``ctx.scopes``) or has no such scope, or never
    ran."""
    if not ctx.trace.ops:
        return None
    scopes = ctx.scopes
    if not scopes or scope not in scopes.values():
        return None
    secs, execs = scoped_s(ctx.trace, ctx.win, program, scopes)
    if not execs:
        return None
    return 1e3 * secs.get(scope, 0.0) / execs


# -- the tool ---------------------------------------------------------------

# the per-layer readers whose numbers the report gives beside its own
READERS = ("host_critical_ms_per_round", "free_ms_per_round",
           "compiles_per_round")


def span_ms_per_round(spans: List[Event], win: Interval, rounds: int
                      ) -> Dict[str, float]:
    acc: Dict[str, int] = defaultdict(int)
    for s in spans:
        if win[0] <= s.start < win[1]:
            acc[s.name] += s.end - s.start
    return {k: v / 1e6 / rounds for k, v in sorted(acc.items())}


def report(ctx) -> dict:
    """What a traced window's ``ctx`` (``harness.reader_context``) says of
    where its time went."""
    tr, win = ctx.trace, ctx.win
    out = {"rounds": ctx.rounds, "window_s": (win[1] - win[0]) / 1e9,
           "span_ms_per_round": span_ms_per_round(tr.program_spans, win,
                                                  ctx.rounds)}
    out.update((m, spec.metric_reader(m).read(ctx)) for m in READERS)
    if not tr.ops:
        return out
    gaps = reduce.idle_intervals(tr, win)
    out["idle_s"] = sum(b - a for a, b in gaps) / 1e9
    out["idle_s_by_span"] = dict(sorted(
        reduce.idle_by_span(gaps, tr.program_spans + tr.spans).items(),
        key=lambda kv: -kv[1]))
    out["longest_idle_gaps"] = [list(g) for g in reduce.idle_gaps(tr, win)]
    scopes = ctx.scopes or {}
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
    from chipbench import harness
    s = harness.setup(spec.ROOT, args.workload)
    params = harness.draw_weights(s, args.seed)
    server = harness.make_server(s, params, args.seed)
    server.warm_up(np.random.default_rng([args.seed, 2]))
    rep, tr, counts = harness.traced_drive(server, s.mix, args.seed,
                                           args.seconds, drain_cap=0.0)
    ctx = harness.reader_context(server, tr, rep, counts, s.chips,
                                 spec.peaks(s.device["kind"]))
    out = {"workload": args.workload, "seed": args.seed}
    out.update(report(ctx))
    out["attention_reads"] = server.srv.attention_reads()
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
