"""One run of one cell: weights from the seed, warm-up, the measured window
through ``ContinuousBatcher``, the per-layer reading of the trace, and the
comparison with the plain reference that decides ``correct``.

The program is the system under test: this module takes from it the
configuration, the sharding rules, the serving loop and its parameter
layout, and, for the per-layer readers, its own host spans, its metrics
registry's counters and its megastep's scope map.  The harness's host spans
and the client's delivery log are added by wrapping the batcher's bound
methods from here.
"""
from __future__ import annotations

import dataclasses
import gc
import pathlib
import shutil
import sys
import tempfile
import time
from types import SimpleNamespace
from typing import Dict, List, Optional

import numpy as np

from chipbench import attribution, check, e2e, reduce, spec, traffic, weights

# a request due in the window (open loop) or seated in it (backlog) that
# still has no first token this long after the window closes has failed.
# Prompts are fed one token a step: the chat mix's longest (1536 tokens)
# takes about a minute at 8 tokens a round before its first token, and may
# queue behind others first, so a minute would call a late answer wrong
DRAIN_CAP_S = 150.0
# the host spans that make up the host path of a round
HOST_SPANS = tuple(reduce.SPAN + s for s in
                   ("submit", "forcing", "absorb", "plan_round",
                    "apply_plan"))
_WARM_ID = 1 << 40                 # warm-up request ids, apart from traffic


def log(msg: str) -> None:
    print(f"[chipbench] {msg}", file=sys.stderr, flush=True)


def device_check(chips: int) -> dict:
    """The devices JAX sees: a TPU, with as many chips as the cell asks
    for.  Anything else ends the run with no result."""
    import jax
    devs = jax.devices()
    d = devs[0]
    if d.platform != "tpu":
        raise SystemExit(f"chipbench: JAX found no TPU (platform "
                         f"{d.platform!r}); no result")
    if len(devs) < chips:
        raise SystemExit(f"chipbench: the cell needs {chips} chips, JAX "
                         f"sees {len(devs)}; no result")
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(devs)}


def import_program(root: pathlib.Path) -> None:
    src = str(pathlib.Path(root) / "src")
    if src not in sys.path:
        sys.path.insert(0, src)


def enable_cache() -> None:
    """JAX's persistent compilation cache in the checkout (or where
    ``JAX_COMPILATION_CACHE_DIR`` says), for every program: the serving
    loop's many small eager programs compile in under the default one
    second and would otherwise never be cached."""
    import jax
    from repro.launch import compile_cache as CC
    log(f"compile cache: {CC.enable_compile_cache()}")
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)


def model_config(conf: dict):
    from repro.configs import get_config
    return dataclasses.replace(get_config(conf["arch"]),
                               **conf.get("overrides", {}))


def setup(root, workload: str):
    """What every tool builds before its weights: the cell's entries and
    files, the device check, the program and its compile cache, the
    program's configuration and its sharding rules on the cell's chips."""
    bench = spec.load_benchmark(root)
    c = spec.cell(bench, workload)
    conf = spec.config(bench, c["config"], root)
    ref = family_reference(conf["family"], root)
    mix = spec.traffic(c["traffic"], root)
    traffic.check_fits(mix, int(conf["serving"]["max_len"]))
    chips = int(c["chips"])
    device = device_check(chips)
    import_program(root)
    import jax
    from repro.launch.mesh import make_mesh
    from repro.launch.serve import serve_rules_for
    enable_cache()
    cfg = model_config(conf)
    mesh = make_mesh((1, chips), ("data", "model"),
                     devices=jax.devices()[:chips])
    return SimpleNamespace(bench=bench, cell=c, conf=conf, ref=ref, mix=mix,
                           chips=chips, device=device, cfg=cfg,
                           rules=serve_rules_for(cfg, mesh))


def family_reference(family: str, root):
    """``reference/<family>.py``, which has to give the family's plain model
    (``logits``) and the work its lanes need (``lane_flops``).  A family
    without the count is refused here, before any weights are drawn."""
    path = f"{spec.PACKAGE}/reference/{family}.py"
    try:
        ref = spec.reference(family, root)
    except FileNotFoundError:
        ref = None
    if not callable(getattr(ref, "lane_flops", None)):
        raise SystemExit(f"chipbench: no FLOP count for family {family!r}: "
                         f"add lane_flops(cfg, p0, p1) to {path}")
    return ref


def draw_weights(s, seed: int):
    """The cell's weights for ``seed``, on the device, in one jitted call."""
    from repro.models.registry import get_model
    return weights.draw(get_model(s.cfg).init, s.cfg, seed,
                        s.conf["weight_draw"], s.rules)


def make_server(s, params, seed: int) -> "Server":
    """The cell's ``Server`` as ``setup`` describes it."""
    return Server(s.cfg, params, s.conf, s.rules, seed, s.ref.lane_flops)


class Server:
    """The program's ``ContinuousBatcher`` with the client around it: it
    submits the traffic, keeps each request's delivery log on the host
    clock, writes the harness spans, and counts each round's work with the
    family's ``lane_flops``."""

    def __init__(self, cfg, params, conf: dict, rules, seed: int,
                 lane_flops):
        from repro.launch.serve import ContinuousBatcher
        from repro.serving import engine as EG
        from repro.serving.sched import Scheduler
        s = conf["serving"]
        self.cfg, self.conf, self.lane_flops = cfg, conf, lane_flops
        # token ids come from the published vocabulary, never from the
        # padding rows of a padded embedding
        self.vocab = min(cfg.vocab_size,
                         int(conf["model"].get("vocab_size", cfg.vocab_size)))
        self.B, self.K = int(s["batch"]), int(s["megastep"])
        self.max_len, self.page_size = int(s["max_len"]), int(s["page_size"])
        n_chips = 1 if rules is None else rules.mesh.size
        _, n_pages = EG.plan_pages(cfg, self.B, self.max_len,
                                   self.page_size, n_chips)
        sched = Scheduler(slots=self.B, page_size=self.page_size,
                          max_len=self.max_len, megastep_k=self.K,
                          policy=s["policy"], proactive=s["proactive"])
        self.srv = ContinuousBatcher(
            cfg, params, batch=self.B, max_len=self.max_len,
            page_size=self.page_size, rules=rules, megastep_k=self.K,
            scheduler=sched, n_pages=n_pages, auto_refill=False, seed=seed)
        self.sched = sched
        self.t0 = time.perf_counter()
        self.records: Dict[int, e2e.Record] = {}
        self.round_flops: List[float] = []
        self._wrap()

    # -- instrumentation ----------------------------------------------------

    def _wrap(self):
        import jax
        srv = self.srv
        for obj, attr in ((srv, "_forcing"), (srv, "_absorb"),
                          (srv, "_apply_plan"), (self.sched, "plan_round")):
            fn = getattr(obj, attr)
            label = reduce.SPAN + attr.lstrip("_")

            def wrapped(*a, _fn=fn, _label=label, **k):
                with jax.profiler.TraceAnnotation(_label):
                    return _fn(*a, **k)
            setattr(obj, attr, wrapped)
        absorb = srv._absorb

        def absorb_and_log(toks, p0, p1):
            t = time.perf_counter() - self.t0
            lanes = [(r, len(r.sampled)) for r in self.sched.lanes
                     if r is not None]
            out = absorb(toks, p0, p1)
            for r, before in lanes:
                n = len(r.sampled) - before
                rec = self.records.get(r.req_id)
                if n and rec is not None:
                    rec.deliveries.append((t, n))
            self.round_flops.append(sum(
                self.lane_flops(self.cfg, a, b) for a, b in zip(p0, p1)))
            return out
        srv._absorb = absorb_and_log

    def now(self) -> float:
        return time.perf_counter() - self.t0

    # -- traffic --------------------------------------------------------------

    def submit(self, req: traffic.Request, due: float,
               req_id: Optional[int] = None) -> None:
        from repro.serving.sched import Request
        rid = req.idx if req_id is None else req_id
        if req_id is None:
            self.records[rid] = e2e.Record(due=due)
        self.sched.submit(Request(req_id=rid, prompt=req.prompt,
                                  max_new_tokens=req.max_new,
                                  arrival=self.sched.clock))

    def step(self) -> None:
        import jax
        with jax.profiler.TraceAnnotation(reduce.ROUND_SPAN):
            self.srv.step_round()

    def warm_up(self, rng: np.random.Generator) -> None:
        """Compile and run every program the window uses: admissions,
        forced prompt tokens, sampling, completions and the frees, over two
        waves of short requests."""
        V = self.vocab
        for wave in range(2):
            for i in range(self.B if wave == 0 else self.B // 2):
                req = traffic.Request(
                    idx=0, prompt=rng.integers(0, V, self.K + 3,
                                               dtype=np.int32),
                    max_new=self.K + 2 + i % 3, gap_s=0.0)
                self.submit(req, 0.0, req_id=_WARM_ID + wave * self.B + i)
            while not self.sched.drained:
                self.step()
        self.round_flops.clear()

    # -- after the window ---------------------------------------------------

    def unserved(self, ids) -> List[int]:
        return [i for i in ids if self.records[i].first_token_at is None]

    def block_table_mismatches(self) -> Optional[int]:
        import jax.numpy as jnp
        st = self.srv.state
        if "table" not in st:
            return None
        return int(self.srv.pt.verify_block_table(
            st["table"], st["seq_ids"], jnp.asarray(self.srv.pos),
            st["block_table"], page_size=self.page_size))

    def probe_p99(self) -> Optional[float]:
        from repro.serving import page_table as PT
        st = self.srv.state
        return None if "table" not in st else PT.PageTable.probe_p99(
            st["table"])

    def finished(self) -> list:
        return [r for r in self.sched.finished if r.req_id in self.records]

    def release(self) -> None:
        """Drop the program's serving state (the KV pools and the recurrent
        state) so that the reference has the memory."""
        self.srv.state = None
        self.srv.tokens = None
        gc.collect()


def drive(server: Server, mix: dict, seed: int, seconds: float,
          trace_dir: Optional[str] = None, on_window_start=None,
          on_window_end=None, drain_cap: Optional[float] = None) -> dict:
    """The lead-in, the measured window (traced into ``trace_dir`` where one
    is given) and the drain after it.  Returns what the run reports about
    its requests.

    An open-loop mix with ``lead_in_s`` starts its arrivals that long before
    the window opens, so that the window finds the lanes and the queue as
    the load keeps them, not empty; those requests are served but not in
    the time-to-first-token sample."""
    import jax
    reqs = traffic.requests(mix, seed, server.vocab)
    open_loop = mix["loop"] == "open"
    depth = int(mix.get("backlog_per_lane", 0)) * server.B
    lead = float(mix.get("lead_in_s", 0.0)) if open_loop else 0.0
    nxt = next(reqs)
    due = -lead

    def feed(now: float) -> None:
        nonlocal nxt, due
        with jax.profiler.TraceAnnotation(reduce.SPAN + "submit"):
            if open_loop:
                while due <= now:
                    server.submit(nxt, due)
                    nxt = next(reqs)
                    due += nxt.gap_s
            else:
                while len(server.sched.queue) < depth:
                    server.submit(nxt, now)
                    nxt = next(reqs)

    def idle_until_due() -> None:
        # an open loop with nothing queued or running waits for its next
        # arrival instead of dispatching empty megasteps
        if open_loop and server.sched.drained:
            time.sleep(max(0.0, due - server.now()))

    def serve_until(end: float) -> int:
        rounds = 0
        while (now := server.now()) < end:
            feed(now)
            idle_until_due()
            if server.now() >= end:
                break
            if not server.sched.drained:
                server.step()
                rounds += 1
        return rounds

    # the window opens at time 0, ``lead`` seconds from now
    server.t0 = time.perf_counter() + lead
    serve_until(0.0)
    server.round_flops.clear()
    if on_window_start is not None:
        on_window_start()
    if trace_dir is not None:
        # host spans and device operations; no per-call Python tracing,
        # which would slow the host and swell the trace
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
    with jax.profiler.TraceAnnotation(reduce.WINDOW_SPAN):
        rounds = serve_until(seconds)
    if trace_dir is not None:
        jax.profiler.stop_trace()
    if on_window_end is not None:
        on_window_end()
    window_flops = sum(server.round_flops)
    if open_loop:
        feed(server.now())          # arrivals due while the last round ran
        owed = [i for i, r in server.records.items() if r.due < seconds]
    else:
        owed = [r.req_id for r in server.sched.running()]
        owed += [r.req_id for r in server.finished()]
    # the drain: no request owed a first token drops out of the sample;
    # its cap counts from here, after the trace has been written out
    deadline = server.now() + (DRAIN_CAP_S if drain_cap is None
                               else drain_cap)
    while server.unserved(owed) and server.now() < deadline:
        if open_loop:
            feed(server.now())
            idle_until_due()
        if not server.sched.drained:
            server.step()
    return {"rounds": rounds, "owed": owed,
            "unserved": server.unserved(owed), "window_flops": window_flops}


def registry_counts(srv) -> Dict[str, float]:
    """The program registry's cumulative counts: its counters and its
    compile listener's ``jit_compiles`` and ``jit_compile_s``."""
    snap = srv.metrics.snapshot()
    counts = dict(snap["counters"])
    counts.update((k, v) for k, v in snap["gauges"].items()
                  if k.startswith("jit_"))
    return counts


def traced_drive(server: Server, mix: dict, seed: int, seconds: float,
                 drain_cap: Optional[float] = None):
    """``drive`` with the window traced.  Returns its report, the trace, and
    the registry's counts differenced over the window."""
    edges = {}

    def at(edge):
        return lambda: edges.setdefault(edge, registry_counts(server.srv))
    trace_dir = tempfile.mkdtemp(prefix="chipbench-trace-")
    try:
        rep = drive(server, mix, seed, seconds, trace_dir,
                    on_window_start=at("start"), on_window_end=at("end"),
                    drain_cap=drain_cap)
        tr = reduce.load(trace_dir)
    finally:
        shutil.rmtree(trace_dir, ignore_errors=True)
    counts = {k: v - edges["start"].get(k, 0)
              for k, v in edges["end"].items()}
    return rep, tr, counts


def reader_context(server: Server, tr, rep: dict, counts: dict, chips: int,
                   peaks: dict) -> SimpleNamespace:
    """What a per-layer reader reads: the trace and its window, the rounds
    and operations of the window, the chips and their peaks, the program's
    counts over the window with the page table's probe length after it,
    the harness's host spans, and the megastep's scope map (built only
    where the trace has device operations for it to name)."""
    return SimpleNamespace(
        trace=tr, win=reduce.window(tr), rounds=rep["rounds"],
        window_flops=rep["window_flops"], chips=chips, peaks=peaks,
        counters=dict(counts, probe_p99=server.probe_p99()),
        host_spans=HOST_SPANS,
        scopes=attribution.program_scopes() if tr.ops else None)


def end_to_end(server: Server, seconds: float, names) -> dict:
    """The cell's end-to-end metrics; one with no sample to read (a run
    that served nothing) stays out of the line."""
    recs = list(server.records.values())
    samples = {
        "tokens_per_s": lambda: [e2e.tokens_delivered(recs, seconds)
                                 / seconds],
        "tpot_p95_ms": lambda: [1e3 * e2e.percentile(s, 95) for s in
                                [e2e.tpot_samples(recs, seconds)] if s],
        "ttft_p90_ms": lambda: [1e3 * e2e.percentile(s, 90) for s in
                                [e2e.ttft_samples(recs, seconds)] if s],
    }
    out = {}
    for m in names:
        v = samples[m["name"]]() if m["name"] in samples else []
        if v:
            out[m["name"]] = {"value": v[0], "unit": m["unit"]}
    return out


def per_layer(root, names, ctx) -> dict:
    out = {}
    for m in names:
        v = spec.metric_reader(m["name"], root).read(ctx)
        if v is not None:
            out[m["name"]] = {"value": v, "unit": m["unit"]}
    return out


def peak_bytes() -> Optional[int]:
    import jax
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in jax.local_devices()]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


def run(root, workload: str, seed: int, seconds: float, trace: bool,
        t_start: float) -> dict:
    """One run of ``workload``; returns the result line's object."""
    s = setup(root, workload)
    import jax
    from repro.launch import compile_cache as CC
    bench, conf, chips, device = s.bench, s.conf, s.chips, s.device
    params = draw_weights(s, seed)
    server = make_server(s, params, seed)
    server.warm_up(np.random.default_rng([seed, 2]))
    jax.block_until_ready(server.srv.state)

    with CC.count_compiles() as compiles:
        if trace:
            rep, tr, counts = traced_drive(server, s.mix, seed, seconds)
        else:
            rep = drive(server, s.mix, seed, seconds)
    setup_s = server.t0 - t_start
    log(f"compiles in the window and drain: {compiles['compiles']} "
        f"({compiles['compile_s']:.3f} s)")
    device["memory_peak_bytes"] = peak_bytes()
    mism = server.block_table_mismatches()

    result = {"correct": False,
              "attempted": len(rep["owed"]),
              "failed": len(rep["unserved"])}
    if trace:
        ctx = reader_context(server, tr, rep, counts, chips,
                             spec.peaks(device["kind"], root))
        result["metrics"] = per_layer(
            root, spec.metrics_for(bench, workload, trace=True), ctx)
        device["busy_s"] = reduce.device_busy_s(tr, ctx.win)
        device["window_s"] = (ctx.win[1] - ctx.win[0]) / 1e9
        result["breakdown"] = {
            "device_ops": [list(x) for x in reduce.top_ops(tr, ctx.win)],
            "idle_gaps": [list(x) for x in reduce.idle_gaps(tr, ctx.win)]}
        del tr, ctx
    else:
        result["metrics"] = end_to_end(
            server, seconds, spec.metrics_for(bench, workload, trace=False))
        result["metrics"]["setup_s"] = {"value": setup_s, "unit": "s"}
    result["device"] = device

    finished = server.finished()
    server.release()
    numbers = check.gap_numbers(check.token_gaps(
        s.ref, params, conf["model"], finished,
        np.random.default_rng([seed, 3])))
    checks = check.judge(conf["limits"], numbers,
                         unserved=len(rep["unserved"]),
                         block_table_mismatches=mism)
    result["correct"] = check.passes(checks)
    for name, v in checks.items():
        log(f"check {name}: {v['value']} (limit {v['limit']})")
    result["checks"] = checks
    return result
