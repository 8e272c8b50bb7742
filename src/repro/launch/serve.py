"""Serving driver: continuous batching over the paged engine, scheduled by
``repro.serving.sched``.

The driver is deliberately THIN: it owns the engine state and the megastep
dispatch (plus the reactive refused-suffix re-issue safety net); every
admit / evict / preempt / grow decision lives in the scheduler.  One round:

1. build the per-lane teacher-forcing arrays (chunked prefill: a lane whose
   request is still consuming its prompt gets its next <=K prompt tokens
   forced inside the SAME megastep budget the decoding lanes sample under);
2. dispatch ONE K-token megastep (``engine.make_serve_megastep``) — the
   host syncs once per K tokens;
3. absorb the sampled tokens into their requests (TTFT accounting) and, in
   CI mode, verify the incremental block-table cache against the wait-free
   lookup;
4. reactive safety net: if any lane ABORTed (forecaster off / capped), run
   the Section 4.3 rebuild into a 2x pool — the frozen pending token means
   the refused suffix re-issues automatically next round;
5. ask the scheduler for the round's Plan (completions, admissions,
   preemptions, proactive growth) and apply it to the engine state:
   ``free_sequences`` + block-row invalidation for evicted lanes,
   ``rebuild_page_table`` for proactive growth (BEFORE the next dispatch —
   the allocator never aborts and the wait-free read path never sees a
   mid-flight rebuild), fresh sequence ids at position 0 for admissions.

With ``Scheduler(proactive=False)`` the driver degenerates to the old
reactive batcher (admit greedily, rebuild after the abort) — the baseline
the adversarial churn tests compare against.

The entry point builds its mesh from the devices it sees (one data row,
the model axis as wide as the device count) and draws the parameters on
the device from ``--seed``; ``run`` is the same serving loop for callers
that bring their own config and parameters (``chip_smoke.py``).

Usage (CPU smoke):
  PYTHONPATH=src python -m repro.launch.serve --arch qwen2.5-32b --smoke \
      --rounds 6 --batch 4 --max-len 48 --megastep 4 --policy deadline \
      --requests 24 --verify-block-table --fail-on-abort
"""
from __future__ import annotations

import argparse
import dataclasses
import logging
import os
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs as OBS
from repro.configs import ARCH_IDS, get_config, get_smoke_config
from repro.dist.sharding import serve_manual_rules, serve_rules
from repro.kernels import stats as KS
from repro.launch import compile_cache as CC
from repro.launch.mesh import make_serve_mesh
from repro.models.registry import init_params
from repro.serving import engine as EG
from repro.serving import page_table as PT
from repro.serving.sched import (Scheduler, churn_request,
                                 synthetic_workload)

logger = logging.getLogger(__name__)


class ContinuousBatcher:
    """Thin driver: B decode slots, one K-token megastep per round, all
    policy in ``scheduler``.  ``n_pages`` overcommits the page pool (the
    scheduler's headroom controller keeps it out of ABORT); ``auto_refill``
    reproduces the endless eviction-churn stream of the old batcher when no
    explicit workload is submitted."""

    def __init__(self, cfg, params, *, batch: int, max_len: int,
                 page_size: int, rules=None, seed: int = 0,
                 megastep_k: int = 1, verify_block_table: bool = False,
                 scheduler: Scheduler | None = None,
                 n_pages: int | None = None, auto_refill: bool = True,
                 tracer: OBS.Tracer | None = None):
        self.cfg, self.params = cfg, params
        self.B, self.max_len, self.page_size = batch, max_len, page_size
        self.K = max(1, int(megastep_k))
        self.verify = verify_block_table
        self.auto_refill = auto_refill
        # one facade bound to cfg's probe strategy — every PT call below
        # goes through it so the allocator semantics (and the Headroom
        # slack the scheduler consumes) stay consistent per config
        self.strategy = getattr(cfg, "probe_strategy", "linear")
        self.pt = PT.for_strategy(self.strategy)
        self.state, _ = EG.make_decode_state(cfg, batch, S_max=max_len,
                                             rules=rules,
                                             page_size=page_size,
                                             n_pages=n_pages)
        self.state["active"] = jnp.zeros((batch,), bool)  # no lanes seated
        # the state is donated: each megastep updates the KV pools in place
        # instead of holding a second copy of them
        self._megastep = EG.make_serve_megastep(
            cfg, S_max=max_len, K=self.K, rules=rules, page_size=page_size)
        self.mega_fn = jax.jit(self._megastep, donate_argnums=(1,))
        pool = EG.decode_headroom(self.state, strategy=self.strategy)
        self.sched = scheduler or Scheduler(
            slots=batch, page_size=page_size, max_len=max_len,
            megastep_k=self.K)
        self.sched.K = self.K
        self.sched.n_pages = None if pool is None else pool.n_pages
        # telemetry (obs/): span tracer shared with the scheduler, metrics
        # registry absorbing the repo's measurement surfaces, and the
        # cumulative device-counter snapshot the per-K sync differences
        self.tracer = tracer
        self.sched.tracer = tracer
        self.metrics = OBS.MetricsRegistry()
        self.metrics.source("fallback",
                            lambda: EG.fallback_report(cfg, rules))
        self.metrics.source("kernel", lambda: dict(KS.KERNEL_STATS))
        self.metrics.source("probe", lambda: dict(PT.PROBE_STATS))
        OBS.listen_compiles()
        self.metrics.source("jit", OBS.compile_stats)
        # the decode-attention path the megastep was built with, and the
        # KV pages the jnp gather reads per attention layer and token step
        # whatever the lanes hold; the pages read are counted each round
        # from positions the round fetches anyway (``attention_reads``)
        self.attn_path = EG.attention_path(cfg, rules)
        self.gather_pages = EG.gather_pages(cfg, rules, batch, max_len,
                                            page_size)
        self.metrics.set_info("attention_path", self.attn_path or "none")
        self._ctr_prev: dict = {}
        # the megastep's scope map, built when first asked for; published
        # so that an in-process trace reader can name its device operations
        self._scopes: dict | None = None
        OBS.publish_scopes("megastep", self.megastep_scopes)
        # quiet engine degradations (kernel fallbacks, gspmd decode, oracle
        # probe path) surface once at startup, not only in dryrun/CI
        logger.info("engine fallback report: %s",
                    EG.fallback_report(cfg, rules))
        self.pos = np.zeros(batch, np.int32)
        self.tokens = jnp.zeros((batch, 1), jnp.int32)
        self.next_seq_id = batch
        self.rng = np.random.default_rng(seed + 1)
        self._next_auto_id = 1 << 20          # ids disjoint from workloads
        # per-lane teacher-forcing view (set at admission)
        self.lane_known = [np.zeros((0,), np.int32)] * batch
        self.lane_stop = np.zeros(batch, np.int32)

    # -- compat conveniences ---------------------------------------------

    @property
    def evictions(self) -> int:
        return (self.sched.stats.completed
                + self.sched.stats.preemptive_evictions)

    @property
    def rebuilds(self) -> int:
        return (self.sched.stats.pool_grows
                + self.sched.stats.reactive_rebuilds)

    def table_stats(self):
        if "table" not in self.state:
            return None
        return self.pt.stats(self.state["table"])

    # -- the round --------------------------------------------------------

    def _check_block_table(self):
        mism = int(self.pt.verify_block_table(
            self.state["table"], self.state["seq_ids"],
            jnp.asarray(self.pos), self.state["block_table"],
            page_size=self.page_size))
        if mism:
            raise RuntimeError(
                f"block-table cache diverged from the wait-free lookup "
                f"({mism} entries) — invalidation/update invariant broken")

    def _refill(self):
        """Endless-churn mode: keep the queue deep enough that every free
        slot can re-admit (the old batcher's workload, as Requests)."""
        sch = self.sched
        deficit = self.B - len(sch.running()) - len(sch.queue)
        for _ in range(max(deficit, 0)):
            sch.submit(churn_request(self._next_auto_id, self.rng,
                                     vocab_size=self.cfg.vocab_size,
                                     max_len=self.max_len))
            self._next_auto_id += 1

    def _forcing(self):
        """Teacher-forcing arrays for this round: chunked prefill shares
        the megastep budget with decode (see engine._mega_scan)."""
        B, K = self.B, self.K
        forced = np.zeros((B, K), np.int32)
        fmask = np.zeros((B, K), bool)
        for s, req in enumerate(self.sched.lanes):
            if req is None:
                continue
            known = self.lane_known[s]
            p0 = int(self.pos[s])
            for k in range(K):
                sp = p0 + k + 1
                if sp < known.size:
                    forced[s, k] = known[sp]
                    fmask[s, k] = True
        return forced, fmask

    def _absorb(self, toks: np.ndarray, p0: np.ndarray, p1: np.ndarray):
        """Fold the round's sampled tokens back into their requests.
        ``toks[s, k]`` is the token at sequence position ``p0[s]+k+1``;
        positions below the lane's known length were forced (prompt), at or
        above it they are model samples."""
        clk = self.sched.clock
        for s, req in enumerate(self.sched.lanes):
            if req is None:
                continue
            nk = self.lane_known[s].size
            stop = int(self.lane_stop[s])
            for k in range(int(p1[s]) - int(p0[s])):
                sp = int(p0[s]) + k + 1
                if nk <= sp < stop:
                    req.sampled.append(int(toks[s, k]))
                    if req.first_token_at is None:
                        req.first_token_at = clk
                        self._emit("first_token", req=req.req_id)

    def _apply_plan(self, plan):
        evict = plan.evict_slots
        if evict:
            with OBS.span("serve.free"):
                self._free_lanes(evict)
        if plan.grow_to is not None and "table" in self.state:
            # PROACTIVE Section 4.3 rebuild: before the abort, between
            # megasteps — the wait-free read path never sees it mid-flight.
            # Traced as "rebuild" (eager, atomic), NOT "grow": only the
            # sharded table's lazy resize opens a frozen-old-table window.
            with OBS.span("serve.rebuild"):
                self.state = EG.rebuild_page_table(self.state,
                                                   n_pages=plan.grow_to,
                                                   strategy=self.strategy)
            self._emit("rebuild", reason="grow", n_pages=plan.grow_to)
        if plan.admissions:
            with OBS.span("serve.admit"):
                self._admit(plan.admissions)

    def _free_lanes(self, evict):
        """Delete the evicted lanes' pages, invalidate their block-table
        rows and deactivate them."""
        if "table" in self.state:
            mask = np.zeros(self.B, bool)
            mask[evict] = True
            dmask = jnp.asarray(mask)
            maxP = -(-self.max_len // self.page_size)
            t_before = self.state["table"]
            self.state["table"] = self.pt.free_sequences(
                self.state["table"], self.state["seq_ids"],
                jnp.asarray(self.pos), page_size=self.page_size,
                max_pages=maxP, active=dmask)
            if "counters" in self.state:
                # eager scalar adds between rounds — still no extra syncs
                self.state["counters"] = OBS.note_free(
                    self.state["counters"], table_before=t_before,
                    table_after=self.state["table"])
            self.state["block_table"] = self.pt.invalidate_block_rows(
                self.state["block_table"], dmask)
        active = np.asarray(self.state["active"]).copy()
        active[evict] = False
        self.state["active"] = jnp.asarray(active)

    def _admit(self, admissions):
        """Seat each admitted request in its slot: a fresh sequence id at
        position 0, its known tokens, and zeroed recurrent state."""
        st = self.sched
        seq_ids = np.asarray(self.state["seq_ids"]).copy()
        active = np.asarray(self.state["active"]).copy()
        aborted = np.asarray(self.state["aborted"]).copy()
        tokens = np.asarray(self.tokens).copy()
        self._reset_recurrent_state([s for s, _ in admissions])
        for slot, req in admissions:
            known = req.known_tokens()
            self.lane_known[slot] = known
            self.lane_stop[slot] = st.stop_of(req)
            seq_ids[slot] = self.next_seq_id
            self.next_seq_id += 1
            self.pos[slot] = 0
            active[slot] = True
            aborted[slot] = False
            tokens[slot, 0] = known[0]
            # fresh admissions start at pos 0 with no pages, so the
            # invalidated (-1) block-table rows ARE the correct cache;
            # an admission carrying prefilled pages would rebuild its
            # rows from the wait-free lookup
            # (PageTable.rebuild_block_table)
        self.state["seq_ids"] = jnp.asarray(seq_ids)
        self.state["active"] = jnp.asarray(active)
        self.state["aborted"] = jnp.asarray(aborted)
        self.state["pos"] = jnp.asarray(self.pos)
        self.tokens = jnp.asarray(tokens)

    def _reset_recurrent_state(self, slots):
        """Zero the admitted lanes' PER-LANE recurrent state.  Paged KV
        needs nothing (freed pages are unreachable once the block-table
        rows are invalidated), but the SSM recurrence (mamba ``h`` / conv
        tails) and the ring buffers carry the previous occupant's history
        in-place — a re-seated request must start from the same zero state
        a fresh ``make_decode_state`` would give it, or its decode (and the
        'lossless recompute preemption' invariant) is silently wrong."""
        adm = np.zeros(self.B, bool)
        adm[slots] = True
        amask = jnp.asarray(adm)

        def rows(t, batch_dim, fill):
            shape = [1] * t.ndim
            shape[batch_dim] = -1
            return jnp.where(amask.reshape(shape),
                             jnp.full_like(t, fill), t)

        if "ssm" in self.state:
            self.state["ssm"] = jax.tree.map(
                lambda t: rows(t, 1, 0), self.state["ssm"])
        if "ring_k" in self.state:
            self.state["ring_k"] = rows(self.state["ring_k"], 1, 0)
            self.state["ring_v"] = rows(self.state["ring_v"], 1, 0)
            self.state["ring_pos"] = rows(self.state["ring_pos"], 0, -1)

    def _emit(self, event: str, **fields):
        if self.tracer is not None:
            self.tracer.emit(event, self.sched.clock, **fields)

    def _emit_decode(self, p0: np.ndarray, p1: np.ndarray):
        """Per-round decode span: which requests decoded, how many tokens
        landed, how many page-boundary allocations they implied (derived
        from positions — exact regardless of the telemetry knob)."""
        reqs = [r.req_id for r in self.sched.lanes if r is not None]
        if self.tracer is None or not reqs:
            return
        ps = self.page_size
        pages = 0
        for s, r in enumerate(self.sched.lanes):
            if r is None:
                continue
            pages += sum(1 for p in range(int(p0[s]), int(p1[s]))
                         if p % ps == 0)
        self._emit("decode", reqs=reqs,
                   tokens=int((p1 - p0).sum()), pages=pages)

    def _read_counters(self):
        """Fetch the device counter plane at the per-K sync (the buffers
        are already on their way for ``pos`` — zero extra dispatches) and
        fold the round's delta into the metrics registry."""
        if "counters" not in self.state:
            return None
        snap = OBS.snapshot(self.state["counters"])
        d = OBS.delta(snap, self._ctr_prev)
        self._ctr_prev = snap
        for k, v in d.items():
            if v:
                self.metrics.inc(k, v)
        return d

    def step_round(self):
        """One scheduled megastep round (K tokens per occupied lane).  Each
        phase is a host span (``obs.span``) on the profiler's clock."""
        with OBS.span("serve.round"):
            if self.auto_refill:
                self._refill()
            with PT.probe_stats_scope() as ps:
                with OBS.span("serve.forcing"):
                    forced, fmask = self._forcing()
                p0 = self.pos.copy()
                seated = np.array([r is not None for r in self.sched.lanes])
                with OBS.span("serve.dispatch"):
                    toks, self.state = self.mega_fn(
                        self.params, self.state, self.tokens,
                        jnp.asarray(self.lane_stop), jnp.asarray(forced),
                        jnp.asarray(fmask))
                    # pending feed (the refused token for aborts)
                    self.tokens = toks[:, -1:]
                with OBS.span("serve.wait"):
                    # 1 host sync per K tokens: the megastep's outputs
                    self.pos = np.asarray(self.state["pos"]).copy()
                    toks = np.asarray(toks)
                    aborted = self.state.get("aborted")
                    n_ab = (0 if aborted is None
                            else int(np.asarray(aborted).sum()))
                p1 = self.pos.copy()
                self.sched.advance(self.K)
                with OBS.span("serve.absorb"):
                    self._absorb(toks, p0, self.pos)
                    self._emit_decode(p0, self.pos)
                if self.verify and "table" in self.state:
                    self._check_block_table()
                if n_ab:
                    # REACTIVE safety net (forecaster off / capped /
                    # wrong): grow the pool, re-hash, move the KV pages,
                    # rebuild the block-table cache, clear the flags; the
                    # refused suffix is re-issued by the next megastep at
                    # the frozen positions
                    n_pages = self.state["pools"].k.shape[1]
                    with OBS.span("serve.rebuild"):
                        self.state = EG.rebuild_page_table(
                            self.state, n_pages=n_pages * 2,
                            strategy=self.strategy)
                    self.sched.note_aborts(n_ab, grew_to=n_pages * 2)
                    self._emit("rebuild", reason="reactive",
                               n_pages=n_pages * 2)
                with OBS.span("serve.headroom"):
                    pool = EG.decode_headroom(self.state,
                                              strategy=self.strategy)
                plan = self.sched.plan_round(self.pos, pool)
                with OBS.span("serve.apply_plan"):
                    self._apply_plan(plan)
                probed = ps["keys_probed"]
            with OBS.span("serve.telemetry"):
                self._telemetry(pool, probed)
                self._count_attention_reads(p0, p1, seated)
            self.sched.end_round(keys_probed=probed)
            return plan

    def _telemetry(self, pool, probed: int):
        """The round's counters and gauges into the registry, and its
        ``round`` event into the tracer."""
        self.metrics.inc("keys_probed", probed)
        ctr = self._read_counters()
        if pool is not None:
            self.metrics.set_gauge("live_pages", pool.live_pages)
            self.metrics.set_gauge("tombstones", pool.tombstones)
            self.metrics.set_gauge("free_cells", pool.free_cells)
            self.metrics.set_gauge("occupancy", pool.occupancy)
        if self.tracer is not None:
            health = None
            if "table" in self.state:
                t = self.state["table"]
                n = int(self.state["pools"].k.shape[1])
                tombs = int(t.num_tombs)
                health = {
                    "live": int(t.num_keys), "tombs": tombs, "n_cells": n,
                    "free": n - int(t.num_keys),
                    "tomb_density": tombs / max(n, 1),
                    "occupancy": (int(t.num_keys) + tombs) / max(n, 1),
                    "probe_p99": PT.PageTable.probe_p99(t),
                    "migrated": 0, "migration_left": 0}
            self._emit("round", counters=ctr, health=health,
                       keys_probed=probed)

    def _count_attention_reads(self, p0, p1, seated):
        """The round's KV page reads into the registry: ``attn_live_pages``
        (what the fused kernel reads), ``attn_gather_pages`` (what the jnp
        gather reads), both per attention layer over the K token steps."""
        if self.attn_path is None:
            return
        self.metrics.inc("attn_token_steps", self.K)
        self.metrics.inc("attn_live_pages", EG.live_pages_read(
            p0, p1, seated, self.K, self.page_size))
        self.metrics.inc("attn_gather_pages", self.K * self.gather_pages)

    def attention_reads(self) -> dict:
        """The attention path and, per attention layer and token step, the
        KV pages it read and the pages the jnp gather reads; ``live_share``
        is their ratio on the kernel, the share of the gather's reads that
        hold a live token."""
        m = self.metrics
        steps = max(m.counter("attn_token_steps"), 1)
        live = m.counter("attn_live_pages") / steps
        gather = m.counter("attn_gather_pages") / steps
        fused = self.attn_path == "fused_decode_kernel"
        return {"path": self.attn_path or "none",
                "pages_read_per_step": live if fused else gather,
                "gather_pages_per_step": gather,
                "live_share": live / gather if gather else 0.0}

    def megastep_scopes(self) -> dict:
        """HLO instruction name -> named scope (``obs.SCOPES``) of the
        megastep compiled for this batcher's shapes: the names a device
        trace gives the megastep's operations.  The first call compiles the
        megastep once more (``obs.fresh_hlo_text``)."""
        if self._scopes is None:
            B, K = self.B, self.K
            args = (self.params, self.state, self.tokens,
                    jnp.asarray(self.lane_stop),
                    jnp.zeros((B, K), jnp.int32), jnp.zeros((B, K), bool))
            self._scopes = OBS.scope_map(OBS.fresh_hlo_text(
                self._megastep, args, donate_argnums=(1,)))
        return self._scopes

    def decode_round(self, steps: int):
        """Drive ~``steps`` decode steps (ceil(steps / K) rounds)."""
        for _ in range(-(-steps // self.K)):
            self.step_round()

    def run_until_drained(self, max_rounds: int = 1000) -> bool:
        """Run until every submitted request completed (requires
        ``auto_refill=False``).  Returns True when drained."""
        for _ in range(max_rounds):
            if self.sched.drained:
                return True
            self.step_round()
        return self.sched.drained

    # -- telemetry exporters ----------------------------------------------

    def metrics_text(self) -> str:
        """Prometheus text-exposition snapshot of the registry."""
        return self.metrics.prometheus_text()

    def metrics_json(self) -> str:
        """JSON snapshot of the registry (same numbers)."""
        return self.metrics.json_snapshot()

    def emit_summary(self):
        """Final trace line: the scheduler roll-up (invariant 3 of
        tools/trace_report.py reconciles its abort count against the
        trace's abort events)."""
        self._emit("summary", **self.sched.summary())


def _span(text: str):
    """An inclusive ``LO,HI`` range flag."""
    lo, hi = (int(v) for v in text.split(","))
    return lo, hi


def parse_args(argv=None) -> argparse.Namespace:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen2.5-32b", choices=sorted(ARCH_IDS))
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--rounds", type=int, default=6,
                    help="print intervals (endless churn) or max run length"
                         " x steps-per-round (fixed workload)")
    ap.add_argument("--steps-per-round", type=int, default=8)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--max-len", type=int, default=48)
    ap.add_argument("--page-size", type=int, default=8)
    ap.add_argument("--megastep", type=int, default=4,
                    help="tokens per dispatch (K of make_serve_megastep)")
    ap.add_argument("--policy", default="fcfs",
                    choices=["fcfs", "priority", "deadline"])
    ap.add_argument("--requests", type=int, default=0,
                    help="fixed synthetic workload size (0 = endless churn)")
    ap.add_argument("--prompt-len", type=_span, default=(2, 6),
                    metavar="LO,HI",
                    help="workload prompt lengths, inclusive range")
    ap.add_argument("--max-new", type=_span, default=(8, 24),
                    metavar="LO,HI",
                    help="workload new-token budgets, inclusive range")
    ap.add_argument("--arrival-every", type=int, default=0,
                    help="stagger arrivals by N steps (0 = storm)")
    ap.add_argument("--slo-fraction", type=float, default=0.5,
                    help="fraction of workload requests carrying an SLO")
    ap.add_argument("--overcommit", type=float, default=1.0,
                    help="pool size factor vs the worst-case plan (<1 "
                         "overcommits; the headroom controller compensates)")
    ap.add_argument("--no-proactive", action="store_true",
                    help="disable the forecaster/headroom controller "
                         "(reactive baseline: abort -> rebuild)")
    ap.add_argument("--fail-on-abort", action="store_true",
                    help="CI soak: exit non-zero if any allocator ABORT "
                         "surfaced")
    ap.add_argument("--verify-block-table", action="store_true",
                    help="CI/debug: check the incremental block-table "
                         "cache against the wait-free lookup every round")
    ap.add_argument("--probe-strategy", default="linear",
                    choices=["linear", "robinhood", "hopscotch"],
                    help="page-allocator probe strategy (cfg.probe_strategy;"
                         " hopscotch = tombstone-free deletes + scheduler "
                         "slack, see core/probe_strategies.py)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--telemetry", action="store_true",
                    help="enable the on-device counter plane "
                         "(cfg.telemetry; read out at the per-K sync)")
    ap.add_argument("--trace", default=None, metavar="PATH",
                    help="write a deterministic JSONL span trace "
                         "(obs/trace.py; render with tools/trace_report.py)")
    ap.add_argument("--metrics-out", default=None, metavar="PREFIX",
                    help="write PREFIX.prom (Prometheus text) and "
                         "PREFIX.json registry snapshots at exit")
    return ap.parse_args(argv)


def serve_rules_for(cfg, mesh):
    """The decode sharding rules ``cfg.tp_impl`` asks for on ``mesh``."""
    return (serve_manual_rules(mesh) if cfg.tp_impl == "manual"
            else serve_rules(mesh))


def peak_bytes_in_use() -> int | None:
    """Largest ``peak_bytes_in_use`` over the local devices (None where the
    backend does not report it)."""
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in jax.local_devices()]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


def run(cfg, params, args: argparse.Namespace, *, rules=None) -> dict:
    """Serve the traffic ``args`` describe with ``params`` and return the
    summary: the scheduler's roll-up plus ``requests`` (completed),
    ``tokens`` (sampled), ``prompt_tokens``, ``wall_s`` (serving loop,
    compiles included), the compile counts of
    ``compile_cache.count_compiles``, ``peak_bytes_in_use``, ``drained`` and
    ``rc`` (0, or 1 when a fixed workload did not drain or an ABORT
    surfaced under ``--fail-on-abort``)."""
    n_chips = 1 if rules is None else rules.mesh.size
    maxP, plan = EG.plan_pages(cfg, args.batch, args.max_len,
                               args.page_size, n_chips)
    n_pages = max(maxP, int(plan * args.overcommit))
    sched = Scheduler(slots=args.batch, page_size=args.page_size,
                      max_len=args.max_len, megastep_k=args.megastep,
                      policy=args.policy,
                      proactive=not args.no_proactive)
    fixed = args.requests > 0
    tracer = OBS.Tracer(args.trace) if args.trace else None
    srv = ContinuousBatcher(cfg, params, batch=args.batch,
                            max_len=args.max_len, page_size=args.page_size,
                            rules=rules, megastep_k=args.megastep,
                            verify_block_table=args.verify_block_table,
                            scheduler=sched, n_pages=n_pages,
                            auto_refill=not fixed, seed=args.seed,
                            tracer=tracer)
    print(f"[serve] fallback report: {EG.fallback_report(cfg, rules)}")
    if fixed:
        sched.submit_many(synthetic_workload(
            args.requests, vocab_size=cfg.vocab_size, max_len=args.max_len,
            seed=args.seed, prompt_len=args.prompt_len,
            max_new=args.max_new, slo_fraction=args.slo_fraction,
            arrival_every=args.arrival_every))

    t0 = time.perf_counter()
    with CC.count_compiles() as compiles:
        for r in range(args.rounds):
            srv.decode_round(args.steps_per_round)
            st = srv.table_stats()
            s = sched.stats
            occ = ("" if st is None else
                   f" live_pages={int(st.live_pages)} "
                   f"tombs={int(st.tombstones)} "
                   f"occupancy={float(st.occupancy):.3f}")
            print(f"[serve] round {r}: done={s.completed} "
                  f"preempted={s.preemptive_evictions} "
                  f"queue={len(sched.queue)} aborts={s.aborts} "
                  f"avoided={s.aborts_avoided} grows={s.pool_grows}{occ}")
            if fixed and sched.drained:
                break
        jax.block_until_ready(srv.state)
    wall = time.perf_counter() - t0

    summary = sched.summary()
    print(f"[serve] summary ({sched.policy.name}, "
          f"{'proactive' if sched.proactive else 'reactive'}): "
          + " ".join(f"{k}={v:.0f}" if isinstance(v, float) else f"{k}={v}"
                     for k, v in summary.items()))
    print(f"[serve] done — megastep K={srv.K}: host synced once per K "
          "tokens; page slots were reused in place (no compaction)")
    if srv.attn_path is not None:
        a = srv.attention_reads()
        print(f"[serve] attention: {a['path']} read "
              f"{a['pages_read_per_step']:.1f} KV pages per layer and token "
              f"step; the jnp gather reads {a['gather_pages_per_step']:.0f} "
              f"(live share {100 * a['live_share']:.1f}%)")
    if tracer is not None:
        srv.emit_summary()
        tracer.close()
        print(f"[serve] trace: {tracer.path} ({tracer.n_events} events)")
    if args.metrics_out:
        d = os.path.dirname(args.metrics_out)
        if d:
            os.makedirs(d, exist_ok=True)
        with open(args.metrics_out + ".prom", "w") as f:
            f.write(srv.metrics_text())
        with open(args.metrics_out + ".json", "w") as f:
            f.write(srv.metrics_json())
        print(f"[serve] metrics: {args.metrics_out}.prom / .json")
    summary.update(
        requests=len(sched.finished),
        tokens=sum(len(r.sampled) for r in sched.finished),
        prompt_tokens=sum(int(r.prompt.size) for r in sched.finished),
        wall_s=wall, peak_bytes_in_use=peak_bytes_in_use(),
        drained=sched.drained, rc=0, **compiles)
    if fixed and not sched.drained:
        print("[serve] FAIL: workload not drained")
        summary["rc"] = 1
    elif args.fail_on_abort and sched.stats.aborts:
        print(f"[serve] FAIL: {sched.stats.aborts} allocator ABORT(s) "
              "surfaced (--fail-on-abort)")
        summary["rc"] = 1
    return summary


def main(argv=None) -> int:
    args = parse_args(argv)
    CC.enable_compile_cache()
    cfg = get_smoke_config(args.arch) if args.smoke else get_config(args.arch)
    if args.probe_strategy != cfg.probe_strategy:
        cfg = dataclasses.replace(cfg, probe_strategy=args.probe_strategy)
    if args.telemetry:
        cfg = dataclasses.replace(cfg, telemetry=True)
    rules = serve_rules_for(cfg, make_serve_mesh())
    params, _ = init_params(cfg, jax.random.PRNGKey(args.seed), rules)
    return run(cfg, params, args, rules=rules)["rc"]


if __name__ == "__main__":
    raise SystemExit(main())
