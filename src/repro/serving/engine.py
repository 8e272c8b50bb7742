"""Decode engines: one ``serve_step`` per architecture family, plus the
K-token ``make_serve_megastep`` (one dispatch, K greedy tokens).

The hash-table page table (serving/page_table) is consulted ONCE per step
(alloc + block-table read); page locality is compacted ONCE per chip
(serving/paged.compact_local); every attention layer then reuses the same
compacted page list.  The block-table read is served from the persistent
``state["block_table"]`` cache, scatter-updated at page-boundary crossings
by ``PageTable.alloc_step_incremental`` — O(crossings) probed keys per token
instead of the old O(B·max_pages) full re-probe — while the paper's
wait-free ``lookup_pages`` remains the authoritative read for admission,
Section 4.3 rebuilds, and the CI verification mode
(``PageTable.verify_block_table``).

The megastep fuses K decode tokens into one ``jax.lax.scan``: greedy
sampling runs in-graph (token t+1 = argmax of token t's logits), page
allocation runs inside the scan, and done/abort conditions latch into
on-device flags, so the host syncs once per K tokens.  A lane that ABORTs
mid-megastep freezes (pos, pending token, recurrent state) and the batcher
re-issues the refused suffix after ``rebuild_page_table``.  The
``forced``/``forced_mask`` inputs teacher-force fed tokens (CHUNKED
PREFILL under the same dispatch budget — see ``_mega_scan`` and
``repro.serving.sched``); ``make_decode_state(n_pages=...)`` overcommits
the pool and ``decode_headroom`` exposes the occupancy the scheduler's
forecaster consumes.

Sharding, gspmd baseline (``serve_rules``): activations replicated (decode
activations are KB-scale), weights TP-sharded over ``model``, page pools
sharded over every mesh axis, SSM/ring state sharded over batch.  The paged
attention op is a fully-manual shard_map; everything else is GSPMD.

``tp_impl="manual"`` (``serve_manual_rules``): ONE fully-manual shard_map
over every mesh axis covers the whole step — embed, the once-per-step
page-table alloc + wait-free lookup + per-chip compaction, every layer's
attention/MLP/MoE, and the read-out.  Layout: KV pools page-sharded over
(pod, data) and *head*-sharded over ``model`` (each chip attends its own
heads end-to-end — no cross-model K/V gather), page-table metadata
replicated (every chip runs the identical lookup), weights Megatron
column/row-parallel with one psum after attention and one after the
MLP/MoE.  When the model axis is WIDER than ``n_kv`` (e.g. kv=8 on the
16-wide production mesh), KV heads are REPLICATED across the surplus width
(``dist/tp.decode_kv_rep``): pools/ring state carry ``n_kv·rep`` tiled
heads so each chip still keeps exactly one resident head.  Local-window
(gemma3) ring layers and the hybrid family's Mamba backbone + shared
attention block run INSIDE the same region (ring/ssm state per-lane; the
mamba math shards its per-head inner dims over ``model`` when
``dist/tp.decode_ssm_tp`` passes — replicated redundant compute
otherwise).  Only ssm
(attention-free) and encdec remain on the gspmd step — every fallback is
logged, never silent (``_manual_decode_reason``).

Liveness (all paths): ``state["active"]`` masks finished/padding lanes out
of page allocation and freezes their ``pos`` (otherwise each dead lane
leaks a phantom page every ``page_size`` steps); ``state["aborted"]``
latches lanes whose allocation ABORTed (pool exhausted) — their token is
refused (no KV write, pos frozen) until the caller evicts or runs the
Section 4.3 ``rebuild_page_table``.
"""
from __future__ import annotations

import functools
import logging
import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro import kernels as KN
from repro.dist import ctx
from repro.dist import tp as TP
from repro.dist.compat import shard_map
from repro.models import hybrid as HY
from repro.models import layers as L
from repro.models import moe as MOE
from repro.models import nn
from repro.models import ssm
from repro.obs import counters as OC
from repro.serving import page_table as PT
from repro.serving import paged
from repro.core import batched as BT
from repro.kernels.fused_decode.fused import fused_decode_kernel

DEFAULT_PAGE_SIZE = 256

logger = logging.getLogger(__name__)


# ---------------------------------------------------------------------------
# Mesh helpers.

def _mesh_axes(rules):
    if rules is None:
        return ()
    return tuple(a for a in ("pod", "data", "model") if a in rules.mesh.shape)


def _n_chips(rules) -> int:
    if rules is None:
        return 1
    n = 1
    for a in _mesh_axes(rules):
        n *= rules.mesh.shape[a]
    return n


def _chip_idx(axes, mesh):
    idx = jnp.int32(0)
    for a in axes:
        idx = idx * mesh.shape[a] + jax.lax.axis_index(a)
    return idx


def _pd_axes(rules):
    """Mesh axes the page dim shards over in the fused manual decode layout
    (everything but ``model``, which shards KV heads instead)."""
    return tuple(a for a in ("pod", "data") if a in rules.mesh.shape)


def _n_pd(rules) -> int:
    """Chips along the page axes of the fused manual decode layout."""
    n = 1
    for a in _pd_axes(rules):
        n *= rules.mesh.shape[a]
    return n


# The two genuinely unsupported families — everything else (dense incl.
# the gemma3 local-window pattern, moe, vlm, hybrid) takes the fused path.
_MANUAL_UNSUPPORTED_FAMILY = {
    "ssm": "attention-free SSM stack: no model-axis work in the region",
    "encdec": "cross-attention decode state not yet inside the fused region",
}


def _manual_decode_reason(cfg, rules) -> Optional[str]:
    """Why ``tp_impl="manual"`` decode falls back to gspmd — None when the
    fused region applies."""
    fam = _MANUAL_UNSUPPORTED_FAMILY.get(cfg.family)
    if fam is not None:
        return fam
    return TP.decode_manual_unsupported(cfg, rules)


def _manual_decode_ok(cfg, rules) -> bool:
    """The fused manual-TP decode region applies (family supported AND the
    shape gate dist/tp.decode_manual_tp passes)."""
    return _manual_decode_reason(cfg, rules) is None


def _fused_kernel_wanted(cfg) -> bool:
    """``cfg.fused_kernel`` where set; unset, the platform decides: the
    kernel on TPU, the jnp gather everywhere else (interpreted Pallas in
    every CPU serving step would cost more than it tests)."""
    if cfg.fused_kernel is None:
        return KN.on_tpu()
    return bool(cfg.fused_kernel)


def _fused_kernel_reason(cfg, rules) -> Optional[str]:
    """Why decode attention does NOT run as the one-dispatch fused
    probe+paged-attention Pallas kernel (kernels/fused_decode) — None when
    it does.  Evaluated for whichever serve path (manual region or gspmd)
    the step factory actually picks; a non-None reason where the kernel was
    wanted (``_fused_kernel_wanted``) is logged by the factories and
    recorded in dry-run artifacts (``fused_kernel`` field), never
    silent."""
    if cfg.fused_kernel is not None and not cfg.fused_kernel:
        return "off (cfg.fused_kernel=False)"
    if cfg.family == "ssm":
        return "attention-free SSM stack: no paged decode attention"
    if cfg.family == "encdec":
        return "cross-attention decode state not wired to the fused kernel"
    if rules is not None and _manual_decode_ok(cfg, rules):
        if TP.decode_kv_rep(cfg, rules.mesh.shape["model"]) != 1:
            return ("kv_rep>1: replicated-KV manual layout keeps the "
                    "two-dispatch per-chip attend path")
    if not _fused_kernel_wanted(cfg):
        return (f"off the TPU ({jax.default_backend()} backend): the jnp "
                "gather attends")
    return None


def _fused_kernel_ok(cfg, rules) -> bool:
    return _fused_kernel_reason(cfg, rules) is None


def attention_path(cfg, rules=None) -> Optional[str]:
    """The decode-attention path the serve factories build for ``cfg``:
    ``"fused_decode_kernel"`` (live pages only, walked in-kernel),
    ``"jnp_gather"`` (every capacity page, ``paged.attend_local``), or None
    for a family with no paged attention."""
    if cfg.family == "ssm":
        return None
    return "fused_decode_kernel" if _fused_kernel_ok(cfg, rules) \
        else "jnp_gather"


def gather_pages(cfg, rules, B: int, S_max: int, page_size: int) -> int:
    """Pages the jnp gather reads per attention layer and token step, over
    every chip: ``paged.capacity`` on each chip that holds pages (the page
    axes of the manual region, every chip of the gspmd step), whatever
    the lanes hold."""
    if rules is not None and _manual_decode_ok(cfg, rules):
        n = _n_pd(rules)
    else:
        n = _n_chips(rules)
    maxP = -(-S_max // page_size)
    return n * paged.capacity(B, maxP, n, factor=cfg.page_capacity_factor)


def live_pages_read(p0, p1, seated, K: int, page_size: int) -> int:
    """KV pages the fused kernel reads in one megastep, per attention layer,
    summed over its K token steps, from the positions at its start ``p0``
    and end ``p1`` (int[B]) and the lanes that hold block-table rows
    (``seated`` bool[B]).  A lane advances one position a step until it
    stops, so at step k it is at ``p0 + k`` and reads pages ``0..pos //
    page_size`` while that is below ``p1``; after (a stop or a refused
    allocation) it reads the ``ceil(p1 / page_size)`` pages it holds.  The
    same walk as the kernel's ``need(p)``: ``p·PS <= pos`` and a present
    table entry."""
    pos = np.asarray(p0)[None, :] + np.arange(K)[:, None]
    p1 = np.asarray(p1)[None, :]
    pages = np.where(pos < p1, pos // page_size + 1, -(-p1 // page_size))
    return int((pages * np.asarray(seated, bool)[None, :]).sum())


def _probe_strategy_reason(cfg, rules=None) -> Optional[str]:
    """Why ``cfg.probe_strategy`` runs without full fast-path acceleration —
    None when fully served.  The strategy SEMANTICS (probe order, claim
    arbitration, deletion mode, metadata) are ALWAYS honoured by the jnp
    allocator — the scheduler's accounting depends on them — so unlike
    ``tp_impl``/``fused_kernel`` this gate never swaps the strategy out; it
    reports which accelerated path degrades to the oracle (logged by the
    step factories, recorded per-cell by dryrun via ``fallback_report``)."""
    from repro.core.probe_strategies import get_strategy
    impl = get_strategy(cfg.probe_strategy)  # raises on unknown names
    if not impl.kernel_supported:
        return ("Pallas probe kernel assumes the linear probe order: bulk "
                "block-table rebuilds serve from the jnp oracle")
    return None


def _pt(cfg) -> PT.PageTable:
    """The strategy-bound page-table facade for this config."""
    return PT.for_strategy(cfg.probe_strategy)


def fallback_report(cfg, rules=None) -> Dict[str, str]:
    """Every gated fast-path fallback in ONE structure: the single source
    consumed by dry-run cell meta and the ``--expect-*`` CI gates (the step
    factories log from the same reason functions, so a logged fallback can
    never diverge from the recorded one).  Values are ``"ok"`` or the
    fallback reason; ``probe_strategy`` is prefixed with the requested
    strategy name so artifacts show WHAT ran, not just whether it
    degraded."""
    manual = _manual_decode_reason(cfg, rules) if rules is not None else None
    strat_reason = _probe_strategy_reason(cfg, rules)
    return {
        "decode_tp": "ok" if manual is None else manual,
        "fused_kernel": ("ok" if _fused_kernel_ok(cfg, rules)
                         else _fused_kernel_reason(cfg, rules)),
        "probe_strategy": (f"{cfg.probe_strategy}: ok"
                           if strat_reason is None
                           else f"{cfg.probe_strategy}: {strat_reason}"),
    }


def _kernel_interpret() -> bool:
    """Pallas kernels run compiled on TPU, interpreted elsewhere (CI's fake
    CPU devices) — resolved at trace time from the same predicate as the
    attention path, never a silent wrong-backend."""
    return not KN.on_tpu()


def _local_block_table(bt, chip_idx, npr: int):
    """Chip-local view of the RAW incremental block table for the fused
    kernel: entries this chip owns (block distribution ``slot // npr ==
    chip``, identical to ``paged.compact_local``/``write_token_kv``) become
    local pool rows, everything else -1.  Liveness (``p·PS <= pos``) is
    enforced in-kernel from ``positions`` — no materialized slots view, no
    per-chip compaction pass."""
    mine = (bt >= 0) & (bt // npr == chip_idx)
    return jnp.where(mine, bt % npr, -1).astype(jnp.int32)


# ---------------------------------------------------------------------------
# State construction.

def plan_pages(cfg, B: int, S_max: int, page_size: int, n_chips: int):
    max_pages = -(-S_max // page_size)
    n_pages = paged.round_pages(int(B * max_pages * 1.25) + n_chips,
                                n_chips)
    return max_pages, n_pages


def _n_attn_layers(cfg) -> Tuple[int, int]:
    """(paged/global attention layers, ring/local attention layers)."""
    if cfg.family == "ssm":
        return 0, 0
    if cfg.family == "hybrid":
        return cfg.num_layers // cfg.shared_attn_every, 0
    if cfg.pattern_local:
        g = cfg.pattern_local + 1
        return cfg.num_layers // g, cfg.num_layers - cfg.num_layers // g
    return cfg.num_layers, 0


def make_decode_state(cfg, B: int, S_max: int, *, rules=None,
                      page_size: int = DEFAULT_PAGE_SIZE,
                      n_pages: Optional[int] = None,
                      abstract: bool = False) -> Tuple[Dict[str, Any], Dict[str, Any]]:
    """Decode-state pytree (+ logical axes).  ``abstract=True`` builds the
    pytree under eval_shape — nothing is allocated (dry-run states can be
    hundreds of GB).

    ``n_pages`` overrides the default worst-case pool plan (``plan_pages``:
    1.25x of B·max_pages): a serving deployment deliberately OVERCOMMITS
    the pool (most sequences finish early), betting on the scheduler's
    admission control / proactive headroom to keep the live set bounded —
    the pool can always be grown later via ``rebuild_page_table``.  The
    value is rounded up to the mesh's chip count (page-shard
    divisibility)."""
    n_chips = _n_chips(rules)
    dtype = cfg.activation_dtype()
    if n_pages is None:
        maxP, n_pages = plan_pages(cfg, B, S_max, page_size, n_chips)
    else:
        maxP = -(-S_max // page_size)
        n_pages = paged.round_pages(int(n_pages), n_chips)
    n_paged, n_ring = _n_attn_layers(cfg)
    manual_tp = rules is not None and _manual_decode_ok(cfg, rules)
    # fused-manual layout with a model axis wider than n_kv: the pool/ring
    # head dim is physically tiled to n_kv·rep so the "kv" logical axis
    # divides the mesh and every chip keeps exactly one resident head copy
    kv_rep = (TP.decode_kv_rep(cfg, rules.mesh.shape["model"])
              if manual_tp else 1)
    n_kv_st = cfg.n_kv * kv_rep

    def build() -> Dict[str, Any]:
        state: Dict[str, Any] = {
            "pos": jnp.zeros((B,), jnp.int32),
            "seq_ids": jnp.arange(B, dtype=jnp.int32),
            "active": jnp.ones((B,), bool),
            "aborted": jnp.zeros((B,), bool),
        }
        if n_paged:
            state["table"] = _pt(cfg).create_table(n_pages)
            # incremental block-table cache: scatter-updated at page-boundary
            # crossings, (re)built from the wait-free lookup on admission /
            # rebuild only (see page_table.alloc_step_incremental)
            state["block_table"] = jnp.full((B, maxP), -1, jnp.int32)
            kv_dtype = (jnp.int8 if cfg.kv_cache_dtype == "int8"
                        else dtype)
            state["pools"] = paged.make_pools(n_paged, n_pages, page_size,
                                              n_kv_st, cfg.hd, kv_dtype)
            if cfg.kv_cache_dtype == "int8":
                state["pool_scales"] = paged.make_pool_scales(
                    n_paged, n_pages, page_size, n_kv_st)
        if n_ring:
            w = cfg.local_window
            state["ring_k"] = jnp.zeros((n_ring, B, w, n_kv_st, cfg.hd),
                                        dtype)
            state["ring_v"] = jnp.zeros((n_ring, B, w, n_kv_st, cfg.hd),
                                        dtype)
            state["ring_pos"] = jnp.full((B, w), -1, jnp.int32)
        if cfg.family in ("ssm", "hybrid"):
            one = ssm.init_mamba_state(cfg, B, dtype)
            state["ssm"] = jax.tree.map(
                lambda x: jnp.broadcast_to(
                    x[None], (cfg.num_layers,) + x.shape) + 0, one)
        if cfg.family == "encdec":
            S_src = max(S_max // 8, 1)
            state["cross_k"] = jnp.zeros(
                (cfg.num_layers, B, S_src, cfg.n_kv, cfg.hd), dtype)
            state["cross_v"] = jnp.zeros(
                (cfg.num_layers, B, S_src, cfg.n_kv, cfg.hd), dtype)
        if getattr(cfg, "telemetry", False):
            # on-device counter plane (obs/counters.py): rides the megastep
            # scan, read out at the existing per-K host sync.  When the knob
            # is off the leaf does not exist and every update site below is
            # skipped — identity fast path, bitwise parity with
            # pre-telemetry programs (tests/test_obs.py).
            state["counters"] = OC.Counters.zeros()
        return state

    axes: Dict[str, Any] = {"pos": (None,), "seq_ids": (None,),
                            "active": (None,), "aborted": (None,)}
    if n_paged:
        axes["table"] = BT.HashTable(table=(None,), num_keys=(),
                                     num_tombs=(), seed=(), meta=(None,))
        axes["block_table"] = (None, None)
        pool_ax = paged.POOL_AXES_TP if manual_tp else paged.POOL_AXES
        axes["pools"] = paged.PagedPools(k=pool_ax, v=pool_ax)
        if cfg.kv_cache_dtype == "int8":
            sc_ax = (paged.POOL_SCALE_AXES_TP if manual_tp
                     else paged.POOL_SCALE_AXES)
            axes["pool_scales"] = paged.PoolScales(k=sc_ax, v=sc_ax)
    if n_ring:
        # fused manual region: ring heads over model (batch replicated —
        # activations in the region are); gspmd: per-sequence over data
        ring_ax = (("layer", None, None, "kv", None) if manual_tp
                   else ("layer", "batch", None, "kv", None))
        axes["ring_k"] = ring_ax
        axes["ring_v"] = ring_ax
        axes["ring_pos"] = (None, None) if manual_tp else ("batch", None)
    if cfg.family in ("ssm", "hybrid"):
        is_ax = lambda x: (isinstance(x, tuple)
                           and not isinstance(x, ssm.MambaState)
                           and all(e is None or isinstance(e, str)
                                   for e in x))
        # fused manual region: ssm state head-sharded over model when the
        # decode_ssm_tp gate passes (batch replicated — activations in the
        # region are), replicated redundant compute otherwise
        ssm_tp = (manual_tp and cfg.family == "hybrid"
                  and TP.decode_ssm_tp(cfg, rules.mesh.shape["model"]))
        if manual_tp:
            axes["ssm"] = jax.tree.map(
                lambda ax: ("layer",) + tuple(
                    (a if (ssm_tp and a != "batch") else None) for a in ax),
                ssm.MAMBA_STATE_AXES, is_leaf=is_ax)
        else:
            axes["ssm"] = jax.tree.map(
                lambda ax: ("layer",) + tuple(ax),
                ssm.MAMBA_STATE_AXES, is_leaf=is_ax)
    if cfg.family == "encdec":
        axes["cross_k"] = ("layer", "batch", None, "kv", None)
        axes["cross_v"] = ("layer", "batch", None, "kv", None)
    if getattr(cfg, "telemetry", False):
        axes["counters"] = OC.Counters.axes()

    state = jax.eval_shape(build) if abstract else build()
    return state, axes


def rebuild_page_table(state: Dict[str, Any], *, n_pages: Optional[int] = None,
                       seed: Optional[int] = None,
                       use_kernel: bool = False,
                       strategy: str = "linear") -> Dict[str, Any]:
    """Section 4.3 ABORT recovery, live in serving: re-hash the page table
    (into ``n_pages`` cells — pass a larger pool to actually gain capacity;
    with tombstone reuse a same-size rebuild only changes the seed, since
    the reuse table aborts only when every cell holds a live key) and MOVE
    the physical KV pages to their keys' new slots — the cell index IS the
    page, so the pages must follow the re-hash.  Clears ``aborted``.

    Host-side, outside jit: aborts are rare (true pool exhaustion), the
    rebuild cost is amortized exactly as in the paper.  ``n_pages`` must
    keep the pool divisible by the mesh's chip/page-shard count."""
    table = state["table"]
    pt = PT.for_strategy(strategy)
    # metadata-carrying strategies (hopscotch) and metadata-free ones build
    # different meta leaves: rebuilding with the wrong strategy would
    # silently corrupt the table
    if (table.meta.size > 0) != (pt.create_table(1).meta.size > 0):
        raise ValueError(
            f"rebuild_page_table: state's table metadata does not match "
            f"strategy {strategy!r} — pass the strategy the state was "
            f"built with (cfg.probe_strategy)")
    m = BT.size(table)
    new_m = m if n_pages is None else n_pages
    fresh, old_slots, new_slots, live = pt.rehash(table, new_m, seed)
    if bool(jnp.any(live & (new_slots < 0))):
        # a live key failed to land (n_pages smaller than the live set):
        # proceeding would orphan pages and wrap dst=-1 into the last row
        raise ValueError(
            f"rebuild_page_table: {int(jnp.sum(live & (new_slots < 0)))} "
            f"live pages do not fit in n_pages={new_m}")

    def move(pool, fill):
        shp = pool.shape[:1] + (new_m,) + pool.shape[2:]
        src = jnp.where(live, old_slots, 0)
        dst = jnp.where(live, new_slots, new_m)      # OOB -> dropped
        return jnp.full(shp, fill, pool.dtype).at[:, dst].set(
            pool[:, src], mode="drop")

    state = dict(state)
    state["table"] = fresh
    state["pools"] = paged.PagedPools(k=move(state["pools"].k, 0),
                                      v=move(state["pools"].v, 0))
    if "pool_scales" in state:
        state["pool_scales"] = paged.PoolScales(
            k=move(state["pool_scales"].k, 1),
            v=move(state["pool_scales"].v, 1))
    if "block_table" in state:
        # every slot moved: rebuild the incremental cache from the fresh
        # table via the authoritative wait-free lookup
        state["block_table"] = pt.rebuild_block_table(
            fresh, state["seq_ids"], state["block_table"].shape[1],
            use_kernel=use_kernel)
    state["aborted"] = jnp.zeros_like(state["aborted"])
    return state


def decode_headroom(state: Dict[str, Any],
                    strategy: str = "linear") -> Optional[PT.Headroom]:
    """First-class occupancy/headroom read of a decode state's page pool
    (None for attention-free families) — the proactive scheduler's
    observation input.  ``strategy`` fills the per-strategy ``slack`` field
    the forecaster adds to its no-ABORT gate.  See
    ``page_table.headroom``."""
    if "table" not in state:
        return None
    return PT.for_strategy(strategy).headroom(state["table"])


# ---------------------------------------------------------------------------
# The paged attention op (shard_map wrapper around serving/paged).

def _rope_single(cfg, x, positions, mrope=None):
    """x [B,H,hd] one token per seq at ``positions`` [B]."""
    x4 = x[:, None]                                  # [B,1,H,hd]
    if mrope is not None and cfg.mrope_sections:
        out = L.apply_mrope(x4, mrope, cfg.mrope_sections, cfg.rope_theta)
    else:
        out = L.apply_rope(x4, positions[:, None], cfg.rope_theta)
    return out[:, 0]


def _paged_attn_chip(cfg, x, ap, pools, scales, layer, lp_tree,
                     write_slot, positions, mrope, bt, *, axes_names, mesh,
                     page_size, kv_sharded, q_sharded, fused=False,
                     interpret=False):
    """Runs per chip (inside shard_map or standalone).  ``pools`` are the
    chip's pages of every attention layer; ``layer`` picks this one."""
    B = x.shape[0]
    npr = pools.k.shape[1]
    chip = _chip_idx(axes_names, mesh) if axes_names else jnp.int32(0)

    with jax.named_scope("attn_proj"):
        q, k, v = L.attn_qkv_decode(ap, x[:, 0])
        if axes_names and q_sharded:
            q = jax.lax.all_gather(q, "model", axis=1, tiled=True)
        if axes_names and kv_sharded:
            k = jax.lax.all_gather(k, "model", axis=1, tiled=True)
            v = jax.lax.all_gather(v, "model", axis=1, tiled=True)
        q = _rope_single(cfg, q, positions, mrope)
        k = _rope_single(cfg, k, positions, mrope)

    with jax.named_scope("kv_write"):
        pools, scales = paged.write_token_kv(
            pools, scales, k, v, write_slot, positions, chip, npr,
            page_size, layer)

    n_kv, G = cfg.n_kv, cfg.n_q // cfg.n_kv
    with jax.named_scope("attend"):
        if fused:
            # one Pallas dispatch: in-kernel block-table walk +
            # double-buffered page DMA + attention partials
            # (kernels/fused_decode)
            local_bt = _local_block_table(bt, chip, npr)
            o, m, l = fused_decode_kernel(q, pools.k, pools.v, local_bt,
                                          positions, layer=layer,
                                          scales=scales, partials=True,
                                          interpret=interpret)
        else:
            lp = paged.LocalPages(*(t[0] for t in lp_tree))
            qg = q.reshape(B, n_kv, G, cfg.hd)
            o, m, l = paged.attend_local(qg, pools, scales, layer, lp,
                                         positions, page_size)
        out = paged.merge_global(o, m, l, axes_names)  # [B,kv,G,hd] f32
        out = out.reshape(B, cfg.n_q, cfg.hd).astype(x.dtype)

    with jax.named_scope("attn_proj"):
        if axes_names and q_sharded:
            hl = cfg.n_q // mesh.shape["model"]
            my = jax.lax.dynamic_slice_in_dim(
                out, jax.lax.axis_index("model") * hl, hl, axis=1)
            y = jax.lax.psum(L.attn_out_decode(ap, my), "model")
        else:
            y = L.attn_out_decode(ap, out)
    return y.astype(x.dtype)[:, None], pools, scales


def paged_attn_op(cfg, rules, x, ap, pools, scales, layer, lp_arrays,
                  write_slot, positions, mrope=None,
                  page_size: int = DEFAULT_PAGE_SIZE, bt=None,
                  fused: bool = False, interpret: bool = False):
    """x [B,1,d]; pools PagedPools [L, n_pages, ...] of every attention
    layer, ``layer`` the one this call attends (and writes); ``scales``
    PoolScales for int8 pools, else None; lp_arrays: LocalPages as
    [n_chips,CAP] arrays (None when ``fused`` — the kernel walks the raw
    block table ``bt`` int32[B, maxP] instead).  Returns (attn_out [B,1,d],
    pools', scales')."""
    if rules is None:
        lp_tree = (None if lp_arrays is None
                   else tuple(t[:1] for t in lp_arrays))
        return _paged_attn_chip(
            cfg, x, ap, pools, scales, layer, lp_tree, write_slot,
            positions, mrope, bt, axes_names=(), mesh=None,
            page_size=page_size, kv_sharded=False, q_sharded=False,
            fused=fused, interpret=interpret)

    mesh = rules.mesh
    axes_names = _mesh_axes(rules)
    tp = mesh.shape.get("model", 1)
    kv_sharded = cfg.n_kv % tp == 0 and tp > 1
    q_sharded = cfg.n_q % tp == 0 and tp > 1
    chips = P(axes_names)
    h_spec = P(None, "model", None) if q_sharded else P()
    kvw_spec = P(None, "model", None) if kv_sharded else P()
    ap_specs = {"wq": h_spec, "wk": kvw_spec, "wv": kvw_spec,
                "wo": P("model", None, None) if q_sharded else P()}
    if "bq" in ap:
        ap_specs.update({
            "bq": P("model", None) if q_sharded else P(),
            "bk": P("model", None) if kv_sharded else P(),
            "bv": P("model", None) if kv_sharded else P()})
    pool_spec = P(None, axes_names, None, None, None)
    pools_spec = paged.PagedPools(k=pool_spec, v=pool_spec)
    scale_spec = P(None, axes_names, None, None)
    scales_spec = (None if scales is None
                   else paged.PoolScales(k=scale_spec, v=scale_spec))
    lp_specs = (None if lp_arrays is None
                else tuple(P(axes_names, None) for _ in lp_arrays))

    fn = functools.partial(
        _paged_attn_chip, cfg, axes_names=axes_names, mesh=mesh,
        page_size=page_size, kv_sharded=kv_sharded, q_sharded=q_sharded,
        fused=fused, interpret=interpret)
    mapped = shard_map(
        fn, mesh=mesh,
        in_specs=(P(), ap_specs, pools_spec, scales_spec, P(), lp_specs,
                  P(), P(), P() if mrope is not None else None,
                  P() if bt is not None else None),
        out_specs=(P(), pools_spec, scales_spec),
        check_vma=False)
    return mapped(x, ap, pools, scales, layer, lp_arrays, write_slot,
                  positions, mrope, bt)


def compact_op(rules, slots, n_pages: int, cap: int):
    """Per-chip page compaction, once per serve step.  Returns LocalPages as
    [n_chips, CAP] arrays (chip-sharded when a mesh is active)."""
    if rules is None:
        lp = paged.compact_local(slots, 0, n_pages, cap)
        return tuple(t[None] for t in lp)
    mesh = rules.mesh
    axes_names = _mesh_axes(rules)
    n_chips = _n_chips(rules)
    npr = n_pages // n_chips

    def fn(slots):
        chip = _chip_idx(axes_names, mesh)
        lp = paged.compact_local(slots, chip, npr, cap)
        return tuple(t[None] for t in lp)

    mapped = shard_map(
        fn, mesh=mesh, in_specs=(P(),),
        out_specs=tuple(P(axes_names, None) for _ in range(4)),
        check_vma=False)
    return mapped(slots)


# ---------------------------------------------------------------------------
# Ring-buffer (sliding window) attention for gemma3 local layers.

def _ring_attn(cfg, x, ap, ring_k_l, ring_v_l, ring_pos, positions):
    """x [B,1,d]; ring [B,W,kv,hd]; ring_pos [B,W] absolute positions."""
    B = x.shape[0]
    W = ring_k_l.shape[1]
    with jax.named_scope("attn_proj"):
        q, k, v = L.attn_qkv_decode(ap, x[:, 0])
        q = _rope_single(cfg, q, positions)
        k = _rope_single(cfg, k, positions)
    slot = positions % W
    with jax.named_scope("kv_write"):
        ring_k_l = ring_k_l.at[jnp.arange(B), slot].set(
            k.astype(ring_k_l.dtype))
        ring_v_l = ring_v_l.at[jnp.arange(B), slot].set(
            v.astype(ring_v_l.dtype))

    n_kv, G = cfg.n_kv, cfg.n_q // cfg.n_kv
    with jax.named_scope("attend"):
        qg = q.reshape(B, n_kv, G, cfg.hd)
        s = jnp.einsum("bkgd,bwkd->bkgw", qg.astype(jnp.float32),
                       ring_k_l.astype(jnp.float32)) / math.sqrt(cfg.hd)
        ok = (ring_pos >= 0) & (ring_pos <= positions[:, None]) & \
            (ring_pos > positions[:, None] - W)
        ok = ok.at[jnp.arange(B), slot].set(True)
        s = jnp.where(ok[:, None, None, :], s, paged.NEG_INF)
        p = jax.nn.softmax(s, axis=-1)
        o = jnp.einsum("bkgw,bwkd->bkgd", p, ring_v_l.astype(jnp.float32))
        o = o.reshape(B, cfg.n_q, cfg.hd).astype(x.dtype)
    with jax.named_scope("attn_proj"):
        y = L.attn_out_decode(ap, o).astype(x.dtype)[:, None]
    return y, ring_k_l, ring_v_l


# ---------------------------------------------------------------------------
# Cross attention at decode (encdec): dense precomputed memory K/V.

@jax.named_scope("attend")
def _cross_attn_decode(cfg, x, cp, ck, cv):
    """x [B,1,d]; ck/cv [B,S_src,kv,hd]."""
    B = x.shape[0]
    q = jnp.einsum("bd,dhk->bhk", x[:, 0], cp["wq"])
    if "bq" in cp:
        q = q + cp["bq"]
    n_kv, G = cfg.n_kv, cfg.n_q // cfg.n_kv
    qg = q.reshape(B, n_kv, G, cfg.hd)
    s = jnp.einsum("bkgd,bskd->bkgs", qg.astype(jnp.float32),
                   ck.astype(jnp.float32)) / math.sqrt(cfg.hd)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bkgs,bskd->bkgd", p, cv.astype(jnp.float32))
    o = o.reshape(B, cfg.n_q, cfg.hd).astype(x.dtype)
    return L.attn_out_decode(cp, o).astype(x.dtype)[:, None]


# ---------------------------------------------------------------------------
# serve_step factories.

def _warn_kernel_fallbacks(cfg, rules):
    """Never a silent fallback: a wanted fused kernel that the gate refuses,
    and a probe strategy whose kernel surface degrades (the strategy itself
    still runs on the jnp allocator), are logged and mirrored in
    ``fallback_report``."""
    if _fused_kernel_wanted(cfg) and not _fused_kernel_ok(cfg, rules):
        logger.warning(
            "fused decode kernel unavailable for %s — %s; "
            "the jnp gather attends",
            cfg.name, _fused_kernel_reason(cfg, rules))
    if _probe_strategy_reason(cfg, rules) is not None:
        logger.warning(
            "probe strategy %s partially degraded for %s — %s",
            cfg.probe_strategy, cfg.name, _probe_strategy_reason(cfg, rules))


def make_serve_step(cfg, *, S_max: int, rules=None,
                    page_size: int = DEFAULT_PAGE_SIZE):
    """Returns serve_step(params, state, tokens [B,1], positions [B],
    [mrope_positions]) -> (logits [B,V], state')."""
    _warn_kernel_fallbacks(cfg, rules)
    if rules is not None and _manual_decode_ok(cfg, rules):
        return _make_manual_serve_step(cfg, S_max=S_max, rules=rules,
                                       page_size=page_size)
    if rules is not None and cfg.tp_impl == "manual":
        # never a silent fallback: the caller asked for the fused region
        logger.warning(
            "fused manual-TP decode unavailable for %s — %s; "
            "falling back to the gspmd serve step",
            cfg.name, _manual_decode_reason(cfg, rules))
    n_chips = _n_chips(rules)
    family = cfg.family

    def serve_step(params, state, tokens, positions, mrope_positions=None):
        with ctx.use_rules(rules):
            return _serve_step_impl(cfg, params, state, tokens, positions,
                                    mrope_positions, rules=rules,
                                    S_max=S_max, page_size=page_size,
                                    n_chips=n_chips)

    return serve_step


def make_serve_megastep(cfg, *, S_max: int, K: int, rules=None,
                        page_size: int = DEFAULT_PAGE_SIZE):
    """The decode megastep: K tokens per dispatch via one ``jax.lax.scan``
    over the per-token serve body — in-graph greedy sampling feeds token
    t+1 from token t's logits, page allocation runs inside the scan, and
    done/abort conditions latch into on-device flags, so the host syncs
    once per K tokens instead of once per token.

    Returns ``megastep(params, state, tokens [B,1], stop_len=None,
    forced=None, forced_mask=None) -> (tokens int32[B, K], state')``.
    ``forced``/``forced_mask`` [B, K] teacher-force the fed tokens where the
    mask is set (chunked prefill: a lane consumes up to K prompt tokens per
    dispatch and flips to greedy decode mid-megastep — see ``_mega_scan``),
    so prefill and decode share one dispatch budget.  Positions come from
    ``state["pos"]``
    (the engine is the source of truth); for the vlm family the M-RoPE
    positions are derived in-graph from the same counter.  ``tokens[:, -1]``
    is always the correct next feed: the last greedy sample for healthy
    lanes, the frozen refused token for lanes that ABORTed mid-megastep
    (their ``pos`` did not advance — after ``rebuild_page_table`` the next
    megastep re-issues the refused suffix automatically).  ``stop_len``
    int32[B] latches ``active=False`` in-graph when a lane's position
    reaches its stop, so finished lanes stop allocating pages without a
    host round-trip.  K=1 degenerates to the single step + in-graph argmax.

    With ``tp_impl="manual"`` the whole scan lives inside the single
    fully-manual shard_map region; otherwise the per-token body is the
    gspmd step.  The factory tags the returned fn with ``.megastep``
    (``"scan-K{K}"``) — recorded by dry-run artifacts so a silent fallback
    to per-token dispatch fails CI's ``--expect-fused``."""
    _warn_kernel_fallbacks(cfg, rules)
    if rules is not None and _manual_decode_ok(cfg, rules):
        return _make_manual_serve_megastep(cfg, S_max=S_max, K=K,
                                           rules=rules, page_size=page_size)
    if rules is not None and cfg.tp_impl == "manual":
        logger.warning(
            "fused manual-TP decode unavailable for %s — %s; "
            "megastep runs over the gspmd serve body",
            cfg.name, _manual_decode_reason(cfg, rules))
    n_chips = _n_chips(rules)

    def megastep(params, state, tokens, stop_len=None, forced=None,
                 forced_mask=None):
        def token_step(st, tok, pos, mrope):
            with ctx.use_rules(rules):
                return _serve_step_impl(cfg, params, st, tok, pos, mrope,
                                        rules=rules, S_max=S_max,
                                        page_size=page_size,
                                        n_chips=n_chips)
        return _mega_scan(cfg, K, token_step, state, tokens, stop_len,
                          forced, forced_mask)

    megastep.megastep = TP.decode_megastep_mode(cfg, rules, K)
    return megastep


# ---------------------------------------------------------------------------
# Fused manual-TP decode (tp_impl="manual"): the whole step in ONE manual
# shard_map region over every mesh axis.

def _qkv_decode_shard(ap, x, kv_rep: int):
    """Per-chip decode QKV inside the fused manual region.  ``kv_rep == 1``:
    the K/V weights were head-sharded by the enclosing shard_map and the
    projection is already local.  ``kv_rep > 1`` (model axis wider than
    n_kv): the K/V weights arrive REPLICATED — compute the full [B, n_kv,
    hd] K/V and keep this chip's single replicated head."""
    q, k, v = L.attn_qkv_decode(ap, x)
    k, v = L.kv_head_slice(k, v, jax.lax.axis_index("model"), kv_rep)
    return q, k, v


def _paged_attn_shard(cfg, x, ap, pools, scales, layer, lp, write_slot,
                      positions, mrope, *, chip_pd, npr, page_size, pd_axes,
                      kv_rep=1, fused_bt=None, interpret=False):
    """One attention sublayer inside the fused manual region, local head
    shard end-to-end: column-parallel QKV, KV write into the chip's own
    pages, per-chip paged attention over local (page, head) slices, lse
    merge across the page axes only, row-parallel out + one psum.  With
    ``kv_rep > 1`` each chip holds ONE replicated KV head serving its
    (disjoint) slice of that head's query group — the psum over ``model``
    still sums distinct q-head contributions exactly once."""
    B = x.shape[0]
    with jax.named_scope("attn_proj"):
        q, k, v = _qkv_decode_shard(ap, x[:, 0], kv_rep)
        q = _rope_single(cfg, q, positions, mrope)
        k = _rope_single(cfg, k, positions, mrope)
    with jax.named_scope("kv_write"):
        pools, scales = paged.write_token_kv(pools, scales, k, v,
                                             write_slot, positions, chip_pd,
                                             npr, page_size, layer)
    kv_l = k.shape[1]                              # n_kv·rep / tp
    G_l = q.shape[1] // kv_l                       # local group size
    with jax.named_scope("attend"):
        if fused_bt is not None:
            # one Pallas dispatch per layer: in-kernel walk of the
            # chip-local raw block table + double-buffered page DMA
            # (kernels/fused_decode); same (o, m, l) partials contract as
            # paged.attend_local
            o, m, l = fused_decode_kernel(q, pools.k, pools.v, fused_bt,
                                          positions, layer=layer,
                                          scales=scales, partials=True,
                                          interpret=interpret)
        else:
            qg = q.reshape(B, kv_l, G_l, cfg.hd)   # grouping is head-local
            o, m, l = paged.attend_local(qg, pools, scales, layer, lp,
                                         positions, page_size)
        out = paged.merge_global(o, m, l, pd_axes)  # heads never cross chips
        out = out.reshape(B, kv_l * G_l, cfg.hd).astype(x.dtype)
    with jax.named_scope("attn_proj"):
        y = jax.lax.psum(L.attn_out_decode(ap, out),
                         "model").astype(x.dtype)
    return y[:, None], pools, scales


def _ring_attn_shard(cfg, x, ap, ring_k_l, ring_v_l, ring_pos, positions,
                     kv_rep=1):
    """gemma3 local-window layer inside the fused manual region: the ring
    buffer is head-sharded over ``model`` (same tiled-head layout as the
    pools), each chip attends its own q-head slice against its resident KV
    head's full window — the softmax needs no cross-chip merge — then
    row-parallel out + one psum.  x [B,1,d]; ring_*_l [B,W,kv_l,hd]."""
    B = x.shape[0]
    W = ring_k_l.shape[1]
    with jax.named_scope("attn_proj"):
        q, k, v = _qkv_decode_shard(ap, x[:, 0], kv_rep)
        q = _rope_single(cfg, q, positions)
        k = _rope_single(cfg, k, positions)
    slot = positions % W
    with jax.named_scope("kv_write"):
        ring_k_l = ring_k_l.at[jnp.arange(B), slot].set(
            k.astype(ring_k_l.dtype))
        ring_v_l = ring_v_l.at[jnp.arange(B), slot].set(
            v.astype(ring_v_l.dtype))

    kv_l = k.shape[1]
    G_l = q.shape[1] // kv_l
    with jax.named_scope("attend"):
        qg = q.reshape(B, kv_l, G_l, cfg.hd)
        s = jnp.einsum("bkgd,bwkd->bkgw", qg.astype(jnp.float32),
                       ring_k_l.astype(jnp.float32)) / math.sqrt(cfg.hd)
        ok = (ring_pos >= 0) & (ring_pos <= positions[:, None]) & \
            (ring_pos > positions[:, None] - W)
        ok = ok.at[jnp.arange(B), slot].set(True)
        s = jnp.where(ok[:, None, None, :], s, paged.NEG_INF)
        p = jax.nn.softmax(s, axis=-1)
        o = jnp.einsum("bkgw,bwkd->bkgd", p, ring_v_l.astype(jnp.float32))
        o = o.reshape(B, kv_l * G_l, cfg.hd).astype(x.dtype)
    with jax.named_scope("attn_proj"):
        y = jax.lax.psum(L.attn_out_decode(ap, o), "model").astype(x.dtype)
    return y[:, None], ring_k_l, ring_v_l


def _manual_decode_parts(cfg, *, S_max: int, rules,
                         page_size: int = DEFAULT_PAGE_SIZE):
    """Shared pieces of the fused manual-TP decode region: the shard_map
    spec builder and the per-token body (runs INSIDE the region) — used by
    both the single serve step and the K-token megastep, which wraps the
    same body in an in-region ``lax.scan``."""
    mesh = rules.mesh
    pd_axes = _pd_axes(rules)
    n_pd = _n_pd(rules)
    tp = mesh.shape["model"]
    kv_rep = TP.decode_kv_rep(cfg, tp)
    ssm_tp = cfg.family == "hybrid" and TP.decode_ssm_tp(cfg, tp)
    maxP = -(-S_max // page_size)
    vocab_sharded = (not cfg.tie_embeddings) and cfg.vocab_size % tp == 0
    use_fused = _fused_kernel_ok(cfg, rules)
    interp = _kernel_interpret()

    def make_specs(params, state):
        pool_spec = P(None, pd_axes or None, None, "model", None)
        state_specs: Dict[str, Any] = {k: P() for k in state}
        state_specs["pools"] = paged.PagedPools(k=pool_spec, v=pool_spec)
        if "pool_scales" in state:
            sc = P(None, pd_axes or None, None, "model")
            state_specs["pool_scales"] = paged.PoolScales(k=sc, v=sc)
        if "ring_k" in state:
            ring_spec = P(None, None, None, "model", None)
            state_specs["ring_k"] = ring_spec
            state_specs["ring_v"] = ring_spec
        if ssm_tp and "ssm" in state:
            # mamba state head-sharded over model (ssm_heads / ssm_inner
            # rules): h [L,B,G,Hg,P,N] on Hg, conv_x [L,B,W-1,di] on di;
            # the shared B/C conv tail stays replicated
            state_specs["ssm"] = ssm.MambaState(
                h=P(None, None, None, "model", None, None),
                conv_x=P(None, None, None, "model"),
                conv_bc=P())
        param_specs = TP.decode_param_specs(cfg, params,
                                            vocab_sharded=vocab_sharded,
                                            kv_rep=kv_rep, ssm_tp=ssm_tp)
        return param_specs, state_specs

    def token_body(params, state, tokens, positions, mrope, *, npr, cap):
        with jax.named_scope("embed"):
            x = nn.embed_lookup(params["embed"], tokens)  # replicated
        new_state = dict(state)
        chip_pd = _chip_idx(pd_axes, mesh)
        act = state["active"] & ~state["aborted"]
        # once per token, identical on every chip: incremental allocation
        # (only crossings probe) + the cached block-table read; the paper's
        # wait-free lookup stays authoritative for admission/rebuild
        with jax.named_scope("allocator"):
            (table, write_slot, aborts), bt = \
                _pt(cfg).alloc_step_incremental(
                    state["table"], state["seq_ids"], positions,
                    state["block_table"], page_size=page_size, active=act)
            if use_fused:
                # the fused kernel walks the raw block table in-kernel: no
                # materialized slots view, no per-chip compaction pass
                lp, fused_bt = None, _local_block_table(bt, chip_pd, npr)
            else:
                slots = PT.PageTable.block_table_slots(
                    bt, positions, page_size=page_size)
                lp = paged.compact_local(slots, chip_pd, npr, cap)
                fused_bt = None
        new_state["table"] = table
        new_state["block_table"] = bt
        new_state["aborted"] = state["aborted"] | aborts
        if "counters" in state:
            # replicated scalar adds, identical on every chip — the counter
            # plane crosses to the host only at the per-K megastep sync
            new_state["counters"] = OC.update_token_counters(
                state["counters"], act=act, aborts=aborts,
                positions=positions, page_size=page_size,
                table_before=state["table"], table_after=table)

        attn = functools.partial(
            _paged_attn_shard, cfg, lp=lp, write_slot=write_slot,
            positions=positions, chip_pd=chip_pd, npr=npr,
            page_size=page_size, pd_axes=pd_axes, kv_rep=kv_rep,
            fused_bt=fused_bt, interpret=interp)

        if cfg.pattern_local:
            x_out = _gemma_layers_shard(cfg, params, state, new_state,
                                        x, attn, positions, kv_rep)
        elif cfg.family == "hybrid":
            x_out = _hybrid_layers_shard(cfg, params, state, new_state,
                                         x, attn,
                                         ssm_axis="model" if ssm_tp
                                         else None)
        else:
            def layer(carry, xs):
                x, pools, scales = carry
                lpar, li = xs
                h, pools, scales = attn(
                    nn.rmsnorm(lpar["ln1"], x), lpar["attn"], pools,
                    scales, li, mrope=mrope)
                x = x + h
                xn = nn.rmsnorm(lpar["ln2"], x)
                with jax.named_scope("mlp"):
                    if cfg.family == "moe":
                        y = MOE.moe_decode_local(lpar["moe"], xn, cfg)
                    else:
                        y = TP.mlp_decode_manual(lpar["mlp"], xn)
                return (x + y, pools, scales), None

            (x_out, pools, scales), _ = jax.lax.scan(
                layer, (x, state["pools"], state.get("pool_scales")),
                (params["layers"], jnp.arange(cfg.num_layers)),
                unroll=cfg.scan_unroll)
            _set_pools(new_state, pools, scales)
        with jax.named_scope("lm_head"):
            x_out = nn.rmsnorm(params["final_norm"], x_out)
            logits = TP.logits_decode_manual(cfg, params, x_out,
                                             vocab_sharded=vocab_sharded)
        new_state["pos"] = jnp.where(act & ~aborts, positions + 1,
                                     positions)
        return logits[:, 0].astype(jnp.float32), new_state

    return mesh, n_pd, maxP, make_specs, token_body


def _make_manual_serve_step(cfg, *, S_max: int, rules,
                            page_size: int = DEFAULT_PAGE_SIZE):
    """Decode step for ``tp_impl="manual"``: page-table alloc + block-table
    read + compaction + all layers + read-out fused into a single manual
    shard_map (see module docstring for the layout).  Covers the dense /
    moe / vlm stacked scan, the gemma3 local:global superblocks (ring
    buffers head-sharded in-region) and the hybrid mamba backbone + shared
    attention block (mamba replicated, shared block Megatron-sharded)."""
    mesh, n_pd, maxP, make_specs, token_body = _manual_decode_parts(
        cfg, S_max=S_max, rules=rules, page_size=page_size)

    def serve_step(params, state, tokens, positions, mrope_positions=None):
        B = tokens.shape[0]
        n_pages = state["pools"].k.shape[1]
        npr = n_pages // n_pd
        cap = paged.capacity(B, maxP, n_pd,
                             factor=cfg.page_capacity_factor)
        param_specs, state_specs = make_specs(params, state)
        mr_spec = P() if mrope_positions is not None else None

        def body(params, state, tokens, positions, mrope):
            return token_body(params, state, tokens, positions, mrope,
                              npr=npr, cap=cap)

        mapped = shard_map(
            body, mesh=mesh,
            in_specs=(param_specs, state_specs, P(), P(), mr_spec),
            out_specs=(P(), state_specs), check_vma=False)
        return mapped(params, state, tokens, positions, mrope_positions)

    return serve_step


def _mega_scan(cfg, K: int, token_step, state, tokens, stop_len,
               forced=None, forced_mask=None):
    """The K-token scan at the megastep's core: in-graph greedy sampling
    feeds token t+1 from token t's logits; a lane whose allocation ABORTs
    latches — its pending (refused) token and position freeze so the host
    can re-issue the suffix after a rebuild; with ``stop_len`` a lane whose
    position reaches its stop latches ``active=False`` (done) in-graph.
    Returns (tokens int32[B, K] — entry k is the token sampled after step k,
    frozen at the refused token for aborted lanes — and the final state).

    CHUNKED PREFILL (``forced``/``forced_mask`` int32/bool[B, K]): where
    ``forced_mask[:, k]`` is True, the token FED at scan step k+1 is
    ``forced[:, k]`` instead of the greedy sample — a prefilling lane
    consumes up to K prompt tokens per dispatch (its KV is written exactly
    as in teacher forcing) and transitions to greedy decode mid-megastep
    the moment its mask runs out, so prefill and decode share one dispatch
    budget.  Column K-1 overrides the RETURNED pending feed ``toks[:, -1]``
    (the next round's first token).  The abort latch wins over forcing: a
    refused forced token stays pending for the post-rebuild re-issue."""
    B = tokens.shape[0]

    def one(carry, xs):
        st, tok = carry
        pos = st["pos"]
        mrope = (jnp.broadcast_to(pos[None, :, None],
                                  (3, B, 1)).astype(jnp.int32)
                 if cfg.family == "vlm" else None)
        logits, st2 = token_step(st, tok, pos, mrope)
        with jax.named_scope("sampling"):
            nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)[:, None]
            if xs is not None:
                f_tok, f_msk = xs
                nxt = jnp.where(f_msk[:, None], f_tok[:, None], nxt)
            # aborted lanes keep their refused token pending for the
            # re-issue
            tok2 = jnp.where(st2["aborted"][:, None], tok, nxt)
            if stop_len is not None:
                st2 = dict(st2)
                st2["active"] = st2["active"] & (st2["pos"] < stop_len)
        return (st2, tok2), tok2[:, 0]

    xs = None
    if forced is not None:
        xs = (jnp.asarray(forced, jnp.int32).T,
              jnp.asarray(forced_mask, bool).T)       # [K, B] scan inputs
    (st, _), toks = jax.lax.scan(one, (state, tokens), xs, length=K)
    return toks.T, st


def _make_manual_serve_megastep(cfg, *, S_max: int, K: int, rules,
                                page_size: int = DEFAULT_PAGE_SIZE):
    """Megastep twin of ``_make_manual_serve_step``: the K-token scan lives
    INSIDE the single fully-manual shard_map region (the pinned XLA rejects
    partially-auto regions — dist/README), so K tokens cost one dispatch
    and zero host round-trips."""
    mesh, n_pd, maxP, make_specs, token_body = _manual_decode_parts(
        cfg, S_max=S_max, rules=rules, page_size=page_size)

    def megastep(params, state, tokens, stop_len=None, forced=None,
                 forced_mask=None):
        B = tokens.shape[0]
        n_pages = state["pools"].k.shape[1]
        npr = n_pages // n_pd
        cap = paged.capacity(B, maxP, n_pd,
                             factor=cfg.page_capacity_factor)
        param_specs, state_specs = make_specs(params, state)
        stop_spec = P() if stop_len is not None else None
        f_spec = P() if forced is not None else None

        def body(params, state, tokens, stop_len, forced, forced_mask):
            def token_step(st, tok, pos, mrope):
                return token_body(params, st, tok, pos, mrope,
                                  npr=npr, cap=cap)
            return _mega_scan(cfg, K, token_step, state, tokens, stop_len,
                              forced, forced_mask)

        mapped = shard_map(
            body, mesh=mesh,
            in_specs=(param_specs, state_specs, P(), stop_spec, f_spec,
                      f_spec),
            out_specs=(P(), state_specs), check_vma=False)
        return mapped(params, state, tokens, stop_len, forced, forced_mask)

    megastep.megastep = TP.decode_megastep_mode(cfg, rules, K)
    return megastep


def _gemma_layers_shard(cfg, params, state, new_state, x, attn, positions,
                        kv_rep):
    """gemma3 superblocks inside the fused manual region: ``pattern_local``
    ring layers (head-sharded window attention) + 1 paged global layer per
    group — the manual twin of ``_gemma_layers``."""
    pat = cfg.pattern_local
    group = pat + 1
    ng = cfg.num_layers // group
    stacked = jax.tree.map(
        lambda t: t.reshape((ng, group) + t.shape[1:]), params["layers"])
    B, W = state["ring_pos"].shape
    ring_k = state["ring_k"].reshape((ng, pat) + state["ring_k"].shape[1:])
    ring_v = state["ring_v"].reshape((ng, pat) + state["ring_v"].shape[1:])

    def body(carry, xs):
        x, pools, scales = carry
        grp, rks, rvs, gi = xs
        new_rk, new_rv = [], []
        for i in range(pat):
            sub = jax.tree.map(lambda t: t[i], grp)
            h, rk2, rv2 = _ring_attn_shard(
                cfg, nn.rmsnorm(sub["ln1"], x), sub["attn"], rks[i],
                rvs[i], state["ring_pos"], positions, kv_rep)
            x = x + h
            xn = nn.rmsnorm(sub["ln2"], x)
            with jax.named_scope("mlp"):
                x = x + TP.mlp_decode_manual(sub["mlp"], xn)
            new_rk.append(rk2)
            new_rv.append(rv2)
        sub = jax.tree.map(lambda t: t[pat], grp)
        h, pools, scales = attn(nn.rmsnorm(sub["ln1"], x), sub["attn"],
                                pools, scales, gi, mrope=None)
        x = x + h
        xn = nn.rmsnorm(sub["ln2"], x)
        with jax.named_scope("mlp"):
            x = x + TP.mlp_decode_manual(sub["mlp"], xn)
        return (x, pools, scales), (jnp.stack(new_rk), jnp.stack(new_rv))

    (x, pools, scales), (rk, rv) = jax.lax.scan(
        body, (x, state["pools"], state.get("pool_scales")),
        (stacked, ring_k, ring_v, jnp.arange(ng)),
        unroll=ng if cfg.unroll_layers else 1)
    new_state["ring_k"] = rk.reshape((ng * pat,) + rk.shape[2:])
    new_state["ring_v"] = rv.reshape((ng * pat,) + rv.shape[2:])
    new_state["ring_pos"] = state["ring_pos"].at[
        jnp.arange(B), positions % W].set(positions)
    _set_pools(new_state, pools, scales)
    return x


def _hybrid_layers_shard(cfg, params, state, new_state, x, attn,
                         ssm_axis=None):
    """zamba2 hybrid inside the fused manual region: the ONE shared
    attention + MLP block is Megatron-sharded with per-invocation paged KV;
    the Mamba backbone shards its per-head inner dims over ``model``
    (``ssm_axis="model"`` when ``dist/tp.decode_ssm_tp`` passes — params
    and recurrent state arrive head-sharded, ``mamba_decode_step`` psums
    the RMS statistic and the row-parallel out projection) and runs as
    replicated redundant compute otherwise."""
    every = cfg.shared_attn_every
    n_inv = cfg.num_layers // every
    sp = params["shared"]
    pools, scales = state["pools"], state.get("pool_scales")
    new_ssm_chunks = []
    for g in range(n_inv):
        with jax.named_scope("ssm"):
            x, s2 = HY.mamba_decode_chunk(cfg, params["layers"],
                                          state["ssm"], x, g * every,
                                          (g + 1) * every, tp_axis=ssm_axis)
        new_ssm_chunks.append(s2)
        h, pools, scales = attn(nn.rmsnorm(sp["ln1"], x), sp["attn"],
                                pools, scales, g, mrope=None)
        x = x + h
        xn = nn.rmsnorm(sp["ln2"], x)
        with jax.named_scope("mlp"):
            x = x + TP.mlp_decode_manual(sp["mlp"], xn)
    rem = cfg.num_layers - n_inv * every
    if rem:
        with jax.named_scope("ssm"):
            x, s2 = HY.mamba_decode_chunk(cfg, params["layers"],
                                          state["ssm"], x, n_inv * every,
                                          cfg.num_layers, tp_axis=ssm_axis)
        new_ssm_chunks.append(s2)
    # new_state["aborted"] already includes this step's aborts: a refused
    # lane's recurrence must not advance (its token is re-issued later)
    new_state["ssm"] = _freeze_lanes(
        jax.tree.map(lambda *ts: jnp.concatenate(ts, axis=0),
                     *new_ssm_chunks),
        state["ssm"], state["active"] & ~new_state["aborted"])
    _set_pools(new_state, pools, scales)
    return x


@jax.named_scope("allocator")
def _page_ops(cfg, state, positions, active, *, S_max, page_size, n_chips,
              rules, fused=False):
    """Once-per-token page-table work: incremental allocation (only the
    page-boundary crossings probe the table) + the block-table read served
    from the persistent cache — O(crossings) probes instead of the
    O(B·max_pages) full re-probe (``PageTable.lookup_pages`` stays the
    authoritative path for admission / rebuild / verification).  With
    ``fused`` the slots view + per-chip compaction are skipped entirely:
    the fused kernel walks the raw block table in-kernel."""
    maxP = -(-S_max // page_size)
    (table, write_slot, aborts), bt = _pt(cfg).alloc_step_incremental(
        state["table"], state["seq_ids"], positions, state["block_table"],
        page_size=page_size, active=active)
    if fused:
        return table, write_slot, aborts, bt, None
    slots = PT.PageTable.block_table_slots(bt, positions,
                                           page_size=page_size)
    B = positions.shape[0]
    cap = paged.capacity(B, maxP, n_chips,
                         factor=cfg.page_capacity_factor)
    lp_arrays = compact_op(rules, slots, BT.size(table), cap)
    return table, write_slot, aborts, bt, lp_arrays


@jax.named_scope("state_freeze")
def _freeze_lanes(new_tree, old_tree, act):
    """Per-lane state freeze for refused/inactive lanes: leaves are
    [L, B, ...] stacked per-layer state.  A refused token must be
    side-effect-free — SSM recurrences are NOT idempotent under re-issue
    (unlike the KV/ring writes, which rewrite the same slot with the same
    value), so the engine masks them here."""
    def sel(n, o):
        m = act.reshape((1, -1) + (1,) * (n.ndim - 2))
        return jnp.where(m, n, o)
    return jax.tree.map(sel, new_tree, old_tree)


def _set_pools(new_state, pools, scales):
    """Store the layer loop's pools (and int8 scales) in the new state."""
    new_state["pools"] = pools
    if scales is not None:
        new_state["pool_scales"] = scales


@jax.named_scope("mlp")
def _mlp_or_moe(cfg, p, x):
    if cfg.family == "moe":
        y, _ = MOE.moe_apply(p["moe"], x, cfg)
        return y
    return L.mlp_apply(p["mlp"], x)


def _serve_step_impl(cfg, params, state, tokens, positions, mrope,
                     *, rules, S_max, page_size, n_chips):
    B = tokens.shape[0]
    with jax.named_scope("embed"):
        x = nn.embed_lookup(params["embed"], tokens)  # [B,1,d]
    new_state = dict(state)
    act = state["active"] & ~state["aborted"]
    aborts = jnp.zeros((B,), bool)
    fused = _fused_kernel_ok(cfg, rules)
    interp = _kernel_interpret()

    if cfg.family in ("dense", "moe", "vlm"):
        table, write_slot, aborts, bt, lp = _page_ops(
            cfg, state, positions, act, S_max=S_max, page_size=page_size,
            n_chips=n_chips, rules=rules, fused=fused)
        new_state["table"] = table
        new_state["block_table"] = bt

        if cfg.pattern_local:
            x, pools, ring, scales = _gemma_layers(cfg, params, state, x,
                                                   lp, write_slot,
                                                   positions, rules,
                                                   page_size, bt=bt,
                                                   fused=fused,
                                                   interpret=interp)
            new_state["ring_k"], new_state["ring_v"], new_state["ring_pos"] \
                = ring
        else:
            # the pools ride the layer loop's carry, so each layer's KV
            # write updates them in place (as scan xs/ys every step would
            # copy the whole pool into a fresh stacked output)
            def body(carry, xs):
                x, pools, scales = carry
                lp_params, li = xs
                h, pools, scales = paged_attn_op(
                    cfg, rules, nn.rmsnorm(lp_params["ln1"], x),
                    lp_params["attn"], pools, scales, li, lp, write_slot,
                    positions, mrope, page_size, bt=bt if fused else None,
                    fused=fused, interpret=interp)
                x = x + h
                x = x + _mlp_or_moe(cfg, lp_params,
                                    nn.rmsnorm(lp_params["ln2"], x))
                return (x, pools, scales), None

            (x, pools, scales), _ = jax.lax.scan(
                body, (x, state["pools"], state.get("pool_scales")),
                (params["layers"], jnp.arange(cfg.num_layers)),
                unroll=cfg.scan_unroll)
        _set_pools(new_state, pools, scales)

    elif cfg.family == "ssm":
        def body(x, xs):
            lp_params, st = xs
            xn = nn.rmsnorm(lp_params["ln"], x)
            with jax.named_scope("ssm"):
                h, st2 = ssm.mamba_decode_step(lp_params["mamba"], xn, cfg,
                                               st)
            return x + h, st2

        x, ssm2 = jax.lax.scan(body, x, (params["layers"], state["ssm"]),
                               unroll=cfg.scan_unroll)
        new_state["ssm"] = _freeze_lanes(ssm2, state["ssm"], act)

    elif cfg.family == "hybrid":
        table, write_slot, aborts, bt, lp = _page_ops(
            cfg, state, positions, act, S_max=S_max, page_size=page_size,
            n_chips=n_chips, rules=rules, fused=fused)
        new_state["table"] = table
        new_state["block_table"] = bt
        every = cfg.shared_attn_every
        n_inv = cfg.num_layers // every

        new_ssm_chunks = []
        pools, scales = state["pools"], state.get("pool_scales")
        sp = params["shared"]
        for g in range(n_inv):
            with jax.named_scope("ssm"):
                x, s2 = HY.mamba_decode_chunk(cfg, params["layers"],
                                              state["ssm"], x,
                                              g * every, (g + 1) * every)
            new_ssm_chunks.append(s2)
            h, pools, scales = paged_attn_op(
                cfg, rules, nn.rmsnorm(sp["ln1"], x), sp["attn"], pools,
                scales, g, lp, write_slot, positions, None, page_size,
                bt=bt if fused else None, fused=fused, interpret=interp)
            x = x + h
            xn = nn.rmsnorm(sp["ln2"], x)
            with jax.named_scope("mlp"):
                x = x + L.mlp_apply(sp["mlp"], xn)
        rem = cfg.num_layers - n_inv * every
        if rem:
            with jax.named_scope("ssm"):
                x, s2 = HY.mamba_decode_chunk(cfg, params["layers"],
                                              state["ssm"], x,
                                              n_inv * every, cfg.num_layers)
            new_ssm_chunks.append(s2)
        # a lane refused THIS step (abort) re-issues its token after the
        # rebuild — its recurrent state must not advance either
        new_state["ssm"] = _freeze_lanes(
            jax.tree.map(lambda *ts: jnp.concatenate(ts, axis=0),
                         *new_ssm_chunks), state["ssm"], act & ~aborts)
        _set_pools(new_state, pools, scales)

    elif cfg.family == "encdec":
        table, write_slot, aborts, bt, lp = _page_ops(
            cfg, state, positions, act, S_max=S_max, page_size=page_size,
            n_chips=n_chips, rules=rules)
        new_state["table"] = table
        new_state["block_table"] = bt

        def body(carry, xs):
            x, pools, scales = carry
            lp_params, li, ck, cv = xs
            h, pools, scales = paged_attn_op(
                cfg, rules, nn.rmsnorm(lp_params["ln1"], x),
                lp_params["attn"], pools, scales, li, lp, write_slot,
                positions, None, page_size)
            x = x + h
            x = x + _cross_attn_decode(cfg, nn.rmsnorm(lp_params["ln_cross"], x),
                                       lp_params["cross"], ck, cv)
            xn = nn.rmsnorm(lp_params["ln2"], x)
            with jax.named_scope("mlp"):
                x = x + L.mlp_apply(lp_params["mlp"], xn)
            return (x, pools, scales), None

        (x, pools, scales), _ = jax.lax.scan(
            body, (x, state["pools"], state.get("pool_scales")),
            (params["decoder"], jnp.arange(cfg.num_layers),
             state["cross_k"], state["cross_v"]),
            unroll=cfg.scan_unroll)
        _set_pools(new_state, pools, scales)
    else:
        raise ValueError(cfg.family)

    with jax.named_scope("lm_head"):
        x = nn.rmsnorm(params["final_norm"], x)
        if cfg.tie_embeddings:
            logits = nn.embed_logits(params["embed"], x)
        else:
            logits = nn.dense(params["lm_head"], x)
    # inactive lanes stay frozen; aborted lanes refuse the token (pos not
    # advanced, no KV written — the caller must evict or rebuild)
    new_state["aborted"] = state["aborted"] | aborts
    new_state["pos"] = jnp.where(act & ~aborts, positions + 1, positions)
    if "counters" in state:
        # one site covers every family: paged families routed through
        # _page_ops (table deltas + probe twin), ssm has no table leaf so
        # only token/abort counts tick
        new_state["counters"] = OC.update_token_counters(
            state["counters"], act=act, aborts=aborts, positions=positions,
            page_size=page_size, table_before=state.get("table"),
            table_after=new_state.get("table"))
    return logits[:, 0].astype(jnp.float32), new_state


def prepare_encdec_state(cfg, params, state, src_embeds, *, rules=None):
    """Run the encoder and fill the decoder's cross K/V (the enc-dec
    'prefill').  src_embeds [B, S_src, d] (stub audio frontend)."""
    from repro.models import encdec
    with ctx.use_rules(rules):
        memory = encdec.encode(cfg, params, src_embeds)

        def one_layer(lp_params):
            cp = lp_params["cross"]
            k = jnp.einsum("bsd,dhk->bshk", memory, cp["wk"])
            v = jnp.einsum("bsd,dhk->bshk", memory, cp["wv"])
            if "bk" in cp:
                k, v = k + cp["bk"], v + cp["bv"]
            return k, v

        ck, cv = jax.vmap(one_layer)(params["decoder"])
    state = dict(state)
    state["cross_k"], state["cross_v"] = ck, cv
    return state


def _gemma_layers(cfg, params, state, x, lp, write_slot, positions, rules,
                  page_size, bt=None, fused=False, interpret=False):
    """gemma3 superblocks at decode: pattern_local ring layers + 1 paged."""
    pat = cfg.pattern_local
    group = pat + 1
    ng = cfg.num_layers // group
    stacked = jax.tree.map(
        lambda t: t.reshape((ng, group) + t.shape[1:]), params["layers"])
    B, W = state["ring_pos"].shape
    ring_k = state["ring_k"].reshape((ng, pat) + state["ring_k"].shape[1:])
    ring_v = state["ring_v"].reshape((ng, pat) + state["ring_v"].shape[1:])

    def body(carry, xs):
        x, pools, scales = carry
        grp, rks, rvs, gi = xs
        new_rk, new_rv = [], []
        for i in range(pat):
            sub = jax.tree.map(lambda t: t[i], grp)
            h, rk2, rv2 = _ring_attn(cfg, nn.rmsnorm(sub["ln1"], x),
                                     sub["attn"], rks[i], rvs[i],
                                     state["ring_pos"], positions)
            x = x + h
            xn = nn.rmsnorm(sub["ln2"], x)
            with jax.named_scope("mlp"):
                x = x + L.mlp_apply(sub["mlp"], xn)
            new_rk.append(rk2)
            new_rv.append(rv2)
        sub = jax.tree.map(lambda t: t[pat], grp)
        h, pools, scales = paged_attn_op(
            cfg, rules, nn.rmsnorm(sub["ln1"], x), sub["attn"], pools,
            scales, gi, lp, write_slot, positions, None, page_size,
            bt=bt if fused else None, fused=fused, interpret=interpret)
        x = x + h
        xn = nn.rmsnorm(sub["ln2"], x)
        with jax.named_scope("mlp"):
            x = x + L.mlp_apply(sub["mlp"], xn)
        return (x, pools, scales), (jnp.stack(new_rk), jnp.stack(new_rv))

    (x, pools, scales), (rk, rv) = jax.lax.scan(
        body, (x, state["pools"], state.get("pool_scales")),
        (stacked, ring_k, ring_v, jnp.arange(ng)),
        unroll=ng if cfg.unroll_layers else 1)
    rk = rk.reshape((ng * pat,) + rk.shape[2:])
    rv = rv.reshape((ng * pat,) + rv.shape[2:])
    ring_pos = state["ring_pos"].at[jnp.arange(B), positions % W].set(
        positions)
    return x, pools, (rk, rv, ring_pos), scales
