"""The paper's hash table as the paged-KV page table / allocator.

The linear-probing table IS the allocator: the table has one cell per
physical KV page, keyed by ``(seq_id, logical_page)``; *claiming cell i
allocates physical page i*.  The paper's operations map 1:1 onto the
serving runtime:

* ``insert`` — page allocation (one per sequence per ``page_size`` tokens);
  probe-order arbitration resolves races between concurrent allocations.
* wait-free ``lookup`` — the block-table read on EVERY decode step's
  critical path (kernels/probe is the Pallas fast path).
* ``delete`` — sequence eviction: all its pages become TOMBSTONEs, and
  **tombstone reuse** (the paper's headline) means freed page slots are
  re-claimed by later allocations directly — no compaction, no rebuild,
  no fragmentation sweep.  This is Proposition 2 operating as a memory
  allocator.

Key packing: key = seq_id * MAX_LOGICAL_PAGES + logical_page (28-bit key
space from core/encoding: seq_id < 2^17 with 2^11 logical pages covers
500k-token contexts at page_size 256).

Incremental block table (the decode hot path): the full ``lookup_pages``
read is O(B·max_pages) probed keys per call, but between two decode steps
at most the page-boundary crossings changed.  ``alloc_step_incremental``
therefore maintains a persistent ``block_table`` int32[B, max_pages] cache
by scatter — the per-token probe work drops to O(crossings) — while the
wait-free lookup stays the *authoritative* read used to (re)build the cache
on admission (``rebuild_block_table``), after a Section 4.3 rebuild, and in
the CI-only verification mode (``verify_block_table``).  Eviction must
invalidate the evicted lanes' rows (``invalidate_block_rows``) or a
re-admitted slot could read a reclaimed page.

Probe strategies: the ``PageTable`` facade binds one ``core/
probe_strategies`` strategy (``linear`` / ``robinhood`` / ``hopscotch``)
at construction and threads it through every operation — callers hold one
facade object (``for_strategy``) instead of plumbing a keyword through
every call site.  (The historical module-level free functions were removed
once the last in-repo callers migrated; the facade is the only API.)

The distributed flavour — hash-prefix sharding of the key space across
host groups, per-shard headroom, lazy incremental resize — lives one layer
up in ``serving/sharded_table.ShardedPageTable``, which routes to one
table-per-shard built from this module's primitives.
"""
from __future__ import annotations

import contextlib
import functools
import logging
from typing import Iterator, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro import kernels as KN
from repro import obs as OBS
from repro.core import batched as BT
from repro.core import encoding as E
from repro.core.probe_strategies import get_strategy

logger = logging.getLogger(__name__)

MAX_LOGICAL_PAGES = 2048  # 2^11 -> 500k tokens at page_size 256

# ---------------------------------------------------------------------------
# Probe accounting (machine-independent perf counter).
#
# Counts keys submitted to table probe operations (insert/find/delete) by the
# page-table layer.  Only *concrete* (eager) calls count — under jit the
# counts are tracers and are skipped — which is exactly what the
# ``probes_per_token`` benchmark wants: a deterministic host-side replay.

PROBE_STATS = {"keys_probed": 0}


def probe_stats_reset() -> None:
    PROBE_STATS["keys_probed"] = 0


@contextlib.contextmanager
def probe_stats_scope() -> Iterator[dict]:
    """Scoped probe accounting: inside the ``with`` block the counter starts
    at 0 and counts only the scope's own (eager) probes; on exit the
    enclosing counter value is RESTORED exactly, so one batcher run / bench
    can never bleed counts into another (the PROBE_STATS lifecycle bug).
    Read the scoped count from the yielded dict *before* the block exits:

        with PT.probe_stats_scope() as ps:
            ...page-table calls...
            n = ps["keys_probed"]

    Scopes nest: each level sees only its own counts."""
    outer = PROBE_STATS["keys_probed"]
    PROBE_STATS["keys_probed"] = 0
    try:
        yield PROBE_STATS
    finally:
        PROBE_STATS["keys_probed"] = outer


def _note_probes(n) -> None:
    try:
        PROBE_STATS["keys_probed"] += int(n)
    except (TypeError, jax.errors.TracerArrayConversionError,
            jax.errors.ConcretizationTypeError):
        pass  # traced: benchmark counters only apply to eager replays


def page_key(seq_ids, logical_pages):
    return (jnp.asarray(seq_ids, jnp.uint32) * jnp.uint32(MAX_LOGICAL_PAGES)
            + jnp.asarray(logical_pages, jnp.uint32))


class AllocStep(NamedTuple):
    """Result of one per-step allocation round.

    ``write_slot`` is -1 for lanes that must NOT write KV this step: inactive
    lanes (finished / padding slots) and lanes whose allocation ABORTed.  The
    -1 sentinel is a *refusal*, not an index — every consumer masks on
    ``write_slot >= 0`` before scattering (``paged.write_token_kv``), so an
    abort can never wrap into physical page -1 and corrupt another
    sequence's KV.  ``aborted`` surfaces the ABORT per lane so the engine /
    batcher can refuse the token and trigger the Section 4.3 rebuild path
    instead of silently serving garbage."""
    table: BT.HashTable
    write_slot: jnp.ndarray   # int32[B]
    aborted: jnp.ndarray      # bool[B]


class PageTableStats(NamedTuple):
    live_pages: jnp.ndarray
    tombstones: jnp.ndarray
    occupancy: jnp.ndarray


class Headroom(NamedTuple):
    """First-class occupancy/headroom view of the page pool (host ints —
    the admission controller's input).  With tombstone reuse (Prop. 2 as
    the allocator) a TOMBSTONE cell is immediately re-claimable, so the
    capacity that matters for admission is ``free_cells = n_pages -
    live_pages``: linear/robinhood ABORT only when every cell holds a live
    key.  Under hopscotch there are never tombstones — ``free_cells``
    counts EMPTY cells exactly — but displacement can fail before the pool
    is full, so ``slack`` carries the strategy's extra headroom requirement
    (``ProbeStrategy.forecast_slack``) for the forecaster's no-ABORT gate:
    admit only while ``demand + safety + slack <= free_cells``.
    ``occupancy`` keeps the paper's definition (non-EMPTY fraction, what
    forces rebuilds in NO-reuse designs) for comparison."""
    n_pages: int
    live_pages: int
    tombstones: int
    free_cells: int        # n_pages - live_pages (tombstones are reusable)
    live_fraction: float   # live_pages / n_pages — the abort-relevant load
    occupancy: float       # (live + tombstones) / n_pages (paper's metric)
    strategy: str = "linear"
    slack: int = 0         # strategy's forecast_slack(n_pages)


class PageTable:
    """Strategy-bound facade over the allocator.  Stateless apart from the
    static strategy string — table state stays a functional pytree passed
    in and returned, so one facade instance serves any number of pools and
    jit caches one program per strategy."""

    def __init__(self, strategy: str = "linear"):
        self._impl = get_strategy(strategy)  # validates the name eagerly
        self.strategy = strategy
        self._kernel_fallback_logged = False

    # -- construction / maintenance ------------------------------------

    def create_table(self, n_pages: int, seed: int = 0) -> BT.HashTable:
        return BT.create(n_pages, seed=seed, strategy=self.strategy)

    def rehash(self, table: BT.HashTable, n_pages: int,
               seed: Optional[int] = None
               ) -> Tuple[BT.HashTable, jnp.ndarray, jnp.ndarray,
                          jnp.ndarray]:
        """Section 4.3 rebuild, page-table flavoured: re-insert every live
        key into a fresh table of ``n_pages`` cells (a new seed by
        default).  Because the cell index IS the physical page, the caller
        must move the KV pages along with their keys: returns (table',
        old_slot[m], new_slot[m], live[m]) — the page permutation (padded
        entries have live=False)."""
        keys, n_live = BT.live_keys(table)
        live = jnp.arange(keys.shape[0]) < n_live
        fresh = BT.create(n_pages,
                          seed=(int(table.seed) + 1 if seed is None
                                else seed),
                          strategy=self.strategy)
        fresh, _ = BT.insert_batch(fresh, keys, active=live,
                                   strategy=self.strategy)
        _, old_slots = BT.find_batch(table, keys, live,
                                     strategy=self.strategy)
        _, new_slots = BT.find_batch(fresh, keys, live,
                                     strategy=self.strategy)
        return fresh, old_slots, new_slots, live

    # -- allocation -----------------------------------------------------

    def alloc_step(self, table: BT.HashTable, seq_ids, positions, *,
                   page_size: int, active=None) -> AllocStep:
        """Per decode step: allocate the page for each sequence's current
        position when it crosses a page boundary.

        ``active`` bool[B] (default all-True) masks lanes that are live:
        inactive lanes neither allocate (the phantom-page leak — a
        finished/padding lane would otherwise claim a real page every
        ``page_size`` steps until eviction) nor receive a
        ``write_slot``."""
        act = (jnp.ones(positions.shape, bool) if active is None
               else jnp.asarray(active, bool))
        page_idx = positions // page_size
        need_new = ((positions % page_size) == 0) & act
        keys = page_key(seq_ids, page_idx)
        table, ret = BT.insert_batch(table, keys, active=need_new,
                                     strategy=self.strategy)
        aborted = need_new & (ret == 2)
        found, slots = BT.find_batch(table, keys, strategy=self.strategy)
        _note_probes(jnp.sum(need_new) + positions.shape[0])
        # a miss means the allocator aborted (pool exhausted) — surface -1
        return AllocStep(table, jnp.where(found & act, slots, -1), aborted)

    def alloc_step_incremental(self, table: BT.HashTable, seq_ids,
                               positions, block_table, *, page_size: int,
                               active=None) -> Tuple[AllocStep, jnp.ndarray]:
        """``alloc_step`` with the incremental block-table cache: only the
        page-boundary crossings probe the table; every other lane's
        ``write_slot`` is served from ``block_table`` (int32[B, max_pages],
        -1 = absent).  Returns (AllocStep, block_table').

        Per-token probe work drops from O(B) to O(crossings); the crossing
        scatter keeps the cache equal to the authoritative wait-free lookup
        (``verify_block_table``).  On ABORT the crossing entry is written
        as -1 — the cache must never retain a stale slot for a page the
        allocator refused (a re-admitted lane's row could otherwise point
        at a reclaimed physical page)."""
        B = positions.shape[0]
        act = (jnp.ones(positions.shape, bool) if active is None
               else jnp.asarray(active, bool))
        page_idx = (positions // page_size).astype(jnp.int32)
        need_new = ((positions % page_size) == 0) & act
        keys = page_key(seq_ids, page_idx)
        table, ret = BT.insert_batch(table, keys, active=need_new,
                                     strategy=self.strategy)
        aborted = need_new & (ret == 2)
        found, slots = BT.find_batch(table, keys, active=need_new,
                                     strategy=self.strategy)
        _note_probes(2 * jnp.sum(need_new))
        fresh_slot = jnp.where(found & need_new, slots, -1)

        max_pages = block_table.shape[1]
        rows = jnp.arange(B, dtype=jnp.int32)
        cached = block_table[rows, jnp.clip(page_idx, 0, max_pages - 1)]
        write_slot = jnp.where(need_new, fresh_slot,
                               jnp.where(act, cached, -1))
        block_table = block_table.at[
            rows, jnp.where(need_new, page_idx, max_pages)].set(
            fresh_slot, mode="drop")
        return AllocStep(table, write_slot, aborted), block_table

    def prefill_alloc(self, table: BT.HashTable, seq_ids, lengths, *,
                      page_size: int, max_pages: int
                      ) -> Tuple[BT.HashTable, jnp.ndarray]:
        """Allocate all pages for freshly prefilling sequences.  Returns
        (table', slots [B, max_pages])."""
        B = seq_ids.shape[0]
        logical = jnp.arange(max_pages, dtype=jnp.uint32)
        keys = page_key(seq_ids[:, None], logical[None, :]).reshape(-1)
        need = (logical[None, :] * page_size < lengths[:, None]).reshape(-1)
        table, _ = BT.insert_batch(table, keys, active=need,
                                   strategy=self.strategy)
        found, slots = BT.find_batch(table, keys, strategy=self.strategy)
        slots = jnp.where(found & need, slots, -1)
        return table, slots.reshape(B, max_pages)

    # -- eviction -------------------------------------------------------

    def free_sequences(self, table: BT.HashTable, seq_ids, positions, *,
                       page_size: int, max_pages: int,
                       active=None) -> BT.HashTable:
        """Evict sequences: delete all their page keys -> slots immediately
        reusable by subsequent alloc_steps (no rebuild).  Linear/robinhood
        leave tombstones (reused, Prop. 2); hopscotch reclaims the cells to
        EMPTY outright."""
        B = seq_ids.shape[0]
        logical = jnp.arange(max_pages, dtype=jnp.uint32)
        keys = page_key(seq_ids[:, None], logical[None, :]).reshape(-1)
        act = jnp.broadcast_to(
            (logical[None, :] <= positions[:, None] // page_size) &
            (jnp.ones((B, 1), bool) if active is None
             else jnp.asarray(active, bool)[:, None]),
            (B, max_pages)).reshape(-1)
        with OBS.span("pt.delete"):
            table, _ = BT.delete_batch(table, keys, active=act,
                                       strategy=self.strategy)
        with OBS.span("pt.count"):       # a device sync, to count keys
            _note_probes(jnp.sum(act))
        return table

    # -- reads ----------------------------------------------------------

    def lookup_pages(self, table: BT.HashTable, seq_ids, positions, *,
                     page_size: int, max_pages: int) -> jnp.ndarray:
        """Wait-free block-table read: physical slot of every logical page
        of every sequence (-1 where absent/not-yet-needed).
        [B, max_pages]."""
        B = seq_ids.shape[0]
        logical = jnp.arange(max_pages, dtype=jnp.uint32)
        keys = page_key(seq_ids[:, None], logical[None, :]).reshape(-1)
        found, slots = BT.find_batch(table, keys, strategy=self.strategy)
        _note_probes(B * max_pages)
        slots = slots.reshape(B, max_pages)
        found = found.reshape(B, max_pages)
        live = logical[None, :] <= (positions[:, None] // page_size)
        return jnp.where(found & live, slots, -1)

    def rebuild_block_table(self, table: BT.HashTable, seq_ids,
                            max_pages: int, *,
                            use_kernel: bool = False) -> jnp.ndarray:
        """(Re)build block-table rows from the authoritative wait-free
        lookup — used on admission (a prefilled sequence brings pages with
        it), after a Section 4.3 ``rehash`` (every slot moved), and by the
        verification mode.  Unlike ``lookup_pages`` this caches every
        present page regardless of the current position — liveness is
        applied at read time by ``block_table_slots``.

        ``use_kernel=True`` serves the bulk lookup through the Pallas
        software-pipelined probe kernel (``kernels/probe``; unresolved tail
        falls back to the same ``BT.find_batch`` oracle in-graph) — bitwise
        the same rows, one VMEM-tiled sweep instead of B·max_pages gathers.
        The kernel assumes the linear probe order: for other strategies the
        request falls back to the jnp oracle, LOGGED (and surfaced by
        ``engine.fallback_report`` / the dryrun ``probe_strategy`` cell
        field — never silent)."""
        B = seq_ids.shape[0]
        logical = jnp.arange(max_pages, dtype=jnp.uint32)
        keys = page_key(seq_ids[:, None], logical[None, :]).reshape(-1)
        if use_kernel and not self._impl.kernel_supported:
            if not self._kernel_fallback_logged:
                logger.warning(
                    "probe kernel fallback: strategy %r is not supported "
                    "by the Pallas probe kernel (linear probe order); "
                    "serving rebuild_block_table from the jnp oracle",
                    self.strategy)
                self._kernel_fallback_logged = True
            use_kernel = False
        if use_kernel:
            from repro.kernels.probe import ops as PK
            found, slots = PK.probe_lookup(
                table, keys, interpret=not KN.on_tpu(),
                strategy=self.strategy)
        else:
            found, slots = BT.find_batch(table, keys,
                                         strategy=self.strategy)
        _note_probes(B * max_pages)
        return jnp.where(found, slots, -1).reshape(B, max_pages)

    @staticmethod
    def block_table_slots(block_table, positions, *,
                          page_size: int) -> jnp.ndarray:
        """The per-step block-table read, cache flavoured: same
        [B, max_pages] view as ``lookup_pages`` (-1 where absent/not-yet-
        needed) with ZERO probes — pure elementwise masking of the cached
        rows."""
        max_pages = block_table.shape[1]
        logical = jnp.arange(max_pages, dtype=jnp.int32)
        live = logical[None, :] <= (positions[:, None] // page_size)
        return jnp.where(live & (block_table >= 0), block_table, -1)

    @staticmethod
    def invalidate_block_rows(block_table, mask) -> jnp.ndarray:
        """Evict lanes from the cache: rows where ``mask`` is True become
        all -1.  MUST be called when a lane's sequence is evicted/freed —
        the slot's next occupant would otherwise read the reclaimed
        physical pages."""
        return jnp.where(jnp.asarray(mask, bool)[:, None],
                         jnp.int32(-1), block_table)

    def verify_block_table(self, table: BT.HashTable, seq_ids, positions,
                           block_table, *, page_size: int) -> jnp.ndarray:
        """CI-only verification mode: mismatch count between the
        incremental cache and the authoritative wait-free lookup (0 = cache
        coherent)."""
        max_pages = block_table.shape[1]
        ref = self.lookup_pages(table, seq_ids, positions,
                                page_size=page_size, max_pages=max_pages)
        got = self.block_table_slots(block_table, positions,
                                     page_size=page_size)
        return jnp.sum(got != ref)

    # -- accounting -----------------------------------------------------

    @staticmethod
    def stats(table: BT.HashTable) -> PageTableStats:
        return PageTableStats(live_pages=table.num_keys,
                              tombstones=table.num_tombs,
                              occupancy=BT.occupancy(table))

    def forecast_slack(self, n_pages: int) -> int:
        """Extra free cells the forecaster must hold for this strategy's
        no-ABORT guarantee (0 for linear/robinhood — Prop. 2 is exact)."""
        return self._impl.forecast_slack(n_pages)

    @staticmethod
    def probe_p99(table: BT.HashTable, q: float = 99.0) -> float:
        """Host-side probe-length percentile of the CURRENT pool: for every
        live key, its displacement from the hash slot (mod table size) — the
        linear-probe distance a lookup walks.  Eager/NumPy (pulls the cell
        array once); telemetry/report-path only, never inside jit."""
        tab = np.asarray(table.table)
        occ = (tab != BT.E.EMPTY) & (tab != BT.E.TOMBSTONE)
        idx = np.nonzero(occ)[0]
        if not idx.size:
            return 0.0
        hv = np.asarray(BT._hash(
            table, jnp.asarray(np.asarray(BT.E.dec_key(tab[idx]),
                                          np.uint32))))
        d = (idx - hv) % tab.shape[0]
        return float(np.percentile(d, q))

    def headroom(self, table: BT.HashTable) -> Headroom:
        """Synchronous (host) headroom read.  One device sync for the two
        counters — cheap next to the once-per-K-tokens megastep sync, and
        the proactive scheduler needs concrete numbers to decide
        evict/grow."""
        m = BT.size(table)
        live = int(table.num_keys)
        tombs = int(table.num_tombs)
        return Headroom(n_pages=m, live_pages=live, tombstones=tombs,
                        free_cells=m - live,
                        live_fraction=live / max(m, 1),
                        occupancy=(live + tombs) / max(m, 1),
                        strategy=self.strategy,
                        slack=self.forecast_slack(m))


@functools.lru_cache(maxsize=None)
def for_strategy(strategy: str = "linear") -> PageTable:
    """The shared per-strategy facade: one instance per strategy string, so
    jit sees stable bound methods and log-once fallback state persists
    across call sites (engine, batcher, benchmarks)."""
    return PageTable(strategy)
