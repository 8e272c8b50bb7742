"""Pallas-TPU wait-free probe-lookup kernel (software-pipelined).

TPU adaptation of the paper's lookup path (DESIGN.md §2): sequential linear
probing touches one cache line per lookup; the TPU analog is one *VMEM tile*
per lookup batch.  Keys are pre-sorted by hash (in the XLA wrapper, ops.py),
so a tile of KT consecutive keys probes a narrow, contiguous region of the
table.  For each key tile the kernel stages **two consecutive table blocks**
(TB cells each) HBM→VMEM — the block containing the tile's first hash
position and its successor.

The staging is a two-stage prefetch-ahead pipeline (Maier & Sanders: memory
latency, not instruction count, dominates open-addressing probes — exactly
what software pipelining hides): the table lives in HBM (``memory_space=
ANY``) and the kernel issues the async copies for tile *t+1*'s window
BEFORE probing tile *t*'s resident window, double-buffering two window
slots with one DMA semaphore per (slot, block).  The grid is sequential on
TPU, so slot ``t % 2`` is always started at step ``t-1`` (or the step-0
warm-up) and waited exactly once at step ``t`` — by then the copy has had a
full tile of probe compute to complete.  This replaces the previous
two-block-window BlockSpec design (where the pipeline depth was whatever
the Mosaic scheduler chose) with explicit prefetch-ahead reads.

Each key then scans its probe window with vector compares out of VMEM.  TPU
constraint honored: dynamic slicing happens only on the *sublane* dimension
(the table lives in VMEM as [rows, 128] lanes); the intra-row offset is
handled by masking lanes before the first probe position instead of shifting
— no lane-dimension dynamic indexing.  Effective probe window per key:
129..256 cells (two 128-lane rows minus the lane offset).

Keys whose run extends past the resident window are reported *unresolved*
and fall back to the jnp oracle — at load factor 1-1/x the expected run
length is O(x^2) << 128 (Knuth / Theorem 21), so the fast path covers the
overwhelming majority; this mirrors the paper's expected-amortized-cost
structure.  Lookups remain wait-free: no writes, no data-dependent retries.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import encoding as E

LANES = 128
DEFAULT_TB = 2048   # table block (cells) resident in VMEM per tile
DEFAULT_KT = 128    # keys per tile
BIG = 1 << 30  # python int: inlined as an immediate, not a captured const


def _probe_kernel(bstart_ref,            # scalar prefetch: int32[nt]
                  keys_ref,              # uint32[1, KT] SMEM
                  hv_ref,                # int32[1, KT] SMEM
                  tab_hbm,               # uint32[nb*TB//128, 128] HBM (ANY)
                  found_ref,             # int32[1, KT] SMEM
                  slot_ref,              # int32[1, KT] SMEM
                  resolved_ref,          # int32[1, KT] SMEM
                  win_ref,               # uint32[2, 2*TB//128, 128] VMEM
                  sem,                   # DMA sem (2 slots, 2 blocks)
                  *, TB: int, KT: int, m: int):
    t = pl.program_id(0)
    nt = pl.num_programs(0)
    rpb = TB // LANES                              # rows per table block
    total_rows = 2 * rpb
    nb = m // TB

    def start(tile, slot):
        """Issue the two async block copies for ``tile``'s window into
        window slot ``slot`` (block b and its wrap-around successor)."""
        b0 = bstart_ref[tile]
        b1 = jax.lax.rem(b0 + 1, nb)
        pltpu.make_async_copy(tab_hbm.at[pl.ds(b0 * rpb, rpb), :],
                              win_ref.at[slot, pl.ds(0, rpb), :],
                              sem.at[slot, 0]).start()
        pltpu.make_async_copy(tab_hbm.at[pl.ds(b1 * rpb, rpb), :],
                              win_ref.at[slot, pl.ds(rpb, rpb), :],
                              sem.at[slot, 1]).start()

    # two-stage pipeline: warm-up fetch for tile 0; thereafter tile t issues
    # tile t+1's copies BEFORE waiting on (then probing) its own window
    @pl.when(t == 0)
    def _warmup():
        start(0, 0)

    @pl.when(t + 1 < nt)
    def _prefetch_next():
        start(t + 1, jax.lax.rem(t + 1, 2))

    slot = jax.lax.rem(t, 2)
    pltpu.make_async_copy(tab_hbm.at[pl.ds(0, rpb), :],
                          win_ref.at[slot, pl.ds(0, rpb), :],
                          sem.at[slot, 0]).wait()
    pltpu.make_async_copy(tab_hbm.at[pl.ds(0, rpb), :],
                          win_ref.at[slot, pl.ds(rpb, rpb), :],
                          sem.at[slot, 1]).wait()

    base = bstart_ref[t] * TB
    lane = jax.lax.broadcasted_iota(jnp.int32, (2, LANES), 1)
    rowi = jax.lax.broadcasted_iota(jnp.int32, (2, LANES), 0)
    lin = rowi * LANES + lane                      # probe-order linear index

    def body(k, _):
        key = keys_ref[0, k]
        hv = hv_ref[0, k]
        off = hv - base                            # >= 0 (keys sorted)
        in_window = off < 2 * TB - LANES           # else: unresolved
        row = jnp.clip(off // LANES, 0, total_rows - 2)
        win = win_ref[slot, pl.ds(row, 2), :]      # [2, 128]
        # probe positions >= hv only
        gpos = row * LANES + lin                   # position within 2 blocks
        valid = gpos >= off
        target = (key << 2) | jnp.uint32(E.TAG_FINAL)
        hit = (win == target) & valid
        empty = (win == jnp.uint32(E.EMPTY)) & valid
        first_hit = jnp.min(jnp.where(hit, lin, BIG))
        first_empty = jnp.min(jnp.where(empty, lin, BIG))
        found = (first_hit < first_empty) & in_window
        done = ((first_hit < BIG) | (first_empty < BIG)) & in_window
        pos = base + row * LANES + first_hit
        pos = jnp.where(pos >= m, pos - m, pos)    # wrap (nb*TB == m)
        found_ref[0, k] = found.astype(jnp.int32)
        slot_ref[0, k] = jnp.where(found, pos, -1)
        resolved_ref[0, k] = done.astype(jnp.int32)
        return 0

    jax.lax.fori_loop(0, KT, body, 0)


def probe_lookup_kernel(table, keys_sorted, hv_sorted, bstart, *,
                        TB: int = DEFAULT_TB, KT: int = DEFAULT_KT,
                        interpret: bool = False):
    """Launch over nt = len(keys)//KT tiles.

    table: uint32[m] with m % TB == 0 and m // TB >= 2 (wrap-safe).
    keys_sorted/hv_sorted: uint32/int32 [nt*KT] sorted by hv.
    bstart: int32[nt] = hv of each tile's first key // TB.
    Returns (found int32[nt*KT], slot int32[nt*KT], resolved int32[nt*KT]).
    """
    m = table.shape[0]
    assert m % TB == 0 and m // TB >= 2, (m, TB)
    nb = m // TB
    nt = keys_sorted.shape[0] // KT
    assert keys_sorted.shape[0] == nt * KT

    table2d = table.reshape(nb * (TB // LANES), LANES)
    # per-key operands live in SMEM (the probe loop reads and writes one
    # scalar per key); the unit middle dim makes each (1, KT) tile equal
    # the array's last two dims, as Mosaic requires of block shapes
    keys3d = keys_sorted.reshape(nt, 1, KT)
    hv3d = hv_sorted.reshape(nt, 1, KT)

    def tile():
        return pl.BlockSpec((None, 1, KT), lambda t, s: (t, 0, 0),
                            memory_space=pltpu.SMEM)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(nt,),
        in_specs=[tile(), tile(),
                  pl.BlockSpec(memory_space=pl.ANY)],  # table in HBM
        out_specs=[tile(), tile(), tile()],
        scratch_shapes=[
            pltpu.VMEM((2, 2 * (TB // LANES), LANES), jnp.uint32),
            pltpu.SemaphoreType.DMA((2, 2)),
        ],
    )
    kernel = functools.partial(_probe_kernel, TB=TB, KT=KT, m=m)
    found, slot, resolved = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((nt, 1, KT), jnp.int32),
            jax.ShapeDtypeStruct((nt, 1, KT), jnp.int32),
            jax.ShapeDtypeStruct((nt, 1, KT), jnp.int32),
        ],
        interpret=interpret,
    )(bstart, keys3d, hv3d, table2d)
    return found.reshape(-1), slot.reshape(-1), resolved.reshape(-1)
