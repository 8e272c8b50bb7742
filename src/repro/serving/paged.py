"""Paged flash-decoding attention over the hash-table page pool.

Layout: the physical page pool [n_pages, page_size, n_kv, hd] is sharded on
the page dim across ALL mesh axes (pod·data·model chips), so each chip holds
``npr = n_pages / n_chips`` pages.  The hash allocator (serving/page_table)
spreads a sequence's pages ~uniformly over chips, so per-decode-step KV
bandwidth per chip ≈ total-KV / n_chips — the flash-decoding ideal — and the
"block table" consulted every step is the paper's wait-free lookup.

Per chip, pages of *all* sequences are compacted into one [CAP] list (jointly
over (seq, page) — per-seq capacity would waste ~8x gather bandwidth at high
chip counts), attended against their owning sequence's query, then merged:
log-sum-exp scatter within the chip, lse-weighted psum across chips.

All functions here execute INSIDE shard_map (or standalone when mesh=None —
the single-chip oracle used by tests).
"""
from __future__ import annotations

import math
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

NEG_INF = -1e30


class PagedPools(NamedTuple):
    k: jnp.ndarray   # [L, n_pages, page_size, n_kv, hd]
    v: jnp.ndarray


class PoolScales(NamedTuple):
    """Per-(page, token, head) dequant scales for int8 KV pools (§Perf:
    KIVI-style per-token quantization — 2x pool bandwidth and memory for
    <1% logits error; scales are hd-times smaller than the pools)."""
    k: jnp.ndarray   # bf16 [L, n_pages, page_size, n_kv]
    v: jnp.ndarray


def round_pages(n: int, n_chips: int) -> int:
    return max(1, -(-n // n_chips)) * n_chips


def make_pools(num_layers: int, n_pages: int, page_size: int, n_kv: int,
               hd: int, dtype) -> PagedPools:
    shp = (num_layers, n_pages, page_size, n_kv, hd)
    return PagedPools(k=jnp.zeros(shp, dtype), v=jnp.zeros(shp, dtype))


def make_pool_scales(num_layers: int, n_pages: int, page_size: int,
                     n_kv: int) -> PoolScales:
    shp = (num_layers, n_pages, page_size, n_kv)
    return PoolScales(k=jnp.ones(shp, jnp.bfloat16),
                      v=jnp.ones(shp, jnp.bfloat16))


def quantize_kv(x):
    """x [B, n_kv, hd] -> (int8 values, bf16 scales [B, n_kv])."""
    s = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=-1) / 127.0
    s = jnp.maximum(s, 1e-8)
    q = jnp.clip(jnp.round(x.astype(jnp.float32) / s[..., None]),
                 -127, 127).astype(jnp.int8)
    return q, s.astype(jnp.bfloat16)


POOL_AXES = ("layer", "pages", None, None, None)
POOL_SCALE_AXES = ("layer", "pages", None, None)

# Fused manual-TP decode layout (serve_manual_rules): pages over (pod, data)
# only, KV *heads* over model — each model-axis chip keeps its head slice of
# every page it owns, so attention runs end-to-end on local heads with no
# cross-model K/V gather (serving/engine._make_manual_serve_step).  When the
# model axis is wider than n_kv, the pool head dim is physically TILED to
# n_kv·rep (dist/tp.decode_kv_rep) so the same "kv" mapping divides: each
# chip keeps exactly one (replicated) resident head, and the rep copies stay
# bitwise identical because every owning chip writes its own copy from the
# same replicated inputs.
POOL_AXES_TP = ("layer", "pages", None, "kv", None)
POOL_SCALE_AXES_TP = ("layer", "pages", None, "kv")


class LocalPages(NamedTuple):
    """Per-chip compacted page list (precomputed once per serve step)."""
    rows: jnp.ndarray    # int32[CAP] local pool row (clamped)
    seq: jnp.ndarray     # int32[CAP] owning sequence (B = trash)
    page: jnp.ndarray    # int32[CAP] logical page id
    valid: jnp.ndarray   # bool[CAP]


def compact_local(slots: jnp.ndarray, chip_idx, npr: int,
                  cap: int) -> LocalPages:
    """slots int32[B, maxP] global physical slots (-1 absent).  Select the
    pages this chip owns and compact them into [cap] entries."""
    B, maxP = slots.shape
    flat = slots.reshape(-1)
    mine = (flat >= 0) & (flat // npr == chip_idx)
    pos = jnp.cumsum(mine.astype(jnp.int32)) - 1
    keep = mine & (pos < cap)
    dst = jnp.where(keep, pos, cap)                  # cap = trash slot
    rows = jnp.zeros((cap + 1,), jnp.int32).at[dst].set(
        jnp.where(keep, flat % npr, 0))
    seq = jnp.full((cap + 1,), B, jnp.int32).at[dst].set(
        jnp.where(keep, jnp.arange(B * maxP) // maxP, B))
    page = jnp.zeros((cap + 1,), jnp.int32).at[dst].set(
        jnp.where(keep, jnp.arange(B * maxP) % maxP, 0))
    valid = jnp.zeros((cap + 1,), bool).at[dst].set(keep)
    return LocalPages(rows=rows[:cap], seq=jnp.where(valid[:cap], seq[:cap], B),
                      page=page[:cap], valid=valid[:cap])


def write_token_kv(pools: PagedPools, scales: Optional[PoolScales], k_new,
                   v_new, write_slot, positions, chip_idx, npr: int,
                   page_size: int, layer):
    """Write one token's K/V [B, n_kv, hd] of attention layer ``layer``
    into the page each sequence's current position maps to (only on the
    owning chip).  RoPE is applied by the caller BEFORE the write (cache
    stores rotated keys).  The pools stay stacked over layers ([L, npr, PS,
    kv, hd]) and the write is one small scatter into them: slicing a
    layer's pool out and stacking it back would copy the whole pool every
    step.  With int8 pools, ``scales`` holds the per-token sidecars.

    ``write_slot = -1`` is the allocator's ABORT/refusal sentinel
    (page_table.AllocStep): such lanes MUST NOT scatter — the clamp below
    routes them to the dropped row, so a -1 can never wrap (Python-style)
    into the last physical page and corrupt another sequence's KV."""
    mine = (write_slot >= 0) & (write_slot // npr == chip_idx)
    rows = jnp.where(mine, jnp.clip(write_slot, 0) % npr, npr)  # npr -> drop
    offs = positions % page_size

    def put(pool, val):
        return pool.at[layer, rows, offs].set(val.astype(pool.dtype),
                                              mode="drop")

    if pools.k.dtype == jnp.int8:
        k_q, k_s = quantize_kv(k_new)
        v_q, v_s = quantize_kv(v_new)
        return (PagedPools(k=put(pools.k, k_q), v=put(pools.v, v_q)),
                PoolScales(k=put(scales.k, k_s), v=put(scales.v, v_s)))
    return PagedPools(k=put(pools.k, k_new), v=put(pools.v, v_new)), scales


def attend_local(q_all, pools: PagedPools, scales: Optional[PoolScales],
                 layer, lp: LocalPages, positions, page_size: int
                 ) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Per-chip partial attention of attention layer ``layer``.

    q_all [B, n_kv, G, hd] (grouped query, full batch); pools [L, npr,
    psize, n_kv, hd]; positions [B] current decode position per sequence.
    Returns per-sequence partials (o [B,kv,G,hd] f32, m [B,kv,G], l [B,kv,G])
    ready for cross-chip lse merge."""
    B = q_all.shape[0]
    _, _, psize, n_kv, hd = pools.k.shape
    scale = 1.0 / math.sqrt(hd)

    # one gather indexed by (layer, row): no per-layer pool slice is built
    k_loc = pools.k[layer, lp.rows]                   # [CAP, psize, kv, hd]
    v_loc = pools.v[layer, lp.rows]
    if pools.k.dtype == jnp.int8:
        k_loc = (k_loc.astype(jnp.float32)
                 * scales.k[layer, lp.rows].astype(jnp.float32)[..., None])
        v_loc = (v_loc.astype(jnp.float32)
                 * scales.v[layer, lp.rows].astype(jnp.float32)[..., None])
    seq_c = jnp.minimum(lp.seq, B - 1)
    q_pages = q_all[seq_c]                            # [CAP, kv, G, hd]
    s = jnp.einsum("ckgd,cskd->ckgs", q_pages.astype(jnp.float32),
                   k_loc.astype(jnp.float32)) * scale
    tpos = lp.page[:, None] * page_size + jnp.arange(psize)[None, :]
    ok = lp.valid[:, None] & (tpos <= positions[seq_c][:, None])
    s = jnp.where(ok[:, None, None, :], s, NEG_INF)   # [CAP,kv,G,psize]
    m_p = jnp.max(s, axis=-1)                         # [CAP,kv,G]
    p = jnp.where(ok[:, None, None, :], jnp.exp(s - m_p[..., None]), 0.0)
    l_p = jnp.sum(p, axis=-1)
    o_p = jnp.einsum("ckgs,cskd->ckgd", p, v_loc.astype(jnp.float32))

    # within-chip per-sequence lse merge (scatter-max then weighted adds)
    seq_i = lp.seq                                    # B = trash row
    m_seq = jnp.full((B + 1,) + m_p.shape[1:], NEG_INF).at[seq_i].max(m_p)
    w = jnp.where(lp.valid[:, None, None],
                  jnp.exp(m_p - m_seq[seq_c]), 0.0)
    l_seq = jnp.zeros((B + 1,) + l_p.shape[1:]).at[seq_i].add(l_p * w)
    o_seq = jnp.zeros((B + 1,) + o_p.shape[1:]).at[seq_i].add(
        o_p * w[..., None])
    return o_seq[:B], m_seq[:B], l_seq[:B]


def merge_global(o, m, l, axis_names) -> jnp.ndarray:
    """lse-weighted cross-chip merge.  axis_names=() -> single chip.
    The o partial psums in bf16 (§Perf: halves per-layer merge wire; m/l
    stay f32 — they are hd-times smaller)."""
    if axis_names:
        m_g = jax.lax.pmax(m, axis_names)
        w = jnp.exp(m - m_g)
        o = jax.lax.psum((o * w[..., None]).astype(jnp.bfloat16),
                         axis_names).astype(jnp.float32)
        l = jax.lax.psum(l * w, axis_names)
    return o / jnp.maximum(l, 1e-20)[..., None]


def capacity(B: int, maxP: int, n_chips: int,
             factor: float = 2.0) -> int:
    """Per-chip compacted-page capacity: ``factor``x the uniform share (+8
    slack), rounded to 8.  The hash allocator spreads pages ~uniformly
    (binomial tails), so overflow is negligible even at 1.3x (§Perf run);
    overflowed pages are dropped from attention and surface as a quality
    regression, never a crash (monitored via LocalPages.valid counts)."""
    mean = B * maxP / n_chips
    cap = int(mean * factor) + 8
    return min(B * maxP, -(-cap // 8) * 8)
