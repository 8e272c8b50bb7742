"""Pallas-TPU paged-attention decode kernel (flash-decoding over pages).

The physical-page indirection comes from the lock-free page table
(serving/page_table.py): the hash-table *slot* of key (seq, logical_page) IS
the physical page index, so the pool is addressed through scalar-prefetched
``page_ids`` feeding the K/V BlockSpec index_maps — one DMA per (seq,
kv-head, page) grid step, online-softmax accumulation in VMEM scratch.

Grid: (B, MP), MP innermost (sequential on TPU; scratch persists across
the page loop).  Block shapes: q [1,KH,G,D], K/V [1,PS,KH,D] selected by
page_ids[b,p] — one DMA brings a whole page (every kv head), and the kernel
walks the heads in a static loop.  A one-head block [1,PS,1,D] would break
Mosaic's rule that a block's last two dims divide by (8, 128) or equal the
array's; the whole-page block equals them.  Each head's f32 op sequence is
the same as a per-head grid step, so results do not change.

Pages past ``lens[b]`` or with id -1 are masked (index_map clamps to page 0;
the mask keeps the math exact).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _pa_kernel(page_ids_ref, lens_ref,      # scalar prefetch [B,MP], [B]
               q_ref,                        # [1, KH, G, D]
               k_ref,                        # [1, PS, KH, D]
               v_ref,                        # [1, PS, KH, D]
               *rest,                        # [ks_ref, vs_ref,] o_ref, scratch
               PS: int, KH: int, G: int, D: int, MP: int,
               quantized: bool = False):
    if quantized:                            # int8 pools: [1, PS, KH] bf16
        ks_ref, vs_ref, o_ref, m_scr, l_scr, acc_scr = rest
    else:
        o_ref, m_scr, l_scr, acc_scr = rest
    b = pl.program_id(0)
    p = pl.program_id(1)

    @pl.when(p == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    length = lens_ref[b]
    pid = page_ids_ref[b, p]
    base = p * PS
    tok = base + jax.lax.broadcasted_iota(jnp.int32, (PS,), 0)
    valid = (tok < length) & (pid >= 0)

    @pl.when(jnp.any(valid))
    def _step():
        for h in range(KH):                  # static: one page, every head
            q = q_ref[0, h].astype(jnp.float32)            # [G, D]
            k = k_ref[0, :, h].astype(jnp.float32)         # [PS, D]
            v = v_ref[0, :, h].astype(jnp.float32)         # [PS, D]
            if quantized:                                  # dequant in f32
                k = k * ks_ref[0, :, h].astype(jnp.float32)[:, None]
                v = v * vs_ref[0, :, h].astype(jnp.float32)[:, None]
            s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                    preferred_element_type=jnp.float32)
            s = s * (D ** -0.5)                            # [G, PS]
            s = jnp.where(valid[None, :], s, NEG_INF)
            m_prev = m_scr[h][:, 0]                        # [G]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=1))
            alpha = jnp.exp(m_prev - m_new)                # [G]
            pexp = jnp.exp(s - m_new[:, None])             # [G, PS]
            pexp = jnp.where(valid[None, :], pexp, 0.0)
            l_new = l_scr[h][:, 0] * alpha + jnp.sum(pexp, axis=1)
            acc = acc_scr[h] * alpha[:, None] + jax.lax.dot_general(
                pexp, v, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            m_scr[h] = m_new[:, None]
            l_scr[h] = l_new[:, None]
            acc_scr[h] = acc

    @pl.when(p == MP - 1)
    def _finish():
        for h in range(KH):
            l = l_scr[h][:, 0]
            norm = jnp.where(l > 0, 1.0 / jnp.maximum(l, 1e-30), 0.0)
            o_ref[0, h] = (acc_scr[h] * norm[:, None]).astype(o_ref.dtype)


def paged_attention_kernel(q, k_pages, v_pages, page_ids, lens, *,
                           scales=None, interpret: bool = False):
    """q [B,QH,D]; pools [NP,PS,KH,D]; page_ids int32[B,MP]; lens int32[B];
    ``scales``: optional (k_scales, v_scales) [NP,PS,KH] bf16 sidecars for
    int8 pools (dequantized in f32 inside the kernel).  Returns [B,QH,D]."""
    B, QH, D = q.shape
    NP, PS, KH, _ = k_pages.shape
    MP = page_ids.shape[1]
    assert QH % KH == 0
    G = QH // KH
    q4 = q.reshape(B, KH, G, D)
    quantized = scales is not None

    def _kv_map(b, p, ids, ln):
        # clamp only for addressing; the kernel masks on the raw -1 sentinel
        return (jnp.clip(ids[b, p], 0, NP - 1), 0, 0, 0)

    def _sc_map(b, p, ids, ln):
        return (jnp.clip(ids[b, p], 0, NP - 1), 0, 0)

    in_specs = [
        pl.BlockSpec((1, KH, G, D), lambda b, p, ids, ln: (b, 0, 0, 0)),
        pl.BlockSpec((1, PS, KH, D), _kv_map),
        pl.BlockSpec((1, PS, KH, D), _kv_map),
    ]
    operands = [q4, k_pages, v_pages]
    if quantized:
        in_specs += [pl.BlockSpec((1, PS, KH), _sc_map)] * 2
        operands += [scales[0], scales[1]]

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, MP),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, KH, G, D),
                               lambda b, p, ids, ln: (b, 0, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((KH, G, 1), jnp.float32),
            pltpu.VMEM((KH, G, 1), jnp.float32),
            pltpu.VMEM((KH, G, D), jnp.float32),
        ],
    )
    kernel = functools.partial(_pa_kernel, PS=PS, KH=KH, G=G, D=D, MP=MP,
                               quantized=quantized)
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, KH, G, D), q.dtype),
        interpret=interpret,
    )(page_ids.astype(jnp.int32), lens.astype(jnp.int32), *operands)
    return out.reshape(B, QH, D)
