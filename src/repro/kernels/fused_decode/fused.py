"""Pallas-TPU fused block-table-walk + paged-attention decode kernel.

One dispatch per decode token (per layer): for each (sequence, kv-head) lane
the kernel walks the *raw* incremental block table (scalar-prefetched int32
rows — the paper's wait-free lookup result, cached by
``page_table.alloc_step_incremental``), derives page liveness in-kernel
(``p·PS <= pos  and  bt[b,p] >= 0``), and computes flash-decoding attention
over exactly the live pages.  This absorbs the separate
``block_table_slots`` dispatch AND its HBM round trip (the two-dispatch
path materializes the masked slot view to HBM and re-reads it), and — the
structural win — it never DMAs a dead page: the baseline kernel's BlockSpec
index_map must clamp ``-1`` ids to page 0 and fetch anyway, so every
(sequence, head) pays ``MP`` page fetches regardless of length.

Page fetches are **double-buffered**: the async copy for page *i+1* is
issued before attention on page *i* starts computing (two VMEM buffer slots,
one DMA semaphore per slot per stream), so the table-walk/page-fetch latency
hides behind the dot products — SNIPPETS.md's ``Prefetch(hash)`` idiom
carried to the page pool.  Walking the table inside the kernel is safe
precisely because the paper's lookup is wait-free: a lookup never blocks and
never retries, so reading the block-table row at dispatch time is a
linearizable snapshot — there is no lock a stalled DMA could hold.

Grid: (B,) — the page loop is an in-kernel ``fori_loop`` (the pipeline
needs manual DMA control, so pages cannot be a grid dimension) whose trip
count is the lane's own ``pos // PS + 1`` slots, not the table's ``MP``.
Each DMA brings a whole page, every kv head of it ([PS, KH, D]), and a
static loop walks the heads: Mosaic refuses a one-head slice of the tiled
(KH, D) dims as a DMA source, and one copy per page moves the same bytes in
fewer descriptors.  The f32 online-softmax update replicates
``paged_attention._pa_kernel`` op for op (same ``dot_general`` shapes, same
masking, same reciprocal-multiply finish), so the fused kernel's normalized
output is **bitwise identical** to the two-dispatch baseline — asserted by
tests/test_kernel_fused.py.

``partials=True`` skips the normalization and emits the per-chip
``(acc, m, l)`` triple consumed by ``serving/paged.merge_global`` — the
shape the fully-manual decode region needs (pages sharded over (pod, data):
each chip walks its *local* block table and the lse merge crosses chips).

int8 KV pools ride along: per-(token, head) bf16 scale sidecars are fetched
through the same double-buffered pipeline and dequantized in f32 before the
dot product, matching the (extended) baseline kernel's op order exactly.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _fused_kernel(bt_ref, pos_ref, layer_ref,  # scalar prefetch [B,MP],
                                                #   [B], [1]
                  q_ref,                      # [1, KH, G, D]
                  k_hbm, v_hbm,               # ANY [L, NP, PS, KH, D]
                  *rest,
                  PS: int, KH: int, G: int, D: int, MP: int, NP: int,
                  quantized: bool, partials: bool):
    if quantized:
        ks_hbm, vs_hbm = rest[:2]
        rest = rest[2:]
    if partials:
        o_ref, m_ref, l_ref = rest[:3]
        scratch = rest[3:]
    else:
        o_ref = rest[0]
        scratch = rest[1:]
    if quantized:
        kb, vb, ksb, vsb, sem, m_scr, l_scr, acc_scr = scratch
    else:
        kb, vb, sem, m_scr, l_scr, acc_scr = scratch

    b = pl.program_id(0)
    pos = pos_ref[b]
    layer = layer_ref[0]
    # the walk ends at the page that holds ``pos``: no later slot can hold
    # a live token, so the loop's trip count follows the lane's length
    n = jnp.minimum(pos // PS + 1, MP)

    def need(p):
        """Page p contributes at least one valid token — the ONLY pages the
        kernel fetches (the two-dispatch baseline DMAs all MP).  The table
        read is clamped so that the prefetch test of the last slot stays in
        bounds."""
        return (p * PS <= pos) & (bt_ref[b, jnp.minimum(p, MP - 1)] >= 0)

    def copies(pid, slot):
        """The async copies of page ``pid`` (every kv head) into ``slot``."""
        cps = [pltpu.make_async_copy(k_hbm.at[layer, pid], kb.at[slot],
                                     sem.at[slot, 0]),
               pltpu.make_async_copy(v_hbm.at[layer, pid], vb.at[slot],
                                     sem.at[slot, 1])]
        if quantized:
            cps += [pltpu.make_async_copy(ks_hbm.at[layer, pid],
                                          ksb.at[slot], sem.at[slot, 2]),
                    pltpu.make_async_copy(vs_hbm.at[layer, pid],
                                          vsb.at[slot], sem.at[slot, 3])]
        return cps

    def start(p, slot):
        pid = jnp.clip(bt_ref[b, p], 0, NP - 1)   # clamp: address only
        for cp in copies(pid, slot):
            cp.start()

    def wait(slot):
        for cp in copies(0, slot):
            cp.wait()

    m_scr[...] = jnp.full_like(m_scr, NEG_INF)
    l_scr[...] = jnp.zeros_like(l_scr)
    acc_scr[...] = jnp.zeros_like(acc_scr)

    # software pipeline: warm-up fetch for page 0, then each iteration
    # issues page p+1's copy BEFORE waiting on / computing page p
    @pl.when(need(0))
    def _warmup():
        start(0, 0)

    def body(p, _):
        slot = jax.lax.rem(p, 2)

        @pl.when((p + 1 < n) & need(p + 1))
        def _prefetch_next():
            start(p + 1, 1 - slot)

        @pl.when(need(p))
        def _attend():
            wait(slot)
            tok = p * PS + jax.lax.broadcasted_iota(jnp.int32, (PS,), 0)
            valid = tok <= pos
            for h in range(KH):                    # static: every head
                # --- identical f32 op order to paged_attention._pa_kernel
                q = q_ref[0, h].astype(jnp.float32)            # [G, D]
                k = kb[slot, :, h].astype(jnp.float32)         # [PS, D]
                v = vb[slot, :, h].astype(jnp.float32)
                if quantized:
                    k = k * ksb[slot, :, h].astype(jnp.float32)[:, None]
                    v = v * vsb[slot, :, h].astype(jnp.float32)[:, None]
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

        return 0

    jax.lax.fori_loop(0, n, body, 0)

    for h in range(KH):
        if partials:
            o_ref[0, h] = acc_scr[h]
            m_ref[0, h] = m_scr[h][:, 0]
            l_ref[0, h] = l_scr[h][:, 0]
        else:
            l = l_scr[h][:, 0]
            norm = jnp.where(l > 0, 1.0 / jnp.maximum(l, 1e-30), 0.0)
            o_ref[0, h] = (acc_scr[h] * norm[:, None]).astype(o_ref.dtype)


def fused_decode_kernel(q, k_pages, v_pages, block_table, positions, *,
                        layer=None, scales=None, partials: bool = False,
                        interpret: bool = False):
    """q [B,QH,D]; pools [NP,PS,KH,D], or [L,NP,PS,KH,D] stacked over
    layers with ``layer`` (int32 scalar) naming the one attended — the
    kernel DMAs pages straight out of the stack, so no per-layer pool
    slice is ever built; block_table int32[B,MP] RAW
    incremental cache rows (-1 absent — liveness is derived in-kernel from
    ``positions``, NOT pre-masked); positions int32[B] current decode
    position (attends tokens <= positions[b]).  ``scales``: optional
    (k_scales, v_scales) [(L,)NP,PS,KH] bf16 sidecars for int8 pools.

    Returns [B,QH,D] (q.dtype), or with ``partials=True`` the unnormalized
    per-chip triple (o f32 [B,KH,G,D], m f32 [B,KH,G], l f32 [B,KH,G])."""
    if k_pages.ndim == 4:                     # one layer: a stack of one
        k_pages, v_pages = k_pages[None], v_pages[None]
        if scales is not None:
            scales = (scales[0][None], scales[1][None])
        layer = 0
    B, QH, D = q.shape
    _, NP, PS, KH, _ = k_pages.shape
    MP = block_table.shape[1]
    assert QH % KH == 0
    G = QH // KH
    q4 = q.reshape(B, KH, G, D)
    quantized = scales is not None

    qmap = lambda b, bt, pos, li: (b, 0, 0, 0)
    in_specs = [
        pl.BlockSpec((1, KH, G, D), qmap),
        pl.BlockSpec(memory_space=pl.ANY),
        pl.BlockSpec(memory_space=pl.ANY),
    ]
    operands = [q4, k_pages, v_pages]
    if quantized:
        in_specs += [pl.BlockSpec(memory_space=pl.ANY)] * 2
        operands += [scales[0], scales[1]]

    if partials:
        out_specs = [pl.BlockSpec((1, KH, G, D), qmap),
                     pl.BlockSpec((1, KH, G), lambda b, *_: (b, 0, 0)),
                     pl.BlockSpec((1, KH, G), lambda b, *_: (b, 0, 0))]
        out_shape = [jax.ShapeDtypeStruct((B, KH, G, D), jnp.float32),
                     jax.ShapeDtypeStruct((B, KH, G), jnp.float32),
                     jax.ShapeDtypeStruct((B, KH, G), jnp.float32)]
    else:
        out_specs = pl.BlockSpec((1, KH, G, D), qmap)
        out_shape = jax.ShapeDtypeStruct((B, KH, G, D), q.dtype)

    scratch = [pltpu.VMEM((2, PS, KH, D), k_pages.dtype),
               pltpu.VMEM((2, PS, KH, D), v_pages.dtype)]
    n_streams = 2
    if quantized:
        scratch += [pltpu.VMEM((2, PS, KH), scales[0].dtype),
                    pltpu.VMEM((2, PS, KH), scales[1].dtype)]
        n_streams = 4
    scratch += [pltpu.SemaphoreType.DMA((2, n_streams)),
                pltpu.VMEM((KH, G, 1), jnp.float32),
                pltpu.VMEM((KH, G, 1), jnp.float32),
                pltpu.VMEM((KH, G, D), jnp.float32)]

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(B,),
        in_specs=in_specs,
        out_specs=out_specs,
        scratch_shapes=scratch,
    )
    kernel = functools.partial(_fused_kernel, PS=PS, KH=KH, G=G, D=D, MP=MP,
                               NP=NP, quantized=quantized, partials=partials)
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=out_shape,
        interpret=interpret,
    )(block_table.astype(jnp.int32), positions.astype(jnp.int32),
      jnp.asarray(layer, jnp.int32).reshape(1), *operands)
    if partials:
        return out[0], out[1], out[2]
    return out.reshape(B, QH, D)
