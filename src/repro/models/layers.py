"""Shared model layers: RoPE / M-RoPE, GQA flash attention (chunked
online-softmax in pure JAX — the XLA-level flash formulation), SwiGLU MLP,
and the standard pre-norm transformer block.

Attention is O(S·window) / O(S²/2) in both memory and FLOPs: the query-chunk
scan's inner kv loop runs only over the chunks a query chunk can attend to
(causal triangle / sliding window), so the dry-run cost analysis reports the
true compute, not a dense S×S rectangle.  GQA is computed in grouped form
(q reshaped to [B,S,kv,group,hd]) — repeated KV is never materialized.
"""
from __future__ import annotations

import functools
import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.models import nn

DEFAULT_Q_CHUNK = 512
DEFAULT_KV_CHUNK = 1024
NEG_INF = -1e30


# ---------------------------------------------------------------------------
# RoPE.

def _rope_angles(positions, dims: int, theta: float):
    """positions [...] -> (sin, cos) [..., dims//2]."""
    half = dims // 2
    freq = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    ang = positions.astype(jnp.float32)[..., None] * freq
    return jnp.sin(ang), jnp.cos(ang)


def apply_rope(x, positions, theta: float):
    """x [B,S,H,hd], positions [B,S] (or [S]) -> rotated x."""
    B, S, H, hd = x.shape
    if positions.ndim == 1:
        positions = jnp.broadcast_to(positions[None], (B, S))
    sin, cos = _rope_angles(positions, hd, theta)       # [B,S,hd/2]
    sin = sin[:, :, None, :]
    cos = cos[:, :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)
    return out.astype(x.dtype)


def apply_mrope(x, positions3, sections: Tuple[int, ...], theta: float):
    """Qwen2-VL M-RoPE: positions3 [3,B,S] (t,h,w); rotary dims split into
    ``sections`` (sum == hd//2); section s rotates with positions3[s]."""
    B, S, H, hd = x.shape
    half = hd // 2
    assert sum(sections) == half, (sections, half)
    freq = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    # per-dim section id -> choose position stream
    sec_id = jnp.repeat(jnp.arange(len(sections)),
                        jnp.array(sections), total_repeat_length=half)
    pos = positions3.astype(jnp.float32)                # [3,B,S]
    pos_per_dim = pos[sec_id, :, :]                     # [half,B,S]
    ang = jnp.einsum("dbs,d->bsd", pos_per_dim, freq)   # [B,S,half]
    sin, cos = jnp.sin(ang)[:, :, None, :], jnp.cos(ang)[:, :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# Flash attention (XLA-level chunked online softmax).

def _attend_chunk(q, k, v, qpos, kpos, kv_valid, *, causal, window, scale):
    """q [B,kv,G,Cq,hd]; k/v [B,kv,Ck,hd]; qpos [Cq]; kpos [Ck];
    kv_valid [Ck] (padding mask).
    Returns (scores-applied partial o [B,kv,G,Cq,hd], m, l)."""
    s = jnp.einsum("bkgqd,bkcd->bkgqc", q, k,
                   preferred_element_type=jnp.float32) * scale
    mask = jnp.broadcast_to(kv_valid[None, :],
                            (qpos.shape[0], kpos.shape[0]))
    if causal:
        mask &= kpos[None, :] <= qpos[:, None]
    if window:
        mask &= kpos[None, :] > qpos[:, None] - window
    s = jnp.where(mask[None, None, None], s, NEG_INF)
    m = jnp.max(s, axis=-1)                              # [B,kv,G,Cq]
    p = jnp.exp(s - m[..., None])
    p = jnp.where(mask[None, None, None], p, 0.0)
    l = jnp.sum(p, axis=-1)
    o = jnp.einsum("bkgqc,bkcd->bkgqd", p, v.astype(jnp.float32))
    return o, m, l


def _grouped(q, k, v, Cq, Ck):
    """Reshape to chunked grouped layout.
    q -> [nq,B,kv,G,Cq,hd]; k/v -> [nk,B,kv,Ck,hd]."""
    B, Sq, Hq, hd = q.shape
    _, Sk, Hkv, _ = k.shape
    G = Hq // Hkv
    nq, nk = Sq // Cq, Sk // Ck
    qg = q.reshape(B, nq, Cq, Hkv, G, hd).transpose(1, 0, 3, 4, 2, 5)
    kg = k.reshape(B, nk, Ck, Hkv, hd).transpose(1, 0, 3, 2, 4)
    vg = v.reshape(B, nk, Ck, Hkv, hd).transpose(1, 0, 3, 2, 4)
    return qg, kg, vg, G, nq, nk


def _kv_bounds(qi, Cq, Ck, nk, q_offset, causal, window):
    """Traced [lo, hi) kv-chunk range a query chunk attends to."""
    if causal:
        hi = jnp.minimum((qi * Cq + Cq - 1 + q_offset) // Ck + 1, nk)
    else:
        hi = jnp.int32(nk)
    if window:
        lo = jnp.maximum((qi * Cq + q_offset - window + 1) // Ck, 0)
    else:
        lo = jnp.zeros((), jnp.int32)
    return lo, hi


def _flash_fwd_impl(q, k, v, causal, window, q_chunk, kv_chunk, q_offset):
    B, Sq, Hq, hd = q.shape
    _, Sk, Hkv, _ = k.shape
    scale = 1.0 / math.sqrt(hd)
    Cq = min(q_chunk, Sq)
    Ck = min(kv_chunk, Sk)
    nq = -(-Sq // Cq)
    nk = -(-Sk // Ck)
    q = _pad_to(q, 1, nq * Cq)
    k = _pad_to(k, 1, nk * Ck)
    v = _pad_to(v, 1, nk * Ck)
    qg, kg, vg, G, nq, nk = _grouped(q, k, v, Cq, Ck)
    valid_k = jnp.arange(nk * Ck) < Sk

    def one_q_chunk(args):
        qi, qc = args
        qpos = q_offset + qi * Cq + jnp.arange(Cq)
        lo, hi = _kv_bounds(qi, Cq, Ck, nk, q_offset, causal, window)

        def body(ki, st):
            o, m, l = st
            kc = jax.lax.dynamic_index_in_dim(kg, ki, 0, keepdims=False)
            vc = jax.lax.dynamic_index_in_dim(vg, ki, 0, keepdims=False)
            kpos = ki * Ck + jnp.arange(Ck)
            kv_valid = jax.lax.dynamic_slice_in_dim(valid_k, ki * Ck, Ck)
            oc, mc, lc = _attend_chunk(qc, kc, vc, qpos, kpos, kv_valid,
                                       causal=causal, window=window,
                                       scale=scale)
            m_new = jnp.maximum(m, mc)
            a = jnp.exp(m - m_new)
            b = jnp.exp(mc - m_new)
            return (o * a[..., None] + oc * b[..., None],
                    m_new, l * a + lc * b)

        o0 = jnp.zeros(qc.shape, jnp.float32)
        m0 = jnp.full(qc.shape[:-1], NEG_INF, jnp.float32)
        l0 = jnp.zeros(qc.shape[:-1], jnp.float32)
        o, m, l = jax.lax.fori_loop(lo, hi, body, (o0, m0, l0))
        lse = m + jnp.log(jnp.maximum(l, 1e-20))
        return o / jnp.maximum(l, 1e-20)[..., None], lse

    out, lse = jax.lax.map(one_q_chunk, (jnp.arange(nq), qg))
    out = out.transpose(1, 0, 4, 2, 3, 5).reshape(B, nq * Cq, Hq, hd)
    return out[:, :Sq].astype(q.dtype), lse     # lse [nq,B,kv,G,Cq]


def _flash_bwd_impl(res, dout, causal, window, q_chunk, kv_chunk, q_offset):
    """Flash backward: recompute scores per (q,kv) chunk pair; accumulate
    dk/dv in chunked f32 buffers via the q-chunk scan's carry.  No residual
    grows with S² anywhere."""
    q, k, v, out, lse = res
    B, Sq, Hq, hd = q.shape
    _, Sk, Hkv, _ = k.shape
    scale = 1.0 / math.sqrt(hd)
    Cq = min(q_chunk, Sq)
    Ck = min(kv_chunk, Sk)
    nq = -(-Sq // Cq)
    nk = -(-Sk // Ck)
    qp = _pad_to(q, 1, nq * Cq)
    kp = _pad_to(k, 1, nk * Ck)
    vp = _pad_to(v, 1, nk * Ck)
    dop = _pad_to(dout, 1, nq * Cq)
    op = _pad_to(out, 1, nq * Cq)
    qg, kg, vg, G, nq, nk = _grouped(qp, kp, vp, Cq, Ck)
    dog = dop.reshape(B, nq, Cq, Hkv, G, hd).transpose(1, 0, 3, 4, 2, 5)
    og = op.reshape(B, nq, Cq, Hkv, G, hd).transpose(1, 0, 3, 4, 2, 5)
    valid_k = jnp.arange(nk * Ck) < Sk
    # D_i = rowsum(do * o)  [nq,B,kv,G,Cq]
    Dg = jnp.sum(dog.astype(jnp.float32) * og.astype(jnp.float32), axis=-1)

    def one_q_chunk(carry, args):
        dkg, dvg = carry                     # [nk,B,kv,Ck,hd] f32
        qi, qc, doc, Dc, lsec = args
        qpos = q_offset + qi * Cq + jnp.arange(Cq)
        lo, hi = _kv_bounds(qi, Cq, Ck, nk, q_offset, causal, window)
        doc32 = doc.astype(jnp.float32)

        def body(ki, st):
            dq, dkg, dvg = st
            kc = jax.lax.dynamic_index_in_dim(kg, ki, 0, keepdims=False)
            vc = jax.lax.dynamic_index_in_dim(vg, ki, 0, keepdims=False)
            kpos = ki * Ck + jnp.arange(Ck)
            kv_valid = jax.lax.dynamic_slice_in_dim(valid_k, ki * Ck, Ck)
            s = jnp.einsum("bkgqd,bkcd->bkgqc", qc, kc,
                           preferred_element_type=jnp.float32) * scale
            mask = jnp.broadcast_to(kv_valid[None, :], (Cq, Ck))
            if causal:
                mask &= kpos[None, :] <= qpos[:, None]
            if window:
                mask &= kpos[None, :] > qpos[:, None] - window
            p = jnp.where(mask[None, None, None],
                          jnp.exp(s - lsec[..., None]), 0.0)
            dv_j = jnp.einsum("bkgqc,bkgqd->bkcd", p, doc32)
            dp = jnp.einsum("bkgqd,bkcd->bkgqc", doc32,
                            vc.astype(jnp.float32))
            ds = p * (dp - Dc[..., None]) * scale
            dq = dq + jnp.einsum("bkgqc,bkcd->bkgqd", ds,
                                 kc.astype(jnp.float32))
            dk_j = jnp.einsum("bkgqc,bkgqd->bkcd", ds, qc.astype(jnp.float32))
            dkg = dkg.at[ki].add(dk_j)
            dvg = dvg.at[ki].add(dv_j)
            return dq, dkg, dvg

        dq0 = jnp.zeros(qc.shape, jnp.float32)
        dq, dkg, dvg = jax.lax.fori_loop(lo, hi, body, (dq0, dkg, dvg))
        return (dkg, dvg), dq

    dk0 = jnp.zeros((nk, B, Hkv, Ck, hd), jnp.float32)
    dv0 = jnp.zeros((nk, B, Hkv, Ck, hd), jnp.float32)
    (dkg, dvg), dqg = jax.lax.scan(
        one_q_chunk, (dk0, dv0),
        (jnp.arange(nq), qg, dog, Dg, lse))
    dq = dqg.transpose(1, 0, 4, 2, 3, 5).reshape(B, nq * Cq, Hq, hd)[:, :Sq]
    dk = dkg.transpose(1, 0, 3, 2, 4).reshape(B, nk * Ck, Hkv, hd)[:, :Sk]
    dv = dvg.transpose(1, 0, 3, 2, 4).reshape(B, nk * Ck, Hkv, hd)[:, :Sk]
    return dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _flash(q, k, v, causal, window, q_chunk, kv_chunk, q_offset):
    out, _ = _flash_fwd_impl(q, k, v, causal, window, q_chunk, kv_chunk,
                             q_offset)
    return out


def _flash_fwd_rule(q, k, v, causal, window, q_chunk, kv_chunk, q_offset):
    out, lse = _flash_fwd_impl(q, k, v, causal, window, q_chunk, kv_chunk,
                               q_offset)
    return out, (q, k, v, out, lse)


def _flash_bwd_rule(causal, window, q_chunk, kv_chunk, q_offset, res, dout):
    return _flash_bwd_impl(res, dout, causal, window, q_chunk, kv_chunk,
                           q_offset)


_flash.defvjp(_flash_fwd_rule, _flash_bwd_rule)


def flash_attention(q, k, v, *, causal: bool = True, window: int = 0,
                    q_chunk: int = DEFAULT_Q_CHUNK,
                    kv_chunk: int = DEFAULT_KV_CHUNK,
                    q_offset: int = 0):
    """q [B,Sq,Hq,hd], k/v [B,Sk,Hkv,hd] -> [B,Sq,Hq,hd].

    ``q_offset``: absolute position of q[0] (decode/chunked-prefill);
    kv positions are 0..Sk-1.  The kv loop visits only chunks within the
    causal triangle / sliding window of each query chunk, so FLOPs and
    memory are O(S·window) / O(S²/2), forward AND backward (custom VJP
    recomputes scores chunkwise — nothing S²-sized is ever saved)."""
    return _flash(q, k, v, causal, window, q_chunk, kv_chunk, q_offset)


def _pad_to(x, axis, size):
    pad = size - x.shape[axis]
    if pad <= 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


# ---------------------------------------------------------------------------
# Attention module (GQA, optional bias / padding to TP-friendly head counts).

def attn_init(key, cfg, dtype):
    d, hd = cfg.d_model, cfg.hd
    nq, nkv = cfg.n_q, cfg.n_kv
    ks = jax.random.split(key, 4)
    scale = 1.0 / math.sqrt(d)
    p = {
        "wq": nn._truncnorm(ks[0], (d, nq, hd), scale, dtype),
        "wk": nn._truncnorm(ks[1], (d, nkv, hd), scale, dtype),
        "wv": nn._truncnorm(ks[2], (d, nkv, hd), scale, dtype),
        "wo": nn._truncnorm(ks[3], (nq, hd, d), scale, dtype),
    }
    a = {
        "wq": ("embed", "heads", "qk_head"),
        "wk": ("embed", "kv", "qk_head"),
        "wv": ("embed", "kv", "qk_head"),
        "wo": ("heads", "qk_head", "embed"),
    }
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((nq, hd), dtype)
        p["bk"] = jnp.zeros((nkv, hd), dtype)
        p["bv"] = jnp.zeros((nkv, hd), dtype)
        a["bq"] = ("heads", "qk_head")
        a["bk"] = ("kv", "qk_head")
        a["bv"] = ("kv", "qk_head")
    return p, a


def attn_qkv(p, x):
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"])
    k = jnp.einsum("bsd,dhk->bshk", x, p["wk"])
    v = jnp.einsum("bsd,dhk->bshk", x, p["wv"])
    if "bq" in p:
        q, k, v = q + p["bq"], k + p["bk"], v + p["bv"]
    return q, k, v


def attn_out(p, o):
    return jnp.einsum("bshk,hkd->bsd", o, p["wo"])


def attn_qkv_decode(p, x):
    """Single-token QKV: x [B, d] -> q/k/v [B, H, hd].  Works on the full
    weights or on a TP head shard (the heads dim pre-sliced by shard_map —
    the decode-mode manual projection of dist/tp.py)."""
    q = jnp.einsum("bd,dhk->bhk", x, p["wq"])
    k = jnp.einsum("bd,dhk->bhk", x, p["wk"])
    v = jnp.einsum("bd,dhk->bhk", x, p["wv"])
    if "bq" in p:
        q, k, v = q + p["bq"], k + p["bk"], v + p["bv"]
    return q, k, v


def attn_out_decode(p, o):
    """Single-token out projection: o [B, H, hd] -> f32 [B, d].  On a TP
    head shard this is the row-parallel half: the caller psums over
    ``model`` and rounds to the activation dtype once, after the sum."""
    return jnp.einsum("bhk,hkd->bd", o, p["wo"],
                      preferred_element_type=jnp.float32)


def kv_head_slice(k, v, shard, kv_rep: int):
    """Per-chip KV head slice when KV heads are REPLICATED across a model
    axis wider than ``n_kv`` (kv_rep = tp / n_kv > 1): k/v [B, n_kv, hd]
    computed from replicated weights; chip ``shard`` keeps original head
    ``shard // kv_rep`` (exactly one head per chip — chips ``shard`` and
    ``shard ^ 1 ... `` holding the same head serve disjoint q-head groups,
    so nothing is double-counted downstream).  Identity when kv_rep == 1
    (the weights were already head-sharded by the enclosing shard_map)."""
    if kv_rep <= 1:
        return k, v
    head = shard // kv_rep
    return (jax.lax.dynamic_slice_in_dim(k, head, 1, axis=1),
            jax.lax.dynamic_slice_in_dim(v, head, 1, axis=1))


def self_attention(p, x, positions, cfg, *, window: int = 0,
                   mrope_positions=None, causal: bool = True):
    """Full-sequence self attention (train / prefill)."""
    q, k, v = attn_qkv(p, x)
    if mrope_positions is not None and cfg.mrope_sections:
        q = apply_mrope(q, mrope_positions, cfg.mrope_sections, cfg.rope_theta)
        k = apply_mrope(k, mrope_positions, cfg.mrope_sections, cfg.rope_theta)
    else:
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_theta)
    o = flash_attention(q, k, v, causal=causal, window=window)
    return attn_out(p, o)


def cross_attn_init(key, cfg, dtype):
    return attn_init(key, cfg, dtype)


def cross_attention(p, x, memory):
    """Encoder-decoder cross attention (no positions on k: memory carries
    its own encoding)."""
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"])
    k = jnp.einsum("bsd,dhk->bshk", memory, p["wk"])
    v = jnp.einsum("bsd,dhk->bshk", memory, p["wv"])
    if "bq" in p:
        q, k, v = q + p["bq"], k + p["bk"], v + p["bv"]
    o = flash_attention(q, k, v, causal=False)
    return attn_out(p, o)


# ---------------------------------------------------------------------------
# MLP (SwiGLU) and block.

def mlp_init(key, d: int, d_ff: int, dtype):
    ks = jax.random.split(key, 3)
    s_in = 1.0 / math.sqrt(d)
    s_out = 1.0 / math.sqrt(d_ff)
    p = {
        "wi_gate": nn._truncnorm(ks[0], (d, d_ff), s_in, dtype),
        "wi_up": nn._truncnorm(ks[1], (d, d_ff), s_in, dtype),
        "wo": nn._truncnorm(ks[2], (d_ff, d), s_out, dtype),
    }
    a = {"wi_gate": ("embed", "mlp"), "wi_up": ("embed", "mlp"),
         "wo": ("mlp", "embed")}
    return p, a


def mlp_partial(p, x):
    """SwiGLU MLP with the f32 sum of its row-parallel ``wo`` projection:
    on a d_ff shard the caller psums it and rounds once, after the sum
    (rounding each shard's partial apart drifts the sharded paths away
    from one chip's result)."""
    g = jax.nn.silu(jnp.einsum("bsd,df->bsf", x, p["wi_gate"]))
    u = jnp.einsum("bsd,df->bsf", x, p["wi_up"])
    return jnp.einsum("bsf,fd->bsd", g * u, p["wo"],
                      preferred_element_type=jnp.float32)


def mlp_apply(p, x):
    return mlp_partial(p, x).astype(x.dtype)


def block_init(key, cfg, dtype, d_ff: Optional[int] = None):
    """Standard pre-norm (attn + MLP) block."""
    k1, k2 = jax.random.split(key)
    pa, aa = attn_init(k1, cfg, dtype)
    pm, am = mlp_init(k2, cfg.d_model, d_ff or cfg.d_ff, dtype)
    pn1, an1 = nn.norm_init(cfg.d_model, dtype)
    pn2, an2 = nn.norm_init(cfg.d_model, dtype)
    p = {"attn": pa, "mlp": pm, "ln1": pn1, "ln2": pn2}
    a = {"attn": aa, "mlp": am, "ln1": an1, "ln2": an2}
    return p, a


def block_apply(p, x, positions, cfg, *, window: int = 0,
                mrope_positions=None):
    h = self_attention(p["attn"], nn.rmsnorm(p["ln1"], x), positions, cfg,
                       window=window, mrope_positions=mrope_positions)
    x = x + h
    x = x + mlp_apply(p["mlp"], nn.rmsnorm(p["ln2"], x))
    return x
