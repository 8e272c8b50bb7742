"""Plain float32 reference of the smoke MoE decoder, as the program builds
its ``moe`` family: pre-norm RMSNorm blocks, grouped-query attention with
rotary embeddings on half-split dimensions and no biases, a mixture of
SwiGLU experts in place of the MLP, a final RMSNorm and the embedding,
tied, as the read-out.  Each token's router picks ``num_experts_per_tok``
experts by their logits snapped to a grid of 1/64 (ties to the lower
expert), the program's routing rule, and weighs them by their softmax
probabilities renormalised over the picked.

The tests copy this file into a temporary checkout as
``chipbench/reference/granitemoe.py``: a family that enters the benchmark
with its own reference and its own FLOP count, and nothing else changed.
"""
from __future__ import annotations

import dataclasses
import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import flops

_BUCKET = 128                           # sequences padded to a multiple
_ROUTER_GRID = 1.0 / 64.0
_HI = jax.lax.Precision.HIGHEST


def _mm(x, w):
    return jnp.dot(x.astype(jnp.float32), w.astype(jnp.float32),
                   precision=_HI)


def _rmsnorm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * scale.astype(jnp.float32)


def _rope(x, theta):
    S, _, hd = x.shape
    inv = theta ** (-jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * inv[None]
    ang = jnp.concatenate([ang, ang], -1)[:, None, :]
    rot = jnp.concatenate([-x[..., hd // 2:], x[..., :hd // 2]], -1)
    return x * jnp.cos(ang) + rot * jnp.sin(ang)


def _experts(h, moe, k):
    """The mixture for every row of ``h`` [S, d]: each expert runs on every
    row and is weighed by its gate, 0 where the router did not pick it."""
    logits = _mm(h, moe["router"])                          # [S, E]
    S, E = logits.shape
    order = jnp.round(logits / _ROUTER_GRID) * (E + 1.0) - jnp.arange(E)
    _, ids = jax.lax.top_k(order, k)
    gates = jnp.take_along_axis(jax.nn.softmax(logits, -1), ids, 1)
    gates = gates / gates.sum(-1, keepdims=True)
    weight = jnp.zeros((S, E)).at[jnp.arange(S)[:, None], ids].set(gates)
    f32 = lambda t: t.astype(jnp.float32)                   # noqa: E731
    g = jnp.einsum("sd,edf->esf", h, f32(moe["wi_gate"]), precision=_HI)
    u = jnp.einsum("sd,edf->esf", h, f32(moe["wi_up"]), precision=_HI)
    y = jnp.einsum("esf,efd->esd", jax.nn.silu(g) * u, f32(moe["wo"]),
                   precision=_HI)
    return jnp.einsum("se,esd->sd", weight, y, precision=_HI)


@functools.partial(jax.jit, static_argnames=("m",))
def _layer(x, layers, i, *, m):
    m = dict(m)
    p = jax.tree.map(lambda t: t[i], layers)
    a = p["attn"]
    S, d = x.shape
    H, KV = m["num_attention_heads"], m["num_key_value_heads"]
    hd, eps = d // H, m["rms_norm_eps"]
    h = _rmsnorm(x, p["ln1"]["scale"], eps)
    q = _rope(_mm(h, a["wq"].reshape(d, H * hd)).reshape(S, H, hd),
              m["rope_theta"]).reshape(S, KV, H // KV, hd)
    k = _rope(_mm(h, a["wk"].reshape(d, KV * hd)).reshape(S, KV, hd),
              m["rope_theta"])
    v = _mm(h, a["wv"].reshape(d, KV * hd)).reshape(S, KV, hd)
    s = jnp.einsum("skgd,tkd->kgst", q, k, precision=_HI) / math.sqrt(hd)
    s = jnp.where(jnp.tril(jnp.ones((S, S), bool)), s, -jnp.inf)
    o = jnp.einsum("kgst,tkd->skgd", jax.nn.softmax(s, -1), v, precision=_HI)
    x = x + _mm(o.reshape(S, H * hd), a["wo"].reshape(H * hd, d))
    h = _rmsnorm(x, p["ln2"]["scale"], eps)
    return x + _experts(h, p["moe"], m["num_experts_per_tok"])


def logits(weights, model: dict, tokens):
    """float32 logits [S, vocab], row p predicting the token after position
    p of ``tokens``; S is ``tokens``' length rounded up to a multiple of
    128 (rows past the sequence are padding)."""
    tokens = np.asarray(tokens, np.int32)
    tok = np.zeros(-(-tokens.size // _BUCKET) * _BUCKET, np.int32)
    tok[:tokens.size] = tokens
    m = tuple(sorted((k, model[k]) for k in (
        "num_attention_heads", "num_key_value_heads", "rms_norm_eps",
        "rope_theta", "num_experts_per_tok")))
    emb = weights["embed"]["embedding"]
    x = emb[jnp.asarray(tok)].astype(jnp.float32)
    for i in range(model["num_hidden_layers"]):
        x = _layer(x, weights["layers"], i, m=m)
    x = _rmsnorm(x, weights["final_norm"]["scale"], model["rms_norm_eps"])
    return _mm(x, emb.T)


def lane_flops(cfg, p0: int, p1: int) -> float:
    """A lane that moved from ``p0`` to ``p1``: the dense count with the
    picked experts' widths in place of one MLP's, and the router."""
    n = int(p1) - int(p0)
    if n <= 0:
        return 0.0
    active = dataclasses.replace(cfg, d_ff=cfg.experts_per_token * cfg.d_ff)
    router = cfg.num_layers * 2.0 * cfg.d_model * cfg.num_experts
    return flops.dense_lane_flops(active, p0, p1) + n * router
