"""Plain float32 reference of a Qwen2-style dense decoder (Qwen2.5).

Written from the published architecture (Hugging Face ``Qwen2ForCausalLM``):
pre-norm RMSNorm blocks, grouped-query attention with biases on q, k and v,
rotary embeddings on half-split dimensions, a SwiGLU MLP, a final RMSNorm
and an untied output head.  Full causal attention over the whole sequence,
no cache, no kernels, every matrix product at ``highest`` precision.  It
imports nothing of the program; it reads the benchmark's weights by their
leaf names.

``quant=True`` is the control: every projection and the output head take
their inputs and weights rounded to float8 (e4m3) with one scale per row of
the activations and per output column of the weights, the step below the
bfloat16 the configuration serves in.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import flops

_F8_MAX = 448.0                         # largest finite float8_e4m3fn
_BUCKET = 512                           # sequences padded to a multiple


def _f8(x, axis):
    s = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / _F8_MAX
    s = jnp.where(s > 0, s, 1.0)
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _mm(x, w, quant):
    """x [S, in] @ w [in, out] in float32 (float8-rounded inputs if quant)."""
    x = x.astype(jnp.float32)
    w = w.astype(jnp.float32)
    if quant:
        x, w = _f8(x, 1), _f8(w, 0)
    return jnp.dot(x, w, precision=jax.lax.Precision.HIGHEST)


def _rmsnorm(x, scale, eps):
    x = x.astype(jnp.float32)
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * scale.astype(jnp.float32)


def _rope(x, theta):
    """x [S, H, hd]; rotate_half form with inv_freq = theta^(-2i/hd)."""
    S, _, hd = x.shape
    inv = theta ** (-jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * inv[None]
    ang = jnp.concatenate([ang, ang], -1)[:, None, :]
    x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
    rot = jnp.concatenate([-x2, x1], -1)
    return x * jnp.cos(ang) + rot * jnp.sin(ang)


@functools.partial(jax.jit, static_argnames=("m", "quant"))
def _layer(x, layers, i, *, m, quant):
    m = dict(m)
    p = jax.tree.map(lambda t: t[i], layers)
    a, mlp = p["attn"], p["mlp"]
    S, d = x.shape
    H, KV, hd = m["num_attention_heads"], m["num_key_value_heads"], m["hd"]
    eps = m["rms_norm_eps"]

    h = _rmsnorm(x, p["ln1"]["scale"], eps)
    q = _mm(h, a["wq"].reshape(d, H * hd), quant).reshape(S, H, hd)
    k = _mm(h, a["wk"].reshape(d, KV * hd), quant).reshape(S, KV, hd)
    v = _mm(h, a["wv"].reshape(d, KV * hd), quant).reshape(S, KV, hd)
    q = q + a["bq"].astype(jnp.float32)
    k = k + a["bk"].astype(jnp.float32)
    v = v + a["bv"].astype(jnp.float32)
    q, k = _rope(q, m["rope_theta"]), _rope(k, m["rope_theta"])
    q = q.reshape(S, KV, H // KV, hd)
    s = jnp.einsum("skgd,tkd->kgst", q, k,
                   precision=jax.lax.Precision.HIGHEST) / math.sqrt(hd)
    causal = jnp.tril(jnp.ones((S, S), bool))
    s = jnp.where(causal, s, -jnp.inf)
    o = jnp.einsum("kgst,tkd->skgd", jax.nn.softmax(s, -1), v,
                   precision=jax.lax.Precision.HIGHEST)
    x = x + _mm(o.reshape(S, H * hd), a["wo"].reshape(H * hd, d), quant)

    h = _rmsnorm(x, p["ln2"]["scale"], eps)
    g = _mm(h, mlp["wi_gate"], quant)
    u = _mm(h, mlp["wi_up"], quant)
    return x + _mm(jax.nn.silu(g) * u, mlp["wo"], quant)


@functools.partial(jax.jit, static_argnames=("eps", "quant"))
def _head(x, scale, w, *, eps, quant):
    return _mm(_rmsnorm(x, scale, eps), w, quant)


def _sizes(model: dict) -> dict:
    m = {k: model[k] for k in ("num_attention_heads", "num_key_value_heads",
                               "rms_norm_eps", "rope_theta")}
    m["hd"] = model["hidden_size"] // model["num_attention_heads"]
    return m


def logits(weights, model: dict, tokens, *, quant: bool = False):
    """float32 logits [S, vocab] on the device, row p predicting the token
    after position p of ``tokens``; S is ``tokens``' length rounded up to a
    multiple of 512 (rows past the sequence are padding).  Computed layer by
    layer so that one layer's float32 weights are on the device at a
    time."""
    tokens = np.asarray(tokens, np.int32)
    S = -(-tokens.size // _BUCKET) * _BUCKET
    # padding after the sequence changes nothing before it (causal)
    tok = np.zeros(S, np.int32)
    tok[:tokens.size] = tokens
    m = tuple(sorted(_sizes(model).items()))
    x = weights["embed"]["embedding"][jnp.asarray(tok)].astype(jnp.float32)
    for i in range(model["num_hidden_layers"]):
        x = _layer(x, weights["layers"], i, m=m, quant=quant)
    return _head(x, weights["final_norm"]["scale"], weights["lm_head"]["w"],
                 eps=model["rms_norm_eps"], quant=quant)


def lane_flops(cfg, p0: int, p1: int) -> float:
    """The operations one lane's tokens at positions ``p0`` to ``p1 - 1``
    need, ``cfg`` being the configuration as run (``flops.dense_lane_flops``):
    a dense decoder's projections, MLP and read-out at every token and
    its attention over the token's real context."""
    return flops.dense_lane_flops(cfg, p0, p1)
