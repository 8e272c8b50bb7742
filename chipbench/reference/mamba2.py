"""Plain float32 reference of a Mamba2 decoder (arXiv:2405.21060).

Written from the published block: in-projection to z, x, B, C and dt; a
depthwise causal convolution of width ``d_conv`` with SiLU over x, B and C;
dt = softplus(dt + dt_bias); the selective state-space recurrence, run one
position at a time,

    h_t = exp(dt_t * A) h_{t-1} + dt_t * x_t B_t^T,   y_t = h_t C_t + D x_t,

with A = -exp(A_log); a gated RMSNorm, RMSNorm(y * SiLU(z)) * w; and the
out-projection, added to the residual.  A final RMSNorm and the embedding,
tied, as the read-out.  No cache, no chunked scan, no kernels, every matrix
product at ``highest`` precision; it imports nothing of the program and
reads the benchmark's weights by their leaf names.

``quant=True`` is the control: every projection and the read-out take their
inputs and weights rounded to float8 (e4m3) with one scale per row of the
activations and per output column of the weights, the step below the
bfloat16 the configuration serves in.
"""
from __future__ import annotations

import functools

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
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * scale.astype(jnp.float32)


def _conv(x, w, b):
    """Depthwise causal convolution: x [S, C], w [W, C]; then SiLU."""
    W = w.shape[0]
    xp = jnp.pad(x, ((W - 1, 0), (0, 0)))
    w = w.astype(jnp.float32)
    y = sum(xp[i:i + x.shape[0]] * w[i] for i in range(W))
    return jax.nn.silu(y + b.astype(jnp.float32))


@functools.partial(jax.jit, static_argnames=("m", "quant"))
def _layer(x, layers, i, *, m, quant):
    m = dict(m)
    p = jax.tree.map(lambda t: t[i], layers)
    mb = p["mamba"]
    S = x.shape[0]
    N, P, eps = m["d_state"], m["headdim"], m["norm_epsilon"]

    u = _rmsnorm(x, p["ln"]["scale"], eps)
    z = _mm(u, mb["w_z"], quant)
    xs = _conv(_mm(u, mb["w_x"], quant), mb["conv_x_w"], mb["conv_x_b"])
    bc = _conv(_mm(u, mb["w_bc"], quant), mb["conv_bc_w"], mb["conv_bc_b"])
    dt = jax.nn.softplus(_mm(u, mb["w_dt"], quant)
                         + mb["dt_bias"].astype(jnp.float32))   # [S, H]
    A = -jnp.exp(mb["A_log"].astype(jnp.float32))               # [H]
    H = A.shape[0]
    xh = xs.reshape(S, H, P)
    Bm, Cm = bc[:, :N], bc[:, N:]

    def step(h, inp):
        x_t, dt_t, b_t, c_t = inp
        h = (h * jnp.exp(dt_t * A)[:, None, None]
             + (dt_t[:, None] * x_t)[:, :, None] * b_t[None, None, :])
        return h, jnp.einsum("hpn,n->hp", h, c_t,
                             precision=jax.lax.Precision.HIGHEST)

    h0 = jnp.zeros((H, P, N), jnp.float32)
    _, y = jax.lax.scan(step, h0, (xh, dt, Bm, Cm))
    y = y + mb["D"].astype(jnp.float32)[None, :, None] * xh
    y = y.reshape(S, H * P) * jax.nn.silu(z)
    y = _rmsnorm(y, mb["norm"], eps)
    return x + _mm(y, mb["w_out"], quant)


@functools.partial(jax.jit, static_argnames=("eps", "quant"))
def _head(x, scale, emb, *, eps, quant):
    return _mm(_rmsnorm(x, scale, eps), emb.T, quant)


def _sizes(model: dict) -> dict:
    return {k: model[k] for k in ("d_state", "headdim", "norm_epsilon")}


def logits(weights, model: dict, tokens, *, quant: bool = False):
    """float32 logits [S, vocab] on the device, row p predicting the token
    after position p of ``tokens``; S is ``tokens``' length rounded up to a
    multiple of 512 (rows past the sequence are padding).  Computed layer by
    layer."""
    tokens = np.asarray(tokens, np.int32)
    S = -(-tokens.size // _BUCKET) * _BUCKET
    # padding after the sequence changes nothing before it (causal)
    tok = np.zeros(S, np.int32)
    tok[:tokens.size] = tokens
    m = tuple(sorted(_sizes(model).items()))
    emb = weights["embed"]["embedding"]
    x = emb[jnp.asarray(tok)].astype(jnp.float32)
    for i in range(model["n_layer"]):
        x = _layer(x, weights["layers"], i, m=m, quant=quant)
    return _head(x, weights["final_norm"]["scale"], emb,
                 eps=model["norm_epsilon"], quant=quant)


def lane_flops(cfg, p0: int, p1: int) -> float:
    """The operations one lane's tokens at positions ``p0`` to ``p1 - 1``
    need, ``cfg`` being the configuration as run (``flops.ssm_lane_flops``):
    a Mamba2 decoder's projections, O(1) recurrence and read-out, the
    same at every position."""
    return flops.ssm_lane_flops(cfg, p0, p1)
