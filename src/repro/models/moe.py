"""Mixture-of-Experts MLP with expert parallelism over the ``model`` axis.

Dispatch strategy (DESIGN.md §5): activations are replicated across the
``model`` axis (they are sharded over ``data``/``pod`` only), so each chip
can gather the tokens destined for ITS local experts directly from its local
token set — dispatch needs **no all-to-all**; the only communication is the
same [T_local, d] all-reduce a dense TP MLP needs (combine psum).  This is
implemented as an explicit ``shard_map`` region so the collective schedule
is exactly what we wrote, not what GSPMD guesses.

Capacity: static per-chip per-expert capacity C = ceil(T_local·k/E · cf);
overflow tokens are dropped (gates renormalized over surviving experts) —
standard practice; the aux load-balance loss keeps overflow rare.  When no
mesh context is active (CPU smoke tests) the same code runs with a single
"shard" holding all experts.
"""
from __future__ import annotations

import functools
import math
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.dist import ctx
from repro.dist.compat import shard_map
from repro.models import nn

def moe_init(key, cfg, dtype):
    d, f, E = cfg.d_model, cfg.d_ff, cfg.num_experts
    ks = jax.random.split(key, 4)
    s_in = 1.0 / math.sqrt(d)
    s_out = 1.0 / math.sqrt(f)
    p = {
        "router": nn._truncnorm(ks[0], (d, E), s_in, jnp.float32),
        "wi_gate": nn._truncnorm(ks[1], (E, d, f), s_in, dtype),
        "wi_up": nn._truncnorm(ks[2], (E, d, f), s_in, dtype),
        "wo": nn._truncnorm(ks[3], (E, f, d), s_out, dtype),
    }
    a = {
        "router": ("embed", None),
        "wi_gate": ("experts", "embed", "mlp_shard"),
        "wi_up": ("experts", "embed", "mlp_shard"),
        "wo": ("experts", "mlp_shard", "embed"),
    }
    return p, a


def _capacity(T: int, k: int, E: int, factor: float) -> int:
    c = int(math.ceil(T * k / E * factor))
    return min(T, max(8, -(-c // 8) * 8))


# Router snap grid (numerics): bf16 reduction-order noise across shardings
# perturbs the router input by ~1e-3, so raw top_k can pick DIFFERENT
# experts per sharding for near-tie logits — a full expert flip, i.e. O(1)
# logits drift from O(eps) numeric noise.  Snapping the scores to a coarse
# grid and breaking ties by expert index makes the selection a step
# function with margins far wider than the noise: shardings disagree only
# when a score sits within eps of a grid edge.  The snap is applied to the
# raw LOGITS (O(1) scale regardless of E) — never to the softmax probs,
# whose ~1/E magnitude would collapse every expert into one grid cell at
# production expert counts (E=128 -> probs ~0.008 << any useful grid).
ROUTER_SNAP_GRID = 1.0 / 64.0


def _router_top_k(logits, probs, k: int, E: int):
    """Deterministic, sharding-robust expert selection: top-k of the
    grid-snapped router logits with a lower-expert-index tie-break; gate
    values still come from the exact probabilities."""
    snapped = jnp.round(logits / ROUTER_SNAP_GRID)        # [T,E] small ints
    idx = jnp.arange(E, dtype=jnp.float32)
    _, ids = jax.lax.top_k(snapped * (E + 1.0) - idx[None, :], k)
    gates = jnp.take_along_axis(probs, ids, axis=-1)      # [T,k]
    return gates, ids


def _moe_local(x, router, wig, wiu, wo, *, k: int, E: int, E_local: int,
               e_offset, C: int):
    """Per-chip MoE: x [T,d] local tokens (replicated over model axis),
    expert weights local [E_local,...].  Returns (partial y [T,d], aux)."""
    T, d = x.shape
    logits = jnp.einsum("td,de->te", x.astype(jnp.float32), router)
    probs = jax.nn.softmax(logits, axis=-1)               # [T,E]
    gates, ids = _router_top_k(logits, probs, k, E)       # [T,k]
    gates = gates / jnp.sum(gates, axis=-1, keepdims=True)

    # aux load-balance loss (Switch-style): E * sum_e f_e * P_e
    me = jnp.mean(probs, axis=0)                          # [E]
    ce = jnp.zeros((E,), jnp.float32).at[ids.reshape(-1)].add(
        jnp.ones((T * k,), jnp.float32)) / (T * k)
    aux = E * jnp.sum(me * ce)

    my_e = e_offset + jnp.arange(E_local)                 # [E_local]
    match = ids[None, :, :] == my_e[:, None, None]        # [E_local,T,k]
    sel = jnp.any(match, axis=-1)                         # [E_local,T]
    gate_e = jnp.sum(jnp.where(match, gates[None], 0.0), axis=-1)
    pos = jnp.cumsum(sel.astype(jnp.int32), axis=1) - 1   # [E_local,T]
    keep = sel & (pos < C)
    slot = jnp.where(keep, pos, C)                        # C = trash slot

    def scatter_tokens(slot_e, keep_e):
        buf = jnp.zeros((C + 1, d), x.dtype).at[slot_e].set(
            jnp.where(keep_e[:, None], x, 0))
        src = jnp.full((C + 1,), T, jnp.int32).at[slot_e].set(
            jnp.where(keep_e, jnp.arange(T), T))
        return buf[:C], src[:C]

    buf, src = jax.vmap(scatter_tokens)(slot, keep)       # [E_local,C,d]

    h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", buf, wig)) * \
        jnp.einsum("ecd,edf->ecf", buf, wiu)
    out = jnp.einsum("ecf,efd->ecd", h, wo)               # [E_local,C,d]

    gate_buf = jnp.take_along_axis(
        gate_e, jnp.minimum(src, T - 1), axis=1) * (src < T)  # [E_local,C]
    y = jnp.zeros((T + 1, d), jnp.float32).at[src.reshape(-1)].add(
        (out * gate_buf[..., None]).astype(jnp.float32).reshape(-1, d),
        mode="drop")
    # f32 partials: callers round to x.dtype once, after any psum —
    # rounding each shard's partial apart made the sharded paths drift by
    # enough to flip a near-tie router choice one layer down
    return y[:T], aux


def moe_apply(p, x, cfg) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """x [B,S,d] -> (y [B,S,d], aux_loss scalar)."""
    B, S, d = x.shape
    E, k = cfg.num_experts, cfg.experts_per_token
    rules = ctx.current_rules()
    ep = (rules is not None and "model" in rules.mesh.shape
          and rules.axis_for("experts", E) is not None
          and E % rules.mesh.shape["model"] == 0)
    if rules is None:
        # single-shard path (smoke tests / tiny meshes)
        y, aux = _moe_local(x.reshape(B * S, d), p["router"], p["wi_gate"],
                            p["wi_up"], p["wo"], k=k, E=E, E_local=E,
                            e_offset=0,
                            C=_capacity(B * S, k, E, cfg.moe_capacity_factor))
        return y.reshape(B, S, d).astype(x.dtype), aux
    if not ep:
        # DP mapping (§Perf): tokens sharded over EVERY axis, all experts
        # local (weights FSDP-gathered per layer by GSPMD outside) — no
        # dispatch communication at all.
        mesh = rules.mesh
        all_axes = tuple(a for a in ("pod", "data", "model")
                         if a in mesh.shape)
        n_all = 1
        for a in all_axes:
            n_all *= mesh.shape[a]
        bspec = all_axes if B % n_all == 0 else None
        B_l = B // n_all if bspec else B
        C = _capacity(B_l * S, k, E, cfg.moe_capacity_factor)

        @functools.partial(
            shard_map, mesh=mesh,
            in_specs=(P(bspec, None, None), P(), P(), P(), P()),
            out_specs=(P(bspec, None, None), P()),
            check_vma=False)
        def _dp(x_l, router, wig, wiu, wo):
            Bl = x_l.shape[0]
            y, aux = _moe_local(x_l.reshape(Bl * S, d), router, wig, wiu,
                                wo, k=k, E=E, E_local=E, e_offset=0, C=C)
            aux = jax.lax.pmean(aux, all_axes)
            return y.reshape(Bl, S, d).astype(x_l.dtype), aux

        return _dp(x, p["router"], p["wi_gate"], p["wi_up"], p["wo"])

    mesh = rules.mesh
    tp = mesh.shape["model"]
    E_local = E // tp
    dp_axes = tuple(a for a in ("pod", "data") if a in mesh.shape)
    dp = 1
    for a in dp_axes:
        dp *= mesh.shape[a]
    serve = getattr(rules, "mode", "train") == "serve"
    # serve mode: tokens replicated; expert FFN width sharded over `data`
    f_sharded = serve and "data" in mesh.shape and \
        cfg.d_ff % mesh.shape["data"] == 0
    if serve:
        bspec, B_local = None, B
    else:
        bspec = dp_axes if B % dp == 0 else None
        B_local = B // dp if bspec else B
    T_local = B_local * S
    C = _capacity(T_local, k, E, cfg.moe_capacity_factor)
    f_spec = "data" if f_sharded else None
    psum_axes = ("model",) + (("data",) if f_sharded else ())

    @functools.partial(
        shard_map, mesh=mesh,
        in_specs=(P(bspec, None, None), P(),
                  P("model", None, f_spec), P("model", None, f_spec),
                  P("model", f_spec, None)),
        out_specs=(P(bspec, None, None), P()),
        check_vma=False)
    def _sharded(x_l, router, wig, wiu, wo):
        Bl = x_l.shape[0]
        e_off = jax.lax.axis_index("model") * E_local
        y, aux = _moe_local(x_l.reshape(Bl * S, d), router, wig, wiu, wo,
                            k=k, E=E, E_local=E_local, e_offset=e_off, C=C)
        y = jax.lax.psum(y, psum_axes)
        aux = jax.lax.psum(aux, "model") / tp
        if dp_axes and not serve:
            aux = jax.lax.pmean(aux, dp_axes)
        return y.reshape(Bl, S, d).astype(x_l.dtype), aux

    return _sharded(x, p["router"], p["wi_gate"], p["wi_up"], p["wo"])


def moe_decode_local(p, x, cfg) -> jnp.ndarray:
    """Per-chip MoE for the fused manual decode region (serving/engine.py):
    tokens replicated over every axis, experts sharded over ``model``
    (weights pre-sliced by the enclosing shard_map's in_specs), combine via
    one psum — the decode-mode manual projection.  Must run INSIDE a manual
    region that owns the model axis; x [B, S, d] -> y [B, S, d].  The aux
    load-balance loss is dropped (decode never trains the router)."""
    B, S, d = x.shape
    E, k = cfg.num_experts, cfg.experts_per_token
    tp = jax.lax.axis_size("model")
    E_local = E // tp
    e_off = jax.lax.axis_index("model") * E_local
    C = _capacity(B * S, k, E, cfg.moe_capacity_factor)
    y, _ = _moe_local(x.reshape(B * S, d), p["router"], p["wi_gate"],
                      p["wi_up"], p["wo"], k=k, E=E, E_local=E_local,
                      e_offset=e_off, C=C)
    return jax.lax.psum(y.reshape(B, S, d), "model").astype(x.dtype)


def moe_flops_per_token(cfg) -> int:
    """Active-expert matmul FLOPs per token (for roofline accounting)."""
    return 6 * cfg.experts_per_token * cfg.d_model * cfg.d_ff * 3
