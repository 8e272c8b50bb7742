"""Tensor-parallel block application.

Two implementations behind one call signature, selected by
``cfg.tp_impl``:

- ``"gspmd"`` (baseline): run the plain ``models.layers`` block; TP comes
  from the weight shardings the active rules induce, with GSPMD inserting
  the collectives.
- ``"manual"``: Megatron-style shard_map blocks — column-parallel QKV /
  gate+up, row-parallel output projections, one explicit bf16 psum after
  attention and one after the MLP.

The manual region is fully manual over EVERY mesh axis (the pinned XLA
rejects partially-auto regions around the attention loops — see
``dist/compat.py``): the batch is explicitly split over the (pod, data)
axes when divisible and replicated otherwise.

The train-side manual path quietly falls back to gspmd whenever it cannot
apply (no active rules, no ``model`` axis, head counts / d_ff not divisible
by the TP width, or already inside a manual region that owns the model
axis) — CPU smoke tests therefore run the exact same numerics as the
single-device reference.  The DECODE-side gate is stricter about silence:
``decode_manual_unsupported`` returns a reason string for every refusal and
``serving/engine`` logs it — a production mesh can never lose the fused
path without a trace.  A model axis wider than ``n_kv`` is NOT a refusal at
decode: KV heads are replicated across the surplus width
(``decode_kv_rep``).

Decode side (the fused manual serve step in ``serving/engine.py``): this
module owns the gate (``decode_manual_tp``), the shard_map in_specs for the
stacked decode params (``decode_param_specs``), and the per-chip manual
projections that run INSIDE the engine's single manual region
(``mlp_decode_manual``, ``logits_decode_manual``).  Unlike the train gate, a
1-wide model axis still takes the fused path — head "shards" are then the
full head set, which gives the region single-process CPU test coverage.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.dist import ctx
from repro.dist.compat import shard_map
from repro.models import layers as L
from repro.models import nn


def _manual_tp(cfg, rules, *, need_ff: bool) -> int:
    """TP width when the manual path applies, else 0."""
    if cfg.tp_impl != "manual" or rules is None:
        return 0
    tp = rules.mesh.shape.get("model", 0)
    if tp <= 1 or "model" in ctx.current_manual_axes():
        return 0
    if cfg.n_q % tp or cfg.n_kv % tp:
        return 0
    if need_ff and cfg.d_ff % tp:
        return 0
    return tp


def _dp_axes(mesh, batch: int):
    """Mesh axes the batch dim is manually split over (empty -> replicated
    redundant compute on non-model axes, still correct)."""
    axes = tuple(a for a in ("pod", "data") if a in mesh.shape)
    n = 1
    for a in axes:
        n *= mesh.shape[a]
    return axes if axes and batch % n == 0 else ()


def _bcast_spec(arr, batch: int, dp):
    """Spec for a per-token side input: batch-sharded when its leading dim
    is the batch, replicated otherwise (e.g. positions [1, S])."""
    if arr is None:
        return None
    if dp and arr.ndim >= 2 and arr.shape[0] == batch:
        return P(dp, *(None,) * (arr.ndim - 1))
    return P()


def _attn_specs(ap):
    specs = {"wq": P(None, "model", None), "wk": P(None, "model", None),
             "wv": P(None, "model", None), "wo": P("model", None, None)}
    if "bq" in ap:
        specs.update(bq=P("model", None), bk=P("model", None),
                     bv=P("model", None))
    return specs


def _attn_manual(cfg, rules, ap, ln, x, positions, window, mrope):
    """x [B,S,d] -> attention sublayer output (pre-residual), heads
    column-parallel over ``model``, row-parallel wo + psum."""
    mesh = rules.mesh
    B = x.shape[0]
    dp = _dp_axes(mesh, B)
    x_spec = P(dp, None, None) if dp else P()
    mr_spec = (P(None, dp, None) if (mrope is not None and dp
                                     and mrope.shape[1] == B)
               else (P() if mrope is not None else None))

    def fn(ap_l, ln_l, x, positions, mrope):
        xn = nn.rmsnorm(ln_l, x)
        q, k, v = L.attn_qkv(ap_l, xn)
        if mrope is not None and cfg.mrope_sections:
            q = L.apply_mrope(q, mrope, cfg.mrope_sections, cfg.rope_theta)
            k = L.apply_mrope(k, mrope, cfg.mrope_sections, cfg.rope_theta)
        else:
            q = L.apply_rope(q, positions, cfg.rope_theta)
            k = L.apply_rope(k, positions, cfg.rope_theta)
        o = L.flash_attention(q, k, v, causal=True, window=window)
        y = L.attn_out(ap_l, o)
        return jax.lax.psum(y, "model")

    mapped = shard_map(
        fn, mesh=mesh,
        in_specs=(_attn_specs(ap), {"scale": P()}, x_spec,
                  _bcast_spec(positions, B, dp), mr_spec),
        out_specs=x_spec, check_vma=False)
    return mapped(ap, ln, x, positions, mrope)


def _mlp_manual(rules, mp, ln, x):
    """SwiGLU MLP, d_ff column-parallel, row-parallel wo + psum."""
    mesh = rules.mesh
    dp = _dp_axes(mesh, x.shape[0])
    x_spec = P(dp, None, None) if dp else P()

    def fn(mp_l, ln_l, x):
        y = L.mlp_partial(mp_l, nn.rmsnorm(ln_l, x))
        return jax.lax.psum(y, "model").astype(x.dtype)

    mapped = shard_map(
        fn, mesh=mesh,
        in_specs=({"wi_gate": P(None, "model"), "wi_up": P(None, "model"),
                   "wo": P("model", None)}, {"scale": P()}, x_spec),
        out_specs=x_spec, check_vma=False)
    return mapped(mp, ln, x)


# ---------------------------------------------------------------------------
# Decode-side manual TP (used by serving/engine's fused serve step).

def decode_kv_rep(cfg, tp: int) -> int:
    """KV-head replication factor for the fused decode region at TP width
    ``tp``: 1 when ``n_kv`` divides ``tp``'s complement (n_kv % tp == 0,
    plain head sharding), ``tp // n_kv`` when the mesh is WIDER than the KV
    head count (each KV head is replicated across the surplus width and
    every chip keeps exactly one head — e.g. kv=8 on the 16-wide production
    mesh, rep=2), and 0 when neither divides (unsupported shape)."""
    if tp <= 0:
        return 0
    if cfg.n_kv % tp == 0:
        return 1
    if cfg.n_kv and tp % cfg.n_kv == 0:
        return tp // cfg.n_kv
    return 0


def decode_manual_unsupported(cfg, rules):
    """Why the fused manual decode region cannot apply — None when it can.

    The gate is shape-only: ``tp_impl="manual"``, an active rule set with a
    ``model`` mesh axis not already manual, ``n_q`` divisible by the TP
    width, a valid KV replication factor (``decode_kv_rep``), and a
    divisible FFN (or expert) count.  tp == 1 is deliberately allowed (see
    module doc).  Family gating lives in ``serving/engine`` (ssm / encdec
    stay on the gspmd step)."""
    if cfg.tp_impl != "manual":
        return f"tp_impl={cfg.tp_impl!r} (not 'manual')"
    if rules is None:
        return "no active sharding rules"
    tp = rules.mesh.shape.get("model", 0)
    if tp < 1:
        return "mesh has no 'model' axis"
    if "model" in ctx.current_manual_axes():
        return "already inside a manual region owning 'model'"
    if cfg.n_q % tp:
        return f"n_q={cfg.n_q} not divisible by tp={tp}"
    if not decode_kv_rep(cfg, tp):
        return (f"n_kv={cfg.n_kv} neither divides nor is divided by "
                f"tp={tp} (no whole-head shard or replication)")
    if cfg.family == "moe":
        if cfg.num_experts % tp:
            return (f"num_experts={cfg.num_experts} not divisible by "
                    f"tp={tp}")
    elif cfg.d_ff % tp:
        return f"d_ff={cfg.d_ff} not divisible by tp={tp}"
    return None


def decode_manual_tp(cfg, rules) -> int:
    """TP width for the fused manual decode region, 0 when inapplicable
    (``decode_manual_unsupported`` gives the reason)."""
    if decode_manual_unsupported(cfg, rules) is not None:
        return 0
    return rules.mesh.shape["model"]


def decode_ssm_tp(cfg, tp: int) -> bool:
    """Whether the hybrid family's Mamba decode math shards over ``model``
    inside the fused region (ROADMAP item: it used to run as replicated
    redundant compute on every chip).  Shape gate: the per-head dims split
    over the existing ``ssm_inner``/``ssm_heads`` rules when the B/C
    streams are shared (``ssm_groups == 1`` — both assigned SSM archs) and
    the head count divides the TP width; otherwise the backbone stays
    replicated (still correct, just redundant).  ``tp == 1`` passes so
    single-process CPU tests cover the sharded code path (psum over a
    1-wide axis is the identity)."""
    if tp < 1 or cfg.ssm_state <= 0 or cfg.ssm_heads <= 0:
        return False                 # no SSM stack at all
    if cfg.ssm_groups != 1:
        return False                 # grouped B/C: head shard splits groups
    Hg = cfg.ssm_heads // cfg.ssm_groups
    return Hg % tp == 0 and cfg.d_inner % tp == 0


def _mamba_param_specs():
    """shard_map in_specs for STACKED mamba layer params (leading dim is the
    layer scan) inside the fused decode region, sharded per the
    ``ssm_inner``/``ssm_heads`` rules: per-head outputs column-parallel
    over ``model``, the shared B/C streams replicated, ``w_out``
    row-parallel."""
    return {
        "w_z": P(None, None, "model"),       # [L, d, di]
        "w_x": P(None, None, "model"),
        "w_bc": P(),                          # shared B/C streams (G == 1)
        "w_dt": P(None, None, "model"),      # [L, d, H]
        "conv_x_w": P(None, None, "model"),  # [L, W, di]
        "conv_x_b": P(None, "model"),
        "conv_bc_w": P(), "conv_bc_b": P(),
        "A_log": P(None, "model"), "dt_bias": P(None, "model"),
        "D": P(None, "model"),
        "norm": P(None, "model"),
        "w_out": P(None, "model", None),     # [L, di, d] row-parallel
    }


def decode_megastep_mode(cfg, rules, K: int) -> str:
    """Bookkeeping tag for the decode megastep (``serving/engine.
    make_serve_megastep``), recorded in dry-run artifacts next to
    ``decode_tp``: ``"scan-K{K}"`` when the K-token ``lax.scan`` dispatch
    applies (every family — the scan wraps whichever per-token body the
    gate above selects), ``"per-token"`` for K <= 1.  Dry-run's
    ``--expect-fused`` fails the build if an expected arch's decode cell
    records anything but a ``scan-`` tag — a regression back to per-token
    host dispatch can never land silently."""
    del cfg, rules  # the scan dispatch is family/mesh-independent today
    return f"scan-K{K}" if K > 1 else "per-token"


def decode_param_specs(cfg, params, *, vocab_sharded: bool,
                       kv_rep: int = 1, ssm_tp: bool = False):
    """shard_map in_specs (prefix pytree) for the fused manual decode region:
    stacked layer weights column/row-parallel over ``model`` (leading dim is
    the layer scan), everything else replicated.  ``vocab_sharded`` shards
    the untied lm_head over the vocab dim (logits all_gathered after).

    ``kv_rep > 1`` (KV heads replicated across the surplus model width):
    the K/V projections stay REPLICATED — each chip computes the full
    [B, n_kv, hd] K/V (n_kv·d·hd flops, noise at decode) and slices its own
    head in-region, which keeps the spec divisible without materialising a
    tiled weight copy per step.

    ``hybrid``: the ONE shared (attention + MLP) block is Megatron-sharded;
    the Mamba backbone shards its per-head dims over ``model`` when
    ``ssm_tp`` (gate ``decode_ssm_tp`` — the ssm_inner/ssm_heads rules) and
    runs replicated (redundant identical compute) otherwise."""
    kvw = P() if kv_rep > 1 else P(None, None, "model", None)
    kvb = P() if kv_rep > 1 else P(None, "model", None)
    if cfg.family == "hybrid":
        sh_attn = {"wq": P(None, "model", None),
                   "wk": P() if kv_rep > 1 else P(None, "model", None),
                   "wv": P() if kv_rep > 1 else P(None, "model", None),
                   "wo": P("model", None, None)}
        if "bq" in params["shared"]["attn"]:
            b1 = P() if kv_rep > 1 else P("model", None)
            sh_attn.update(bq=P("model", None), bk=b1, bv=b1)
        specs = {k: P() for k in params}
        specs["shared"] = {
            "attn": sh_attn, "ln1": P(), "ln2": P(),
            "mlp": {"wi_gate": P(None, "model"), "wi_up": P(None, "model"),
                    "wo": P("model", None)}}
        if ssm_tp:
            specs["layers"] = {"mamba": _mamba_param_specs(), "ln": P()}
        return specs
    h = P(None, None, "model", None)                 # [L, d, H, hd]
    attn = {"wq": h, "wk": kvw, "wv": kvw,
            "wo": P(None, "model", None, None)}      # [L, H, hd, d]
    if "bq" in params["layers"]["attn"]:
        attn.update(bq=P(None, "model", None), bk=kvb, bv=kvb)
    layer = {"attn": attn, "ln1": P(), "ln2": P()}
    if cfg.family == "moe":
        e = P(None, "model", None, None)             # [L, E, d|f, f|d]
        layer["moe"] = {"router": P(), "wi_gate": e, "wi_up": e, "wo": e}
    else:
        layer["mlp"] = {"wi_gate": P(None, None, "model"),
                        "wi_up": P(None, None, "model"),
                        "wo": P(None, "model", None)}
    specs = {k: P() for k in params}
    specs["layers"] = layer
    if vocab_sharded and "lm_head" in params:
        specs["lm_head"] = {"w": P(None, "model")}
    return specs


def mlp_decode_manual(mp, x):
    """SwiGLU MLP on a d_ff column shard + row-parallel wo; runs INSIDE an
    enclosing manual region that owns the model axis.  x [B, S, d]."""
    return jax.lax.psum(L.mlp_partial(mp, x), "model").astype(x.dtype)


def logits_decode_manual(cfg, params, x, *, vocab_sharded: bool):
    """Read-out inside the manual region.  Tied embeddings stay replicated
    (the same table serves the lookup); an untied head is vocab-sharded over
    ``model`` with a tiled all_gather when the width divides."""
    if cfg.tie_embeddings:
        return nn.embed_logits(params["embed"], x)
    y = nn.dense(params["lm_head"], x)
    if vocab_sharded:
        y = jax.lax.all_gather(y, "model", axis=-1, tiled=True)
    return y


def attn_apply_tp(cfg, p, x, positions, *, window: int = 0,
                  mrope_positions=None):
    """Attention sublayer with residual: x + attn(rmsnorm(ln1, x)).

    ``p`` is the full layer param dict (needs "attn" and "ln1"); used by the
    MoE family whose FFN half is handled by ``models.moe``."""
    rules = ctx.current_rules()
    if not _manual_tp(cfg, rules, need_ff=False):
        h = L.self_attention(p["attn"], nn.rmsnorm(p["ln1"], x), positions,
                             cfg, window=window,
                             mrope_positions=mrope_positions)
        return x + h
    return x + _attn_manual(cfg, rules, p["attn"], p["ln1"], x, positions,
                            window, mrope_positions)


def block_apply_tp(cfg, p, x, positions, *, window: int = 0,
                   mrope_positions=None):
    """Full pre-norm (attn + MLP) block, TP'd per ``cfg.tp_impl``."""
    rules = ctx.current_rules()
    if not _manual_tp(cfg, rules, need_ff=True):
        return L.block_apply(p, x, positions, cfg, window=window,
                             mrope_positions=mrope_positions)
    x = x + _attn_manual(cfg, rules, p["attn"], p["ln1"], x, positions,
                         window, mrope_positions)
    x = x + _mlp_manual(rules, p["mlp"], p["ln2"], x)
    return x
