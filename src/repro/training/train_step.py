"""Train-step factories.

``make_train_step`` — the GSPMD path: loss -> grad -> clip -> AdamW, with
activation remat on the layer scan and logical sharding constraints from the
active ``dist.ctx`` rules.  Gradient reduction over (pod, data) is inserted
by autodiff/GSPMD (batch is sharded over those axes).

``make_train_step_manual_pod`` — the distributed-optimization variant for
DCN-separated pods: the pod axis is handled *manually* (shard_map at the top
level), so the cross-pod gradient all-reduce is explicit and runs through
``dist.compression`` (int8 + error feedback), overlapping nothing it
shouldn't.  Used by the multi-pod dry-run as the compressed-DP configuration.
"""
from __future__ import annotations

import functools
from typing import Any, Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.dist import compression
from repro.dist import ctx
from repro.dist.compat import shard_map
from repro.models.registry import get_model
from repro.training import optimizer as opt


class TrainState(NamedTuple):
    params: Any
    opt: opt.OptState
    step: jnp.ndarray


def init_state(cfg, key) -> tuple[TrainState, Any]:
    model = get_model(cfg)
    params, axes = model.init(cfg, key)
    state = TrainState(params=params, opt=opt.init(params),
                       step=jnp.zeros((), jnp.int32))
    state_axes = TrainState(params=axes, opt=opt.opt_state_axes(axes),
                            step=())
    return state, state_axes


def make_loss_fn(cfg, remat: bool = True) -> Callable:
    model = get_model(cfg)

    def loss_fn(params, batch):
        kwargs = {}
        if "src_embeds" in batch:
            kwargs["src_embeds"] = batch["src_embeds"]
        if cfg.family == "vlm":
            logits, aux = model.forward(
                cfg, params, batch["tokens"],
                patch_embeds=batch.get("patch_embeds"),
                mrope_positions=batch.get("mrope_positions"),
                remat=remat)
            logp = jax.nn.log_softmax(logits, axis=-1)
            ll = jnp.take_along_axis(logp, batch["labels"][..., None],
                                     axis=-1)[..., 0]
            return -jnp.mean(ll) + (0.01 * aux / cfg.num_layers
                                    if cfg.family == "moe" else 0.0)
        return model.loss_fn(cfg, params, batch["tokens"], batch["labels"],
                             remat=remat, **kwargs)

    return loss_fn


def make_train_step(cfg, adamw: Optional[opt.AdamWConfig] = None,
                    remat: bool = True, rules=None) -> Callable:
    adamw = adamw or opt.AdamWConfig()
    loss_fn = make_loss_fn(cfg, remat=remat)

    def train_step(state: TrainState, batch) -> tuple[TrainState, dict]:
        with ctx.use_rules(rules):
            loss, grads = jax.value_and_grad(loss_fn)(state.params, batch)
            params2, opt2, metrics = opt.apply(adamw, state.params,
                                               state.opt, grads)
        metrics["loss"] = loss
        return TrainState(params2, opt2, state.step + 1), metrics

    return train_step


def make_train_step_manual_pod(cfg, mesh,
                               adamw: Optional[opt.AdamWConfig] = None,
                               remat: bool = True, rules=None) -> Callable:
    """Cross-pod compressed-gradient variant.  Params are replicated over
    ``pod`` (FSDP/TP sharding *within* a pod via ``rules``); the batch is
    manually split over pods; per-pod grads are reduced over the pod axis
    with int8 error-feedback compression, then the optimizer runs
    identically on every pod."""
    assert "pod" in mesh.shape, "manual-pod step needs a pod axis"
    adamw = adamw or opt.AdamWConfig()
    loss_fn = make_loss_fn(cfg, remat=remat)

    dp_axes = tuple(a for a in ("data",) if a in mesh.shape)

    def train_step(state: TrainState, err, batch):
        """``err`` leaves carry a leading [npods] dim (per-pod residuals),
        sharded over the pod axis.  The region is fully manual (the pinned
        XLA rejects partially-auto regions around the attention loops — see
        dist/compat.py): the batch is split over (pod, data), grads are
        pmean'd over ``data`` uncompressed (cheap ICI), then reduced over
        ``pod`` through int8 error-feedback compression (the expensive DCN
        hop).  The model axis sees replicated inputs and computes
        redundantly — identical on every chip, so the optimizer stays
        bitwise in sync."""
        bsp = P(("pod",) + dp_axes)
        batch_specs = jax.tree.map(lambda _: bsp, batch)
        err_specs = jax.tree.map(lambda _: P("pod"), err)

        @functools.partial(
            shard_map, mesh=mesh,
            in_specs=(P(), err_specs, batch_specs),
            out_specs=(P(), err_specs, P(), P()),
            check_vma=False)
        def _pod_step(state, err, batch):
            err_local = jax.tree.map(lambda e: e[0], err)
            with ctx.use_rules(rules):
                loss, grads = jax.value_and_grad(loss_fn)(state.params,
                                                          batch)
                if dp_axes:   # within-pod DP mean, uncompressed
                    grads = jax.tree.map(
                        lambda g: jax.lax.pmean(g, dp_axes), grads)
                grads, err2 = compression.tree_compressed_psum(
                    grads, "pod", err_local)
                npods = jax.lax.axis_size("pod")
                grads = jax.tree.map(lambda g: g / npods, grads)
                loss = jax.lax.pmean(loss, ("pod",) + dp_axes)
                params2, opt2, metrics = opt.apply(adamw, state.params,
                                                   state.opt, grads)
            err2 = jax.tree.map(lambda e: e[None], err2)
            return (TrainState(params2, opt2, state.step + 1), err2, loss,
                    metrics["grad_norm"])

        state2, err2, loss, gnorm = _pod_step(state, err, batch)
        return state2, err2, {"loss": loss, "grad_norm": gnorm}

    return train_step


def init_pod_error_buffers(params, npods: int):
    """Per-pod error-feedback residuals, leading [npods] dim (pod-sharded)."""
    return jax.tree.map(
        lambda p: jnp.zeros((npods,) + p.shape, jnp.float32), params)
