"""Smoke run of the serving path on TPU: the quickest proof that the system
still starts on the chip.

    python chip_smoke.py             # one chip: phases 0-3
    python chip_smoke.py --chips 4   # four chips: tensor-parallel decode only

The model is qwen2.5-32b at its published widths (d_model 5120, 40 query
and 8 KV heads of 128, d_ff 27648, the whole 152064-token vocabulary, QKV
bias, bf16).  Depth is the only cut: 4 of 64 layers on one chip, 16 on
four; the other layers would sit on further chips as pipeline stages.
Weights are random, drawn on the device from ``--seed``.

One chip:
  0. device check: JAX must see a TPU (there is no CPU fallback);
  1. config and parameters;
  2. correctness: paged decode (``make_serve_step``) with the jnp gather
     against the model's own forward pass; the fused Pallas decode kernel,
     the TPU's default attention path, against the jnp gather; the
     probe-kernel block-table rebuild against the jnp oracle; the kernels'
     compiled HLO must hold a ``tpu_custom_call``;
  3. serving: a seeded 64-request workload through
     ``repro.launch.serve.run`` (``ContinuousBatcher``), which must drain
     with no ABORT and no pool growth.

Four chips: the manual tensor-parallel decode against the GSPMD decode on a
(data 1, model 4) mesh, then a short workload drained on the manual path.

The last line of standard output is one JSON object:
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
Any failure raises: the exit code is then non-zero and that line is never
printed.  Everything runs in this one process, which holds the chip.
"""
from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

# Logit agreement bound, as a share of the reference's largest |logit|
# (at least 1): 2^-4, i.e. 16 bf16 ulps at the logit scale.  Both sides
# keep the residual stream in bf16 and differ only in how they reduce
# (prefill matmuls against one-token decode; the Pallas kernel's online
# softmax against the gathered einsum; 4-way psums against one chip's
# sums), so they drift by a few bf16 roundings per layer and never by a
# wrong token's worth.
LOGIT_TOL = 2.0 ** -4


def log(msg: str) -> None:
    print(f"[smoke] {msg}", flush=True)


class SmokeFailure(RuntimeError):
    pass


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


# ---------------------------------------------------------------------------
# Phase 0 and 1.

def device_check(chips: int):
    devs = jax.devices()
    d = devs[0]
    log(f"phase 0 device: platform={d.platform} kind={d.device_kind} "
        f"count={len(devs)}")
    check(d.platform == "tpu",
          f"phase 0: JAX found no TPU (platform {d.platform!r})")
    check(len(devs) == chips,
          f"phase 0: {chips} chip(s) asked for, JAX sees {len(devs)}")
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(devs)}


def qwen_config(layers: int):
    """qwen2.5-32b at published widths, ``layers`` deep, 40 query heads
    (no head padding: neither one chip nor tp=4 needs it)."""
    from repro.configs import get_config
    return dataclasses.replace(get_config("qwen2.5-32b"), num_layers=layers,
                               pad_heads_to=0)


def init_phase(cfg, rules, seed: int):
    from repro.models import nn
    from repro.models.registry import init_params
    t0 = time.perf_counter()
    params, _ = init_params(cfg, jax.random.PRNGKey(seed), rules)
    jax.block_until_ready(params)
    log(f"phase 1 config: {cfg.name} d_model={cfg.d_model} "
        f"heads={cfg.n_q}/{cfg.n_kv}x{cfg.hd} d_ff={cfg.d_ff} "
        f"vocab={cfg.vocab_size} qkv_bias={cfg.qkv_bias} dtype={cfg.dtype}; "
        f"depth cut to {cfg.num_layers} of 64 layers (the rest would be "
        f"pipeline stages on further chips); "
        f"{nn.param_bytes(params) / 1e9:.2f} GB of weights drawn on device "
        f"in {time.perf_counter() - t0:.1f} s")
    return params


# ---------------------------------------------------------------------------
# Phase 2: correctness on one chip.

def require_kernel(compiled, what: str) -> None:
    """A kernel path compiled for the chip holds a Mosaic custom call; in
    interpret mode it would not."""
    check("tpu_custom_call" in compiled.as_text(),
          f"phase 2: no tpu_custom_call in the compiled {what}")


def compare_logits(got, ref, what: str) -> None:
    """Log how far ``got`` is from ``ref``; fail past ``LOGIT_TOL``."""
    got = np.asarray(got, np.float32)
    ref = np.asarray(ref, np.float32)
    check(bool(np.isfinite(got).all()), f"{what}: non-finite logits")
    err = np.abs(got - ref)
    scale = max(1.0, float(np.abs(ref).max()))
    log(f"{what}: max|d|={float(err.max()):.4g} "
        f"rms={float(np.sqrt(np.mean(err ** 2))):.3g} "
        f"(bound {LOGIT_TOL} x {scale:.3g})")
    check(float(err.max()) <= LOGIT_TOL * scale, f"{what}: logits disagree")


def gb(n) -> str:
    return "not reported" if n is None else f"{n / 1e9:.2f} GB"


def decode_logits(cfg, params, tokens, *, rules, page_size, n_pages):
    """Teacher-force ``tokens`` [B, T] through the jitted paged serve step
    (state donated).  Returns (logits [T, B, V], final state, compiled)."""
    from repro.serving import engine as EG
    B, T = tokens.shape
    S_max = 2 * T
    state, _ = EG.make_decode_state(cfg, B, S_max=S_max, rules=rules,
                                    page_size=page_size, n_pages=n_pages)
    step = jax.jit(EG.make_serve_step(cfg, S_max=S_max, rules=rules,
                                      page_size=page_size),
                   donate_argnums=(1,))
    compiled = step.lower(params, state, tokens[:, :1],
                          jnp.zeros((B,), jnp.int32)).compile()
    outs = []
    for t in range(T):
        lg, state = compiled(params, state, tokens[:, t:t + 1],
                             jnp.full((B,), t, jnp.int32))
        outs.append(np.asarray(lg))
    return np.stack(outs), state, compiled


def correctness_phase(cfg, params, rules, *, seed: int, prompt: int = 64,
                      page_size: int = 16, n_pages: int = 4096) -> None:
    """``n_pages`` is a multiple of the probe kernel's 2048-cell block, so
    the rebuild below goes through the kernel and not its fallback."""
    from repro.models.registry import get_model
    from repro.serving import engine as EG
    from repro.serving import page_table as PT

    tokens = jax.random.randint(jax.random.PRNGKey(seed + 1), (2, prompt),
                                0, cfg.vocab_size, jnp.int32)
    fwd = jax.jit(lambda p, t: get_model(cfg).forward(cfg, p, t)[0])
    ref = np.asarray(fwd(params, tokens)).transpose(1, 0, 2)   # [T, B, V]

    gather_cfg = dataclasses.replace(cfg, fused_kernel=False)
    plain, state, _ = decode_logits(gather_cfg, params, tokens, rules=rules,
                                    page_size=page_size, n_pages=n_pages)
    compare_logits(plain, ref,
                   f"phase 2 jnp-gather decode vs forward over 2x{prompt} "
                   "tokens")
    del state

    # the TPU's default decode attention is the fused kernel
    report = EG.fallback_report(cfg, rules)
    check(report["fused_kernel"] == "ok",
          f"phase 2: fused kernel fell back: {report['fused_kernel']}")
    check(report["probe_strategy"] == "linear: ok",
          f"phase 2: probe kernel fell back: {report['probe_strategy']}")
    fused, state, compiled = decode_logits(cfg, params, tokens, rules=rules,
                                           page_size=page_size,
                                           n_pages=n_pages)
    require_kernel(compiled, "fused decode step")
    compare_logits(fused, plain, "phase 2 fused kernel vs the jnp gather")

    # the Section 4.3 rebuild: kernel-served block table == the oracle's
    maxP = state["block_table"].shape[1]
    pt = PT.for_strategy(cfg.probe_strategy)
    with_kernel = EG.rebuild_page_table(state, n_pages=n_pages,
                                        use_kernel=True)
    oracle = EG.rebuild_page_table(state, n_pages=n_pages)
    bt_k = np.asarray(with_kernel["block_table"])
    bt_o = np.asarray(oracle["block_table"])
    check(np.array_equal(bt_k, bt_o),
          "phase 2: probe-kernel block table differs from the oracle's")
    mism = int(pt.verify_block_table(with_kernel["table"],
                                     with_kernel["seq_ids"],
                                     with_kernel["pos"],
                                     with_kernel["block_table"],
                                     page_size=page_size))
    check(mism == 0, f"phase 2: rebuilt block table has {mism} bad rows")
    probe = jax.jit(lambda t, s: pt.rebuild_block_table(t, s, maxP,
                                                        use_kernel=True))
    require_kernel(probe.lower(with_kernel["table"],
                               with_kernel["seq_ids"]).compile(),
                   "probe-kernel rebuild")
    log(f"phase 2 rebuild: probe-kernel block table == oracle "
        f"({int((bt_k >= 0).sum())} live entries, 0 mismatches); "
        f"tpu_custom_call in both kernel paths")


# ---------------------------------------------------------------------------
# Phase 3: serving through the entry point's own loop.

SERVE_ARGS = ["--batch", "32", "--max-len", "4096", "--page-size", "16",
              "--megastep", "8", "--requests", "64",
              "--prompt-len", "256,1024", "--max-new", "64,256",
              "--slo-fraction", "0", "--rounds", "10000",
              "--steps-per-round", "128", "--fail-on-abort"]


def serve_phase(cfg, params, rules, argv, *, phase: str) -> None:
    from repro.launch import serve
    args = serve.parse_args(argv)
    s = serve.run(cfg, params, args, rules=rules)
    log(f"{phase} serving: {s['requests']}/{args.requests} requests "
        f"completed, {s['tokens']} tokens sampled + {s['prompt_tokens']} "
        f"prompt tokens, aborts={s['aborts']} grows={s['pool_grows']}, "
        f"wall {s['wall_s']:.1f} s, of which compile {s['compile_s']:.1f} s "
        f"for {s['compiles']} programs (cache hits {s['cache_hits']}, "
        f"writes {s['cache_writes']}) and trace {s['trace_s']:.1f} s, "
        f"peak_bytes_in_use={gb(s['peak_bytes_in_use'])}")
    check(s["rc"] == 0 and s["drained"], f"{phase}: serving run failed")
    check(s["requests"] == args.requests,
          f"{phase}: {s['requests']}/{args.requests} requests completed")
    check(s["aborts"] == 0 and s["pool_grows"] == 0,
          f"{phase}: aborts={s['aborts']} grows={s['pool_grows']}")


# ---------------------------------------------------------------------------
# Four chips: manual tensor-parallel decode against GSPMD.

TP_SERVE_ARGS = ["--batch", "8", "--max-len", "1024", "--page-size", "16",
                 "--megastep", "8", "--requests", "16",
                 "--prompt-len", "64,256", "--max-new", "32,64",
                 "--slo-fraction", "0", "--rounds", "10000",
                 "--steps-per-round", "128", "--fail-on-abort"]


def tp_phase(cfg, params, mesh, *, seed: int, prompt: int = 32,
             page_size: int = 16, K: int = 8) -> None:
    from repro.dist.sharding import serve_manual_rules, serve_rules
    from repro.serving import engine as EG

    mcfg = dataclasses.replace(cfg, tp_impl="manual")
    mrules, grules = serve_manual_rules(mesh), serve_rules(mesh)
    report = EG.fallback_report(mcfg, mrules)
    check(report["decode_tp"] == "ok",
          f"tp: manual decode fell back: {report['decode_tp']}")
    tokens = jax.random.randint(jax.random.PRNGKey(seed + 1), (2, prompt),
                                0, cfg.vocab_size, jnp.int32)
    n_pages = 1024
    manual, mstate, _ = decode_logits(mcfg, params, tokens, rules=mrules,
                                      page_size=page_size, n_pages=n_pages)
    gspmd, gstate, _ = decode_logits(cfg, params, tokens, rules=grules,
                                     page_size=page_size, n_pages=n_pages)
    compare_logits(manual, gspmd,
                   f"tp decode, manual vs gspmd over 2x{prompt} tokens")

    # one K-token megastep per path from those states: greedy tokens, and
    # the allocator's positions, which must be equal
    tok0 = tokens[:, -1:]
    outs = {}
    for name, c, r, st in (("manual", mcfg, mrules, mstate),
                           ("gspmd", cfg, grules, gstate)):
        mega = jax.jit(EG.make_serve_megastep(c, S_max=2 * prompt, K=K,
                                              rules=r, page_size=page_size),
                       donate_argnums=(1,))
        toks, st = mega(params, st, tok0)
        outs[name] = (np.asarray(toks), np.asarray(st["pos"]))
    agree = float((outs["manual"][0] == outs["gspmd"][0]).mean())
    check(np.array_equal(outs["manual"][1], outs["gspmd"][1]),
          "tp: megastep positions differ between paths")
    log(f"tp megastep K={K}: greedy tokens agree on {agree:.0%} "
        f"(manual {outs['manual'][0].tolist()} / gspmd "
        f"{outs['gspmd'][0].tolist()})")
    del mstate, gstate, outs
    gc.collect()
    serve_phase(mcfg, params, mrules, TP_SERVE_ARGS, phase="tp")


# ---------------------------------------------------------------------------

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, default=1, choices=[1, 4],
                    help="4: run only the tensor-parallel decode phase")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    device = device_check(args.chips)

    from repro.launch.compile_cache import enable_compile_cache
    from repro.launch.mesh import make_serve_mesh
    from repro.launch.serve import peak_bytes_in_use, serve_rules_for
    log(f"compile cache: {enable_compile_cache()}")
    mesh = make_serve_mesh()
    if args.chips == 4:
        cfg = qwen_config(16)
        params = init_phase(cfg, serve_rules_for(cfg, mesh), args.seed)
        tp_phase(cfg, params, mesh, seed=args.seed)
    else:
        cfg = qwen_config(4)
        rules = serve_rules_for(cfg, mesh)
        params = init_phase(cfg, rules, args.seed)
        correctness_phase(cfg, params, rules, seed=args.seed)
        gc.collect()
        log(f"phase 2 peak_bytes_in_use so far: {gb(peak_bytes_in_use())}")
        serve_phase(cfg, params, rules, SERVE_ARGS, phase="phase 3")
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
