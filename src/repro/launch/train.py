"""Production train runner: data -> train_step -> checkpoint, wired with the
fault-tolerance layer (watchdog, straggler monitor, SDC canary, elastic
restore).  Runs the same loop at every scale: smoke configs on one CPU
device, full configs on the production mesh.

Usage (CPU smoke):
  PYTHONPATH=src python -m repro.launch.train --arch qwen2.5-32b --smoke \
      --steps 20 --batch 4 --seq 64 --ckpt-dir /tmp/ckpt
"""
from __future__ import annotations

import argparse
import time

import jax
import numpy as np

from repro.configs import ARCH_IDS, get_config, get_smoke_config
from repro.dist import fault_tolerance as FT
from repro.dist import sharding as SH
from repro.launch.compile_cache import enable_compile_cache
from repro.training import checkpoint as CKPT
from repro.training import data as DATA
from repro.training import train_step as TS


class TrainRunner:
    """Checkpointed, watchdogged train loop (restartable by construction:
    batches are a pure function of step)."""

    def __init__(self, cfg, *, rules=None, ckpt_dir=None, ckpt_every=50,
                 deadline_s=3600.0, dedup=False):
        self.cfg = cfg
        self.rules = rules
        self.step_fn = jax.jit(TS.make_train_step(cfg, rules=rules))
        self.ckpt = (CKPT.CheckpointManager(ckpt_dir)
                     if ckpt_dir else None)
        self.ckpt_every = ckpt_every
        self.watchdog = FT.StepWatchdog(deadline_s)
        self.straggler = FT.StragglerMonitor()
        self.dedup = DATA.DedupState() if dedup else None
        self.canary_fp = None

    def init_or_restore(self, key):
        state, axes = TS.init_state(self.cfg, key)
        self.axes = axes
        start = 0
        if self.ckpt is not None and CKPT.latest_step(self.ckpt.dir) is not None:
            state, start = self.ckpt.restore_latest(state, rules=self.rules)
            print(f"[train] restored checkpoint at step {start}")
        return state, start

    def run(self, *, batch: int, seq_len: int, steps: int, seed: int = 0,
            log_every: int = 10):
        state, start = self.init_or_restore(jax.random.PRNGKey(seed))
        it = DATA.make_batch_iterator(self.cfg, batch=batch, seq_len=seq_len,
                                      seed=seed, start_step=start,
                                      dedup=self.dedup)
        losses = []
        for step, b in it:
            if step >= steps:
                break
            b.pop("keep", None)
            b.pop("dup_frac", None)
            self.watchdog.arm(step)
            t0 = time.monotonic()
            state, metrics = self.step_fn(state, b)
            loss = float(metrics["loss"])   # sync point
            dt = time.monotonic() - t0
            self.watchdog.check()
            verdict = self.straggler.observe(step, dt)
            if verdict == "replan":
                print(f"[train] step {step}: persistent straggler — a real "
                      f"deployment would re-shard / swap in a hot spare")
            losses.append(loss)
            if step % log_every == 0:
                print(f"[train] step {step} loss {loss:.4f} "
                      f"gnorm {float(metrics['grad_norm']):.3f} "
                      f"lr {float(metrics['lr']):.2e} {dt*1e3:.0f}ms")
            if self.ckpt is not None and (step + 1) % self.ckpt_every == 0:
                self.ckpt.save_async(step + 1, state, self.axes)
        if self.ckpt is not None:
            self.ckpt.save_async(steps, state, self.axes)
            self.ckpt.wait()
        return state, losses


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen2.5-32b", choices=sorted(ARCH_IDS))
    ap.add_argument("--smoke", action="store_true",
                    help="reduced config (CPU-runnable)")
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--dedup", action="store_true")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    enable_compile_cache()

    cfg = get_smoke_config(args.arch) if args.smoke else get_config(args.arch)
    runner = TrainRunner(cfg, ckpt_dir=args.ckpt_dir,
                         ckpt_every=args.ckpt_every, dedup=args.dedup)
    t0 = time.time()
    _, losses = runner.run(batch=args.batch, seq_len=args.seq,
                           steps=args.steps, seed=args.seed)
    if not losses:
        print(f"[train] checkpoint already at step >= {args.steps}; "
              f"nothing to do")
        return 0
    print(f"[train] {args.steps} steps in {time.time()-t0:.1f}s; "
          f"loss {losses[0]:.4f} -> {losses[-1]:.4f}")
    assert np.isfinite(losses).all(), "NaN/inf loss"
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
