"""Benchmark harness: one module per paper table/claim + the roofline
aggregation.  ``python -m benchmarks.run [--fast] [--only name]``."""
from __future__ import annotations

import argparse
import json
import os
import time


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--fast", action="store_true",
                    help="reduced sizes (CI)")
    ap.add_argument("--smoke", action="store_true",
                    help="tiny sizes: CI gate that every perf script stays "
                         "runnable on CPU (implies --fast)")
    ap.add_argument("--only", default=None,
                    choices=["space", "steps", "reuse", "throughput",
                             "kernels", "roofline"],
                    help="run a single bench")
    args = ap.parse_args()
    fast = args.fast or args.smoke

    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()

    from benchmarks import (bench_kernels, bench_reuse, bench_roofline,
                            bench_space, bench_steps, bench_throughput)
    benches = {
        "space": lambda: bench_space.run(),
        "steps": lambda: bench_steps.run(fast=fast),
        "reuse": lambda: bench_reuse.run(fast=fast),
        "throughput": lambda: bench_throughput.run(fast=fast),
        "kernels": lambda: bench_kernels.run(fast=fast),
        "roofline": lambda: bench_roofline.run(),
    }
    if args.only:
        benches = {args.only: benches[args.only]}

    import jax

    results = {}
    for name, fn in benches.items():
        print(f"\n=== {name} " + "=" * (60 - len(name)))
        t0 = time.time()
        results[name] = fn()
        print(f"[{name}] {time.time() - t0:.1f}s")
        # each retained XLA:CPU executable holds mmap'd JIT code; a full
        # sweep accumulates enough to exhaust vm.max_map_count and segfault
        # the next section's compile — caches are per-section state anyway
        jax.clear_caches()
    os.makedirs("results", exist_ok=True)
    with open("results/benchmarks.json", "w") as f:
        json.dump(results, f, indent=1, default=str)
    print("\nall benchmarks done -> results/benchmarks.json")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
