"""Entry point: one run of one benchmark cell.

    python3 -m chipbench.run --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Run from the root of a checkout, on a machine whose JAX sees the TPU chips
the cell asks for.  The last line of standard output is the run's result
(one JSON object); a run that finds no TPU, or fewer chips than the cell
needs, exits non-zero and prints none.  The numbers that decide ``correct``
are the last lines of standard error, each beside its limit.
"""
import time

T_START = time.perf_counter()           # set-up is counted from here

import argparse  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402


def _finite(x):
    """JSON has no infinity: a value that is (a request that never got its
    first token, a run with nothing to compare) prints as 1e300."""
    if isinstance(x, float) and not math.isfinite(x):
        return 1e300
    if isinstance(x, dict):
        return {k: _finite(v) for k, v in x.items()}
    if isinstance(x, list):
        return [_finite(v) for v in x]
    return x


def parse_args(argv=None) -> argparse.Namespace:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=[0, 1], default=0)
    return ap.parse_args(argv)


def main(argv=None) -> int:
    args = parse_args(argv)
    from chipbench import harness, spec
    result = harness.run(spec.ROOT, args.workload, args.seed, args.seconds,
                         bool(args.trace), T_START)
    print(json.dumps(_finite(result)), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
