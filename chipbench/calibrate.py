"""The readings a cell's limits are set from, on the chip, in one process:

    python3 -m chipbench.calibrate --workload <cell> --seeds 1,2,3 --seconds <s> [--controls 3]

For each seed it runs the cell as a run does (weights from the seed,
warm-up, the lead-in, the window at the cell's own load, the drain) and
compares a sample of finished requests against the float32 reference: the
program's served tokens give the lower reading.  The first ``--controls``
seeds also read the float8 control on the same sample, the upper reading.
Each reading goes through ``check.judge`` with the configuration's limits,
so a row also says whether the run, and the control, would pass.  One JSON
line a reading, all of them written to ``--out`` too.  The benchmark's own
runs never run the control.
"""
from __future__ import annotations

import argparse
import gc
import json
import pathlib
import time

import numpy as np


def main(argv=None, root=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True,
                    help="comma-separated seeds")
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--controls", type=int, default=3,
                    help="read the control on this many of the seeds")
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)

    from chipbench import check, harness, spec
    root = spec.ROOT if root is None else root
    s = harness.setup(root, args.workload)
    ref, limits, model = s.ref, s.conf["limits"], s.conf["model"]
    rows = []

    def emit(row):
        print(json.dumps(row), flush=True)
        rows.append(row)

    for i, seed in enumerate(int(x) for x in args.seeds.split(",")):
        t0 = time.perf_counter()
        params = harness.draw_weights(s, seed)
        server = harness.make_server(s, params, seed)
        server.warm_up(np.random.default_rng([seed, 2]))
        rep = harness.drive(server, s.mix, seed, args.seconds)
        mism = server.block_table_mismatches()
        finished = server.finished()
        server.release()
        unserved = len(rep["unserved"])
        rng = lambda: np.random.default_rng([seed, 3])  # noqa: E731
        numbers = check.gap_numbers(check.token_gaps(ref, params, model,
                                                     finished, rng()))
        judged = check.judge(limits, numbers, unserved=unserved,
                             block_table_mismatches=mism)
        emit({"workload": args.workload, "seed": seed, "reading": "program",
              **numbers, "correct": check.passes(judged),
              "finished": len(finished), "unserved": unserved,
              "block_table_mismatches": mism,
              "seconds": time.perf_counter() - t0})
        if i < args.controls:
            _, low, _ = check.control_gaps(ref, params, model, finished,
                                           rng())
            judged = check.judge(limits, {"logit_gap": low},
                                 unserved=unserved,
                                 block_table_mismatches=mism)
            emit({"workload": args.workload, "seed": seed,
                  "reading": "control", "logit_gap": low,
                  "correct": check.passes(judged)})
        del server, params, finished
        gc.collect()
    if args.out:
        pathlib.Path(args.out).parent.mkdir(parents=True, exist_ok=True)
        pathlib.Path(args.out).write_text(
            "".join(json.dumps(r) + "\n" for r in rows))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
