"""Find an open-loop cell's knee once, by a sweep of offered rates on the
chip, in one process:

    python3 -m chipbench.sweep --workload <cell> --rates 0.5,1,2 --seconds <s> --seed <n>

For each rate it runs the cell's lead-in and window at that rate and prints
one JSON line: requests due and finished in the window, the queue at its
start and at its end, tokens per second and the time-to-first-token median
and 90th percentile.  The knee is the highest rate whose queue does not
grow through the window.
The cell itself then offers a fixed rate; nothing searches at run time.
"""
from __future__ import annotations

import argparse
import json

import numpy as np


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args(argv)

    from chipbench import e2e, harness, spec
    s = harness.setup(spec.ROOT, args.workload)
    params = harness.draw_weights(s, args.seed)
    for rate in (float(r) for r in args.rates.split(",")):
        server = harness.make_server(s, params, args.seed)
        server.warm_up(np.random.default_rng([args.seed, 2]))
        queue = {}

        def at(when):
            return lambda: queue.setdefault(when, len(server.sched.queue))
        rep = harness.drive(server, dict(s.mix, rate_per_s=rate), args.seed,
                            args.seconds, on_window_start=at("start"),
                            on_window_end=at("end"), drain_cap=0.0)
        recs = list(server.records.values())
        T = args.seconds
        ttft = e2e.ttft_samples(recs, T)
        done = [r for r in server.finished()
                if r.req_id in server.records]
        print(json.dumps({
            "rate_per_s": rate,
            "due": len(ttft),
            "queue_at_start": queue["start"],
            "queue_at_end": queue["end"],
            "tokens_per_s": e2e.tokens_delivered(recs, T) / T,
            "ttft_p50_ms": 1e3 * e2e.percentile(ttft, 50),
            "ttft_p90_ms": 1e3 * e2e.percentile(ttft, 90),
            "finished": len(done),
            "unserved": len(rep["unserved"])}), flush=True)
        server.release()
        del server
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
