"""CPU rehearsal of a whole run at smoke size: the harness's window drives
``ContinuousBatcher`` and prints a well-formed result; the real entry point
finds no TPU here and exits non-zero with no result."""
import json
import os
import subprocess
import sys
import time

import pytest

from chipbench_smoke import ROOT, SMOKE_CELLS


def _run(root, workload, seed, trace=False, seconds=2.0):
    from chipbench import harness
    return harness.run(root, workload, seed, seconds, trace,
                       time.perf_counter())


@pytest.mark.parametrize("workload", [w for w, _, _ in SMOKE_CELLS])
def test_run_record_is_well_formed(smoke_root, on_cpu, workload):
    res = _run(smoke_root, workload, seed=2 ** 31 + 7)
    assert set(res) >= {"correct", "attempted", "failed", "metrics",
                        "device", "checks"}
    assert list(res)[-1] == "checks"
    assert res["correct"] is True, res["checks"]
    assert res["attempted"] > 0 and res["failed"] == 0
    m = res["metrics"]
    assert m["tokens_per_s"]["value"] > 0
    assert m["tpot_p95_ms"]["value"] > 0
    assert m["setup_s"]["value"] > 0
    assert ("ttft_p90_ms" in m) == workload.startswith("qwen")
    assert res["device"]["platform"] == "cpu"
    json.dumps(res)


def test_traced_run_reads_the_trace(smoke_root, on_cpu):
    res = _run(smoke_root, "qwen-smoke.open", seed=5, trace=True)
    m = res["metrics"]
    # the CPU has no device plane: the device readers find nothing and
    # stay out of the line, the host spans and the counters are there,
    # the harness's and the program's
    assert m["host_ms_per_round"]["value"] > 0
    assert "probe_len_p99" in m
    assert m["host_critical_ms_per_round"]["value"] > 0
    assert m["free_ms_per_round"]["value"] >= 0
    assert m["compiles_per_round"]["value"] >= 0
    assert "megastep_ms" not in m and "device_idle_share" not in m
    assert res["device"]["window_s"] > 0
    assert res["correct"] is True, res["checks"]


def test_entry_point_refuses_a_machine_without_tpu(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, "-m", "chipbench.run", "--workload",
         "qwen2.5-32b.decode", "--seed", "1", "--seconds", "1",
         "--trace", "0"], cwd=ROOT, env=env, capture_output=True,
        text=True, timeout=300)
    assert p.returncode != 0
    assert "no TPU" in p.stderr
    assert not p.stdout.strip()


def test_lead_in_fills_the_lanes_before_the_window(smoke_root, on_cpu):
    """An open-loop mix with a lead-in opens its window on busy lanes; the
    requests due in the lead-in are served and owed, but stay out of the
    time-to-first-token sample."""
    import numpy as np
    from chipbench import e2e, harness, spec
    s = harness.setup(smoke_root, "qwen-smoke.open")
    params = harness.draw_weights(s, 8)
    server = harness.make_server(s, params, 8)
    server.warm_up(np.random.default_rng([8, 2]))
    busy = {}
    rep = harness.drive(server, dict(s.mix, lead_in_s=1.0), 8, 1.0,
                        on_window_start=lambda: busy.setdefault(
                            "lanes", len(server.sched.running())))
    early = [i for i, r in server.records.items() if r.due < 0]
    assert early and set(early) <= set(rep["owed"])
    assert busy["lanes"] > 0
    recs = list(server.records.values())
    assert len(e2e.ttft_samples(recs, 1.0)) == \
        sum(0 <= r.due < 1.0 for r in recs)
    assert not rep["unserved"]
    assert spec.traffic("chat")["lead_in_s"] > 0
