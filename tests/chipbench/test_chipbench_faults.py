"""``correct`` comes out false when the timed path is broken underneath,
for each fault a serving cell can have, and the float8 control lies
clearly above what sound runs read.  Smoke size, on the CPU: the test
steers the look for a chip and plants each fault in the megastep after the
warm-up, so that the window and everything after it run as in a real run."""
import json
import os
import subprocess
import sys
import textwrap
import time

import jax.numpy as jnp
import numpy as np
import pytest

from chipbench_smoke import ROOT, TP_CELL


@pytest.fixture
def short_drain(monkeypatch):
    from chipbench import harness
    monkeypatch.setattr(harness, "DRAIN_CAP_S", 3.0)


def _plant(monkeypatch, fault):
    """Replace the megastep with ``fault(megastep, server)`` once the
    warm-up has compiled and run the real one."""
    from chipbench import harness
    warm = harness.Server.warm_up

    def warm_then_break(self, rng):
        warm(self, rng)
        self.srv.mega_fn = fault(self.srv.mega_fn, self)
    monkeypatch.setattr(harness.Server, "warm_up", warm_then_break)


def _state_unchanged(mega, server):
    def step(params, state, tokens, *rest):
        return jnp.repeat(tokens, server.K, axis=1), state
    return step


def _token_altered(mega, server):
    V = server.cfg.vocab_size

    def step(*args):
        toks, st = mega(*args)
        return (toks + 1) % V, st
    return step


def _half_batch_left_out(mega, server):
    half = server.B // 2

    def step(params, state, tokens, stop_len, forced, fmask):
        return mega(params, state, tokens, stop_len.at[half:].set(0),
                    forced, fmask)
    return step


FAULTS = {"state_unchanged": _state_unchanged,
          "token_altered": _token_altered,
          "half_batch_left_out": _half_batch_left_out}


def _run(root, workload, seed):
    from chipbench import harness
    return harness.run(root, workload, seed, 2.0, False, time.perf_counter())


@pytest.mark.parametrize("workload", ["qwen-smoke.open",
                                      "mamba-smoke.backlog"])
@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_fault_makes_the_run_incorrect(smoke_root, on_cpu, short_drain,
                                       monkeypatch, workload, fault):
    _plant(monkeypatch, FAULTS[fault])
    res = _run(smoke_root, workload, seed=3)
    assert res["correct"] is False, res["checks"]


@pytest.mark.parametrize("workload", ["qwen-smoke.open",
                                      "mamba-smoke.backlog"])
def test_control_reads_above_sound_runs(smoke_root, on_cpu, workload):
    """The float8 control on the same sample: its widest gap is at least
    three times the program's, on each of three seeds."""
    from chipbench import check, harness, spec, weights
    from repro.models.registry import get_model
    bench = spec.load_benchmark(smoke_root)
    c = spec.cell(bench, workload)
    conf = spec.config(bench, c["config"], smoke_root)
    mix = spec.traffic(c["traffic"], smoke_root)
    cfg = harness.model_config(conf)
    ref = spec.reference(conf["family"], smoke_root)
    limit = conf["limits"]["logit_gap"]
    readings = []
    for seed in (1, 2, 3):
        params = weights.draw(get_model(cfg).init, cfg, seed,
                              conf["weight_draw"])
        server = harness.Server(cfg, params, conf, None, seed,
                                ref.lane_flops)
        server.warm_up(np.random.default_rng([seed, 2]))
        harness.drive(server, mix, seed, 2.0)
        prog, ctrl, n = check.control_gaps(
            ref, params, conf["model"], server.finished(),
            np.random.default_rng([seed, 3]))
        assert n > 0
        readings.append((seed, prog, ctrl))
    assert all(p <= limit < c for _, p, c in readings), readings
    assert min(c for _, _, c in readings) >= \
        3 * max(p for _, p, _ in readings), readings


_TP_SCRIPT = textwrap.dedent("""
    import json, pathlib, sys, time
    sys.path.insert(0, {root!r}); sys.path.insert(0, {tests!r})
    import jax
    import chipbench_smoke
    from chipbench import harness
    harness.device_check = lambda chips: {{"platform": "cpu",
                                          "kind": "cpu", "count": 4}}
    harness.enable_cache = lambda: None
    harness.DRAIN_CAP_S = 3.0
    root = chipbench_smoke.write_smoke_root(pathlib.Path({tmp!r}))
    out = {{}}
    out["sound"] = harness.run(root, {cell!r}, 5, 2.0, False,
                               time.perf_counter())["correct"]
    # the exchange between chips left out: every psum returns the chip's
    # own partial sum
    jax.lax.psum = lambda x, axis_name, **k: x
    jax.clear_caches()
    out["no_exchange"] = harness.run(root, {cell!r}, 5, 2.0, False,
                                     time.perf_counter())["correct"]
    print(json.dumps(out))
""")


def test_exchange_left_out_makes_the_tp_run_incorrect(tmp_path):
    script = _TP_SCRIPT.format(root=str(ROOT), tests=str(ROOT / "tests" /
                                                         "chipbench"),
                               tmp=str(tmp_path / "root"), cell=TP_CELL[0])
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=str(ROOT / "src"))
    p = subprocess.run([sys.executable, "-c", script], env=env,
                       capture_output=True, text=True, timeout=900)
    assert p.returncode == 0, p.stderr[-3000:]
    out = json.loads(p.stdout.strip().splitlines()[-1])
    assert out == {"sound": True, "no_exchange": False}, p.stderr[-3000:]


def test_calibration_judges_the_control_not_correct(smoke_root, on_cpu,
                                                    tmp_path, capsys):
    """The calibration passes each reading through the run's own judgement:
    the program's seeds come out correct and the float8 control does not."""
    from chipbench import calibrate
    out = tmp_path / "cal.jsonl"
    assert calibrate.main(["--workload", "qwen-smoke.open", "--seeds",
                           "4,5", "--seconds", "2", "--controls", "1",
                           "--out", str(out)], root=smoke_root) == 0
    rows = [json.loads(x) for x in out.read_text().splitlines()]
    assert [r["reading"] for r in rows] == ["program", "control", "program"]
    assert all(r["correct"] for r in rows if r["reading"] == "program"), rows
    assert [r["correct"] for r in rows if r["reading"] == "control"] == \
        [False], rows


def test_state_left_by_the_lane_before_makes_the_run_incorrect(
        smoke_root, on_cpu, short_drain, monkeypatch):
    """The recurrent state a lane's previous request left is not cleared at
    admission: every request of the window sits in a lane the warm-up
    used, and starts from that request's state."""
    from chipbench import harness
    warm = harness.Server.warm_up

    def warm_then_break(self, rng):
        warm(self, rng)
        self.srv._reset_recurrent_state = lambda slots: None
    monkeypatch.setattr(harness.Server, "warm_up", warm_then_break)
    res = _run(smoke_root, "mamba-smoke.backlog", seed=3)
    assert res["correct"] is False, res["checks"]
