"""Entry points: the serving loop behind ``python -m repro.launch.serve`` as
a callable, the jitted parameter init, and ``chip_smoke.py``'s refusal to
run without a TPU."""
import os
import pathlib
import subprocess
import sys

import jax
import numpy as np

from repro.configs import get_smoke_config
from repro.launch import serve
from repro.launch.mesh import make_mesh
from repro.models.registry import get_model, init_params

ROOT = pathlib.Path(__file__).resolve().parents[1]


def test_init_params_matches_eager_init():
    cfg = get_smoke_config("qwen2.5-32b")
    key = jax.random.PRNGKey(0)
    jitted, axes = init_params(cfg, key)
    eager, eager_axes = get_model(cfg).init(cfg, key)
    assert axes == eager_axes
    assert jax.tree.structure(jitted) == jax.tree.structure(eager)
    for a, b in zip(jax.tree.leaves(jitted), jax.tree.leaves(eager)):
        assert a.dtype == b.dtype
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_serve_run_drains_fixed_workload():
    """``serve.run`` on the entry point's own mesh and rules: a seeded
    workload drains with no ABORT, and the summary counts what ran."""
    cfg = get_smoke_config("qwen2.5-32b")
    mesh = make_mesh((1, 1), ("data", "model"), devices=jax.devices()[:1])
    rules = serve.serve_rules_for(cfg, mesh)
    params, _ = init_params(cfg, jax.random.PRNGKey(0), rules)
    args = serve.parse_args([
        "--batch", "4", "--max-len", "32", "--page-size", "4",
        "--megastep", "4", "--requests", "6", "--prompt-len", "4,10",
        "--max-new", "3,6", "--slo-fraction", "0", "--rounds", "200",
        "--fail-on-abort"])
    s = serve.run(cfg, params, args, rules=rules)
    assert s["rc"] == 0 and s["drained"]
    assert s["requests"] == 6 and s["aborts"] == 0
    assert 6 * 4 <= s["prompt_tokens"] <= 6 * 10
    assert 6 * 3 <= s["tokens"] <= 6 * 6
    assert s["wall_s"] > 0 and s["compile_s"] > 0


def test_chip_smoke_refuses_cpu():
    """No CPU fallback: without a TPU the script stops at phase 0, exits
    non-zero and never prints its result line."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run([sys.executable, "chip_smoke.py"], cwd=ROOT, env=env,
                       capture_output=True, text=True, timeout=300)
    assert r.returncode != 0
    assert "phase 0" in r.stdout + r.stderr
    assert '"ok"' not in r.stdout
