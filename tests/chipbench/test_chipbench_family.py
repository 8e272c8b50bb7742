"""A model of a new family enters the benchmark by new files alone: its
configuration, its ``reference/<family>.py`` with ``logits`` and its own
FLOP count ``lane_flops``, and a ``BENCHMARK.json`` entry.  The two
families already in the benchmark count and draw exactly as before the
count moved behind the family: the same operations for the same lane
positions, the same fan-in for every leaf."""
import dataclasses
import json
import math
import shutil

import jax
import numpy as np
import pytest

from chipbench import harness, reduce, spec, weights
from chipbench_smoke import ROOT, SMOKE_SERVING, write_smoke_root

HERE = ROOT / "tests" / "chipbench"
# granite-moe-1b-a400m's smoke widths (``repro.configs``), with no token
# dropped at any capacity.  The limit lies between what sound runs read on
# the CPU, 0 to 0.39 over 37 seeds (30 of them under 0.03), and what a run
# reads with each served token altered, 3.25 to 3.73 over 6 seeds.
MOE_SMOKE = {
    "source": "test", "family": "granitemoe", "arch": "granite-moe-1b-a400m",
    "model": {"hidden_size": 64, "intermediate_size": 64,
              "num_attention_heads": 4, "num_key_value_heads": 2,
              "num_hidden_layers": 2, "vocab_size": 256,
              "num_local_experts": 4, "num_experts_per_tok": 2,
              "rope_theta": 10000.0, "rms_norm_eps": 1e-06},
    "overrides": {"num_layers": 2, "d_model": 64, "num_heads": 4,
                  "num_kv_heads": 2, "d_ff": 64, "vocab_size": 256,
                  "head_dim": 16, "num_experts": 4, "experts_per_token": 2,
                  "moe_capacity_factor": 100.0, "pad_heads_to": 0},
    "serving": SMOKE_SERVING,
    "weight_draw": {"embedding_std": 0.1, "bias_std": 0.1},
    "limits": {"logit_gap": 1.0}}
# the std of a normal truncated at two sigma, as a share of its scale
TRUNCATED_STD = 0.8796


def _add_family(root, family, conf, reference_source):
    """The new family's files and entries, added to a checkout-shaped
    directory; no file already there is edited."""
    pkg = root / "chipbench"
    before = {p: p.read_bytes() for p in pkg.rglob("*") if p.is_file()}
    name = conf["arch"] + "-smoke"
    (pkg / "configs" / f"{name}.json").write_text(json.dumps(conf))
    (pkg / "reference" / f"{family}.py").write_text(reference_source)
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["configs"].append({"name": name, "source": "test",
                             "file": f"chipbench/configs/{name}.json",
                             "reduced": [], "why": "test"})
    cell = f"{name}.backlog"
    bench["workloads"].append({"name": cell, "config": name,
                               "traffic": "backlog", "chips": 1,
                               "why": "test"})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    assert all(p.read_bytes() == b for p, b in before.items())
    return cell


@pytest.fixture(scope="module")
def moe_root(tmp_path_factory):
    root = write_smoke_root(tmp_path_factory.mktemp("family"))
    cell = _add_family(root, "granitemoe", MOE_SMOKE,
                       (HERE / "moe_smoke_reference.py").read_text())
    assert cell == "granite-moe-1b-a400m-smoke.backlog"
    return root, cell


def test_new_family_enters_by_files_alone(moe_root, on_cpu, monkeypatch):
    """The MoE cell runs through ``harness.run``, is judged ``correct``,
    and its ``megastep_mfu`` counts its window with the family's own
    ``lane_flops``.  The CPU trace has no device plane, so the megastep's
    device time is stood in for by one second."""
    import time
    root, cell = moe_root
    counted = []
    reference = spec.reference

    def spy(family, root=spec.ROOT):
        mod = reference(family, root)
        if family == "granitemoe":
            count = mod.lane_flops
            mod.lane_flops = lambda *a: counted.append(count(*a)) or \
                counted[-1]
        return mod
    monkeypatch.setattr(spec, "reference", spy)
    monkeypatch.setattr(reduce, "program_time",
                        lambda tr, win, name: (1.0, 1.0))
    seen = {}
    per_layer = harness.per_layer

    def keep_ctx(root, names, ctx):
        seen["ctx"] = ctx
        return per_layer(root, names, ctx)
    monkeypatch.setattr(harness, "per_layer", keep_ctx)
    res = harness.run(root, cell, 2 ** 31 + 11, 2.0, True,
                      time.perf_counter())
    assert res["correct"] is True, res["checks"]
    ctx = seen["ctx"]
    assert counted and 0 < ctx.window_flops <= sum(counted)
    assert res["metrics"]["megastep_mfu"]["value"] == pytest.approx(
        100.0 * ctx.window_flops / 1e12)


def test_family_without_lane_flops_is_refused_at_setup(tmp_path, on_cpu,
                                                       monkeypatch):
    root = write_smoke_root(tmp_path)
    logits_only = (HERE / "moe_smoke_reference.py").read_text().split(
        "\ndef lane_flops")[0]
    cell = _add_family(root, "nocount", dict(MOE_SMOKE, family="nocount"),
                       logits_only)
    drawn = []
    monkeypatch.setattr(harness, "draw_weights",
                        lambda *a: drawn.append(a))
    with pytest.raises(SystemExit) as e:
        harness.run(root, cell, 1, 1.0, False, 0.0)
    assert "chipbench/reference/nocount.py" in str(e.value)
    assert "lane_flops" in str(e.value)
    assert not drawn


def test_expert_leaves_are_drawn_at_their_fan_in():
    """Stacked expert matrices read d inputs (``wi_gate``, ``wi_up``) and f
    (``wo``), never the expert count."""
    from repro.configs import get_config
    from repro.models.registry import get_model
    cfg = dataclasses.replace(
        get_config("granite-moe-1b-a400m"), num_layers=1, d_model=256,
        d_ff=64, num_experts=8, experts_per_token=2, vocab_size=256,
        num_heads=4, num_kv_heads=2, head_dim=64)
    p = weights.draw(get_model(cfg).init, cfg, 3,
                     {"embedding_std": 0.1, "bias_std": 0.1})
    moe = p["layers"]["moe"]
    std = lambda w: float(np.asarray(w, np.float32).std()) / TRUNCATED_STD
    assert std(moe["wi_gate"]) == pytest.approx(256 ** -0.5, rel=0.05)
    assert std(moe["wi_up"]) == pytest.approx(256 ** -0.5, rel=0.05)
    assert std(moe["wo"]) == pytest.approx(64 ** -0.5, rel=0.05)
    assert std(moe["router"]) == pytest.approx(256 ** -0.5, rel=0.05)


def _positional_fan_in(names, shape):
    """The rule the benchmark drew by before fan-in came from the axes: a
    leaf under ``layers`` loses its first dim, attention's 3-d ``wo`` reads
    its first two dims, any other matrix its first; one dim is a bias."""
    inner = shape[1:] if "layers" in names else shape
    if len(inner) == 1:
        return "bias"
    if names[-1] == "wo" and len(inner) == 3:
        return inner[0] * inner[1]
    return inner[0]


@pytest.mark.parametrize("config", ["qwen2.5-32b", "mamba2-2.7b"])
def test_fan_in_of_every_leaf_is_as_before(config):
    """At the published shapes, as the cell runs them, with nothing drawn:
    every matrix leaf the draw scales by 1/sqrt(fan-in) gets the fan-in it
    had, and every bias stays a bias."""
    from repro.models.registry import get_model
    bench = spec.load_benchmark()
    cfg = harness.model_config(spec.config(bench, config))
    box = {}

    def layout(k):
        p, box["axes"] = get_model(cfg).init(cfg, k)
        return p
    flat = jax.tree_util.tree_flatten_with_path(
        jax.eval_shape(layout, jax.random.PRNGKey(0)))[0]
    drawn_by_name = ("scale", "norm", "A_log", "dt_bias", "D", "embedding")
    checked = 0
    for path, leaf in flat:
        names = [weights._key(k) for k in path]
        if names[-1] in drawn_by_name or (names[-1].startswith("conv")
                                          and len(leaf.shape) > 2):
            continue
        axes = weights.leaf_axes(box["axes"], path)
        inner = [a for a in axes if a not in weights.STACKING]
        new = ("bias" if len(inner) == 1
               else weights.fan_in(axes, leaf.shape))
        assert new == _positional_fan_in(names, leaf.shape), names
        checked += 1
    assert checked >= (9 if config.startswith("qwen") else 7)


def _lane_flops_before(c, p0, p1):
    """``flops.lane_flops`` as it was while it dispatched on the family."""
    from chipbench import flops
    n = int(p1) - int(p0)
    if n <= 0:
        return 0.0
    if c.family == "ssm":
        return n * flops.ssm_token_flops(c)
    ctx = (p0 + 1 + p1) * n / 2.0
    return n * flops.dense_token_flops(c, 0.0) + (
        c.num_layers * 2.0 * 2.0 * c.num_heads * c.hd * ctx)


@pytest.mark.parametrize("config", ["qwen2.5-32b", "mamba2-2.7b"])
def test_family_count_is_bitwise_the_old_one(config):
    """At the published shapes, over a grid of lane positions given as the
    harness gives them (numpy ints), the family's ``lane_flops`` is the old
    count to the bit."""
    bench = spec.load_benchmark()
    conf = spec.config(bench, config)
    cfg = harness.model_config(conf)
    count = spec.reference(conf["family"]).lane_flops
    pos = np.array([0, 1, 7, 8, 15, 16, 100, 511, 1535, 4095], np.int32)
    for p0 in pos:
        for k in (0, 1, 3, 8):
            p1 = p0 + np.int32(k)
            assert count(cfg, p0, p1) == _lane_flops_before(cfg, p0, p1)
            assert count(cfg, int(p0), int(p1)) == \
                _lane_flops_before(cfg, int(p0), int(p1))
