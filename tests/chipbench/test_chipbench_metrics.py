"""End-to-end metric arithmetic on synthetic delivery logs, and the
megastep's FLOP count (each family's ``reference/<family>.py``) against
``launch/flops_model``."""
import dataclasses
import math

import pytest

from chipbench import e2e, spec
from chipbench.e2e import Record

FAMILY = {"qwen2.5-32b": "qwen2", "mamba2-2.7b": "mamba2"}


def test_percentile_is_nearest_rank():
    xs = list(range(1, 101))
    assert e2e.percentile(xs, 90) == 90
    assert e2e.percentile(xs, 95) == 95
    assert e2e.percentile([3.0], 95) == 3.0
    assert e2e.percentile([1.0, 2.0, math.inf], 50) == 2.0
    with pytest.raises(ValueError):
        e2e.percentile([], 50)


def test_k_token_bursts_give_one_sample_per_token():
    # K=8 bursts every 0.4 s after the first token at 1.0 s
    r = Record(due=0.5, deliveries=[(1.0, 8), (1.4, 8), (1.8, 8)])
    s = e2e.tpot_samples([r], window_s=10.0)
    assert len(s) == 16
    assert all(abs(x - 0.05) < 1e-12 for x in s)
    assert e2e.ttft_samples([r], 10.0) == [0.5]
    assert e2e.tokens_delivered([r], 10.0) == 24


def test_uneven_burst_splits_its_gap():
    r = Record(due=0.0, deliveries=[(0.2, 1), (0.5, 3), (0.6, 1)])
    assert sorted(e2e.tpot_samples([r], 1.0)) == pytest.approx(
        [0.1, 0.1, 0.1, 0.1])


def test_window_bounds_the_samples():
    r = Record(due=0.0, deliveries=[(0.5, 8), (1.5, 8), (2.5, 8)])
    assert e2e.tokens_delivered([r], 2.0) == 16
    assert len(e2e.tpot_samples([r], 2.0)) == 8
    late = Record(due=3.0, deliveries=[(3.5, 8)])
    assert e2e.ttft_samples([r, late], 2.0) == [0.5]


def test_request_never_served_is_larger_than_every_sample():
    served = [Record(due=float(i), deliveries=[(i + 0.1, 4)])
              for i in range(8)]
    stalled = [Record(due=8.0), Record(due=9.0)]
    s = e2e.ttft_samples(served + stalled, 10.0)
    assert s.count(math.inf) == 2
    # two of ten never served: the p90 is one of them, the median is not
    assert e2e.percentile(s, 90) == math.inf
    assert e2e.percentile(s, 50) == pytest.approx(0.1)
    assert e2e.tpot_samples(stalled, 10.0) == []


def test_spread_is_interquartile_share_of_median():
    assert e2e.spread([1.0, 1.0, 1.0, 1.0]) == 0.0
    v = [90.0, 95.0, 100.0, 105.0, 110.0]
    q1, q3 = 92.5, 107.5
    assert e2e.spread(v) == pytest.approx((q3 - q1) / 100.0)


@pytest.mark.parametrize("arch", ["qwen2.5-32b", "mamba2-2.7b"])
def test_flops_match_the_flops_model_at_smoke_shape(arch):
    """B lanes, one token each at context S: the benchmark's count equals
    ``executed_flops``'s decode count once its page-capacity over-read is
    taken out of attention (heads are unpadded at smoke size)."""
    from repro.configs import get_smoke_config
    from repro.configs.base import ShapeConfig
    from repro.launch import flops_model as FM
    cfg = get_smoke_config(arch)
    B, S = 4, 96
    fb = FM.executed_flops(cfg, ShapeConfig("d", S, B, "decode"))
    want = (fb.attn_proj + fb.attn_score / FM.PAGE_CAPACITY_WASTE + fb.mlp
            + fb.ssm + fb.logits + fb.router)
    lane_flops = spec.reference(FAMILY[arch]).lane_flops
    got = B * lane_flops(cfg, S - 1, S)
    assert got == pytest.approx(want, rel=1e-12)


def test_flops_count_real_context_and_unpadded_heads():
    from repro.configs import get_config
    cfg = dataclasses.replace(get_config("qwen2.5-32b"), num_layers=4)
    assert cfg.n_q == 48                  # padded for TP; not counted
    lane_flops = spec.reference("qwen2").lane_flops
    one = lane_flops(cfg, 0, 1)
    # a lane that moved 8 positions from 100 attends 101..108 keys
    eight = lane_flops(cfg, 100, 108)
    attn = 4 * 2 * 2 * 40 * 128
    assert eight == pytest.approx(
        8 * (one - attn * 1) + attn * sum(range(101, 109)))
    assert lane_flops(cfg, 5, 5) == 0.0


def test_gap_numbers_and_the_limits_that_judge_them():
    """``logit_gap`` is the widest gap of the sample; ``early_mean_gap`` the
    mean over each request's first served tokens; a number is judged only
    where the configuration gives it a limit."""
    import numpy as np
    from chipbench import check
    n = check.EARLY_TOKENS
    gaps = [np.r_[np.full(n, 0.2), np.full(100, 0.0), 3.0],
            np.r_[np.zeros(n), 0.5]]
    nums = check.gap_numbers(gaps)
    assert nums["logit_gap"] == 3.0
    assert nums["early_mean_gap"] == pytest.approx(0.1)
    assert check.gap_numbers([]) == {"logit_gap": math.inf,
                                     "early_mean_gap": math.inf}
    only_widest = check.judge({"logit_gap": 4.0}, nums, unserved=0,
                              block_table_mismatches=None)
    assert set(only_widest) == {"logit_gap", "unserved"}
    assert check.passes(only_widest)
    both = check.judge({"logit_gap": 4.0, "early_mean_gap": 0.05}, nums,
                       unserved=0, block_table_mismatches=0)
    assert set(both) == {"logit_gap", "early_mean_gap", "unserved",
                         "block_table_mismatches"}
    assert not check.passes(both)
