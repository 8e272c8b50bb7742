"""The traffic generator: one seed gives one stream, another seed another
order of the same work, and every mix file of the package is well formed."""
import itertools
import json

import numpy as np
import pytest

from chipbench import spec, traffic
from chipbench_smoke import ROOT

MIXES = sorted(p.stem for p in (ROOT / "chipbench" / "traffic").glob(
    "*.json"))


def _take(mix, seed, n, vocab=1000):
    return list(itertools.islice(traffic.requests(mix, seed, vocab), n))


@pytest.mark.parametrize("name", MIXES)
def test_same_seed_same_requests(name):
    mix = spec.traffic(name)
    a, b = _take(mix, 2 ** 31 + 3, 70), _take(mix, 2 ** 31 + 3, 70)
    for x, y in zip(a, b):
        assert np.array_equal(x.prompt, y.prompt)
        assert (x.max_new, x.gap_s) == (y.max_new, y.gap_s)


@pytest.mark.parametrize("name", MIXES)
def test_other_seed_other_order_same_work(name):
    mix = spec.traffic(name)
    n = int(mix["block"])
    a, b = _take(mix, 1, 2 * n), _take(mix, 2, 2 * n)
    assert [r.max_new for r in a] != [r.max_new for r in b]
    assert not np.array_equal(a[0].prompt, b[0].prompt)
    for blk in range(2):
        sl = slice(blk * n, (blk + 1) * n)
        for f in (lambda r: r.prompt.size, lambda r: r.max_new,
                  lambda r: r.gap_s):
            assert sorted(map(f, a[sl])) == sorted(map(f, b[sl]))


@pytest.mark.parametrize("name", MIXES)
def test_lengths_rate_and_fit(name):
    mix = spec.traffic(name)
    reqs = _take(mix, 7, 3 * int(mix["block"]))
    p = np.array([r.prompt.size for r in reqs])
    o = np.array([r.max_new for r in reqs])
    assert p.min() >= mix["prompt_len"]["min"]
    assert p.max() <= mix["prompt_len"]["max"]
    assert o.min() >= mix["output_len"]["min"]
    assert o.max() <= mix["output_len"]["max"]
    g = np.array([r.gap_s for r in reqs])
    if mix["loop"] == "open":
        assert g.mean() == pytest.approx(1 / mix["rate_per_s"], rel=1e-9)
        assert g.std() / g.mean() > 1.0           # bursty
    else:
        assert not g.any()
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    for cell in bench["workloads"]:
        if cell["traffic"] == name:
            conf = spec.config(bench, cell["config"])
            traffic.check_fits(mix, conf["serving"]["max_len"])


def test_lognormal_quantiles_keep_the_median():
    spec_ = {"dist": "lognormal", "median": 256, "sigma": 0.8, "min": 32,
             "max": 1536}
    v = traffic.lengths(spec_, 33)
    assert v[16] == 256
    assert list(v) == sorted(v)


def test_a_request_longer_than_max_len_is_refused():
    mix = spec.traffic("chat")
    with pytest.raises(ValueError):
        traffic.check_fits(mix, 1024)
