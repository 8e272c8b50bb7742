"""The one traffic generator: every mix is a ``traffic/<name>.json`` file of
parameters that this module reads.

Requests come in blocks of ``block`` requests.  The sizes (prompt length,
output length) and the open-loop gaps between arrivals of a block are the
same multiset for every seed: lengths are the quantiles of the stated
distribution at ``(i + 0.5) / block``, and gaps a fixed draw from the stated
gamma distribution.  The seed permutes each block, and draws the prompts'
token ids.  So two seeds offer the same work in another order, and any
stretch of whole blocks carries the same work.

Parameters of a mix:

- ``loop``: ``"open"`` (arrivals on a schedule, ``rate_per_s`` requests per
  second, gaps with coefficient of variation ``gap_cv``) or ``"backlog"``
  (the queue kept at least ``backlog_per_lane`` times the lane count deep);
- ``prompt_len`` and ``output_len``: ``{"dist": "lognormal", "median": m,
  "sigma": s, "min": lo, "max": hi}`` or ``{"dist": "uniform", "min": lo,
  "max": hi}``, in tokens, both ends included;
- ``block``: requests per block;
- ``lead_in_s`` (open loop, optional): arrivals start this many seconds
  before the window opens (``harness.drive``), so that the window finds the
  lanes and the queue at the load's own level.
"""
from __future__ import annotations

import dataclasses
import math
from statistics import NormalDist
from typing import Iterator

import numpy as np

# The gaps' multiset is drawn once from this fixed seed: the run's seed
# only orders it.
_GAP_SEED = 0


@dataclasses.dataclass
class Request:
    idx: int
    prompt: np.ndarray          # int32 token ids
    max_new: int                # output tokens, all served greedily
    gap_s: float                # open loop: seconds after the previous due


def _quantiles(n: int) -> np.ndarray:
    return (np.arange(n) + 0.5) / n


def lengths(spec: dict, n: int) -> np.ndarray:
    """The ``n`` lengths of one block, in ascending order."""
    lo, hi = int(spec["min"]), int(spec["max"])
    q = _quantiles(n)
    if spec["dist"] == "lognormal":
        z = np.array([NormalDist().inv_cdf(float(p)) for p in q])
        v = np.round(float(spec["median"]) * np.exp(float(spec["sigma"]) * z))
    elif spec["dist"] == "uniform":
        v = np.floor(lo + q * (hi - lo + 1))
    else:
        raise ValueError(f"unknown length distribution {spec['dist']!r}")
    return np.clip(v, lo, hi).astype(np.int64)


def gaps(mix: dict, n: int) -> np.ndarray:
    """The ``n`` open-loop gaps of one block (seconds), with mean exactly
    ``1 / rate_per_s``."""
    cv = float(mix["gap_cv"])
    k = 1.0 / (cv * cv)
    g = np.random.default_rng(_GAP_SEED).gamma(k, 1.0, n)
    return np.sort(g / g.mean() / float(mix["rate_per_s"]))


def requests(mix: dict, seed: int, vocab_size: int) -> Iterator[Request]:
    """The mix's endless stream of requests for ``seed``."""
    n = int(mix["block"])
    p_len = lengths(mix["prompt_len"], n)
    o_len = lengths(mix["output_len"], n)
    gap = gaps(mix, n) if mix["loop"] == "open" else np.zeros(n)
    idx = 0
    for b in range(2 ** 62):
        rng = np.random.default_rng([seed, b])
        pp, po, pg = (rng.permutation(n) for _ in range(3))
        for i in range(n):
            prompt = rng.integers(0, vocab_size, int(p_len[pp[i]]),
                                  dtype=np.int32)
            yield Request(idx=idx, prompt=prompt, max_new=int(o_len[po[i]]),
                          gap_s=float(gap[pg[i]]))
            idx += 1


def mean_tokens(mix: dict) -> tuple:
    """(mean prompt, mean output) tokens of a block."""
    n = int(mix["block"])
    return (float(lengths(mix["prompt_len"], n).mean()),
            float(lengths(mix["output_len"], n).mean()))


def longest(mix: dict) -> int:
    """The longest request of the mix, prompt plus output, in tokens."""
    return int(mix["prompt_len"]["max"]) + int(mix["output_len"]["max"])


def check_fits(mix: dict, max_len: int) -> None:
    if longest(mix) > max_len:
        raise ValueError(f"traffic {mix.get('name')!r}: a request of "
                         f"{longest(mix)} tokens exceeds max_len {max_len}")
    if mix["loop"] == "open" and not math.isfinite(float(mix["rate_per_s"])):
        raise ValueError("open-loop traffic needs a finite rate_per_s")
