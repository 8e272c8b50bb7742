"""End-to-end metrics from the client's delivery log, on the host's clock.

A request's record holds the time it was due and each delivery of its
sampled tokens: ``(t, n)``, n tokens received by the host at time t (a
megastep round's sync).  All times are seconds from the window's start.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Iterable, List, Optional, Tuple


@dataclasses.dataclass
class Record:
    due: float
    deliveries: List[Tuple[float, int]] = dataclasses.field(
        default_factory=list)

    @property
    def first_token_at(self) -> Optional[float]:
        return self.deliveries[0][0] if self.deliveries else None


def percentile(values: Iterable[float], q: float) -> float:
    """Nearest-rank percentile: the smallest sample with at least ``q``% of
    the samples at or below it.  It never interpolates, so a sample of
    ``inf`` (a request that never got its first token) stays larger than
    every other."""
    xs = sorted(values)
    if not xs:
        raise ValueError("percentile of no samples")
    k = max(math.ceil(q / 100.0 * len(xs)) - 1, 0)
    return xs[k]


def ttft_samples(records: Iterable[Record], window_s: float) -> List[float]:
    """Time to first token of every request due in the window; ``inf`` for
    one that never got it."""
    out = []
    for r in records:
        if 0.0 <= r.due < window_s:
            t = r.first_token_at
            out.append(math.inf if t is None else t - r.due)
    return out


def tpot_samples(records: Iterable[Record], window_s: float) -> List[float]:
    """Time per output token: a delivery of n tokens, g seconds after the
    request's previous delivery, gives n samples of g / n.  Deliveries that
    land in the window count; the one carrying the first token does not
    (its wait is time to first token)."""
    out = []
    for r in records:
        for (t0, _), (t1, n) in zip(r.deliveries, r.deliveries[1:]):
            if 0.0 <= t1 <= window_s:
                out.extend([(t1 - t0) / n] * n)
    return out


def tokens_delivered(records: Iterable[Record], window_s: float) -> int:
    return sum(n for r in records for t, n in r.deliveries
               if 0.0 <= t <= window_s)


def spread(values: List[float]) -> float:
    """Distance between the first and third quartile as a share of the
    median, as ``statistics.quantiles(values, n=4)`` gives them."""
    import statistics
    q1, q2, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / q2
