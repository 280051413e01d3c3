"""Metric arithmetic over what the serve loop recorded.

Percentiles are nearest-rank, as in ``repro.core.metrics._percentile``;
goodput counts step requests that met their deadline, as the paper's
request-level metric 1 (``repro.core.metrics.goodput``), with a request
that failed or never finished counted as a miss.  Copied here so that
the yardstick does not move with the program.
"""
from __future__ import annotations

import math
from typing import List, Sequence


def percentile(values: Sequence[float], q: float) -> float:
    """Nearest-rank ``q``-th percentile (0 < q <= 100) of ``values``."""
    if not values:
        raise ValueError("percentile of no values")
    s = sorted(values)
    rank = math.ceil(q / 100.0 * len(s))
    return s[min(max(rank, 1), len(s)) - 1]


def beyond(values: Sequence[float], q: float) -> int:
    """How many values lie strictly above the ``q``-th percentile: the
    samples a tail rests on."""
    p = percentile(values, q)
    return sum(v > p for v in values)


def met_deadline(step) -> bool:
    return (step.finish is not None
            and step.finish - step.due <= step.deadline_s)


def goodput(steps, seconds: float) -> float:
    """Counted steps that finished by their deadline, per second."""
    return sum(met_deadline(s) for s in steps if s.counted) / seconds


def ttfts(steps, end: float) -> List[float]:
    """Due time to first token on the host, for every counted step.  A
    step that has no token when the run stops counts the time up to the
    stop: a lower bound, ranked with the slowest."""
    return [(s.token_times[0] if s.token_times else end) - s.due
            for s in steps if s.counted]


def token_gaps(steps) -> List[float]:
    """Every gap between consecutive output tokens of counted steps."""
    out = []
    for s in steps:
        if s.counted:
            t = s.token_times
            out.extend(b - a for a, b in zip(t, t[1:]))
    return out


def tokens_in(steps, t0: float, t1: float) -> int:
    """Output tokens of any step that reached the host in [t0, t1)."""
    return sum(t0 <= t < t1 for s in steps for t in s.token_times)


def tail_line(name: str, values: Sequence[float], q: float) -> str:
    return (f"{name}: p{q:g} over {len(values)} samples, "
            f"{beyond(values, q)} beyond it")

