"""Medians and spreads, written out so that nobody has to guess the rule."""

from __future__ import annotations

import statistics
from typing import Sequence


def median(samples: Sequence[float]) -> float:
    return statistics.median(samples)


def quartile_spread(samples: Sequence[float]) -> float:
    """(Q3 - Q1) / median with ``statistics.quantiles(n=4)``: the spread
    the bounds in BENCHMARK.json are set from."""
    q1, _, q3 = statistics.quantiles(samples, n=4)
    return (q3 - q1) / statistics.median(samples)
