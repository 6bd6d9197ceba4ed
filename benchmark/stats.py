"""Metric arithmetic kept with the yardstick: percentiles, spreads, and the
Prometheus text the program's counters arrive in. No jax, no program code."""

from __future__ import annotations

import math
import statistics


def percentile(values, q: float) -> float:
    """The q-th percentile (0..100) by linear interpolation between the
    closest ranks of the sorted values (numpy's default rule)."""
    xs = sorted(values)
    if not xs:
        raise ValueError("percentile of no values")
    pos = (len(xs) - 1) * q / 100.0
    lo = math.floor(pos)
    hi = min(lo + 1, len(xs) - 1)
    return float(xs[lo] + (xs[hi] - xs[lo]) * (pos - lo))


def quartile_spread(values) -> float:
    """Distance between the first and the third quartile, as a share of the
    median — the spread the bounds are set from (statistics.quantiles)."""
    q1, _, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / statistics.median(values)


def parse_prometheus(text: str | bytes) -> dict[str, float]:
    """Prometheus text format -> {series-with-labels: value}."""
    if isinstance(text, bytes):
        text = text.decode("utf-8", "replace")
    out: dict[str, float] = {}
    for line in text.splitlines():
        line = line.strip()
        if not line or line.startswith("#"):
            continue
        series, _, value = line.rpartition(" ")
        try:
            out[series] = float(value)
        except ValueError:
            continue
    return out


def family(counters: dict[str, float], name: str) -> dict[str, float]:
    """All series of one metric name, any labels."""
    return {
        k: v for k, v in counters.items()
        if k == name or k.startswith(name + "{")
    }


def delta(before: dict[str, float], after: dict[str, float]) -> dict[str, float]:
    """after - before for every series of ``after`` (a series absent
    before counts from 0)."""
    return {k: v - before.get(k, 0.0) for k, v in after.items()}


def histogram_mean(counters: dict[str, float], name: str) -> float | None:
    """Mean of one histogram family over whatever interval ``counters``
    spans: sum of the _sum series over sum of the _count series."""
    total = sum(family(counters, name + "_sum").values())
    n = sum(family(counters, name + "_count").values())
    return total / n if n > 0 else None
