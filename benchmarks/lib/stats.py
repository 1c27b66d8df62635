"""The statistics every metric is reduced with: one copy."""

import math
import statistics


def percentile(values, q):
    """q-th percentile (0-100) by linear interpolation between order
    statistics; +inf samples sort last, so a tail that reaches them is
    +inf.  None for an empty sample."""
    xs = sorted(values)
    if not xs:
        return None
    k = (len(xs) - 1) * q / 100.0
    lo, hi = math.floor(k), math.ceil(k)
    if lo == hi or math.isinf(xs[hi]):
        return xs[hi]
    return xs[lo] + (xs[hi] - xs[lo]) * (k - lo)


def series_statistic(how, ctx):
    """``{"series": ..., "statistic": ...}`` of a reader's file, over a
    series the driver left in ``ctx.obs``; None if it left none."""
    values = ctx.obs["series"].get(how["series"])
    return reduce(values, how["statistic"]) if values else None


def reduce(values, statistic):
    """``statistic``: mean, max, sum, count, or pNN."""
    values = list(values)
    if not values:
        return None
    if statistic == "mean":
        return sum(values) / len(values)
    if statistic == "max":
        return max(values)
    if statistic == "sum":
        return sum(values)
    if statistic == "count":
        return float(len(values))
    if statistic.startswith("p"):
        return percentile(values, float(statistic[1:]))
    raise ValueError(f"unknown statistic {statistic!r}")


def quartile_spread(values):
    """Distance between the first and third quartile as a share of the
    median, quartiles as ``statistics.quantiles(values, n=4)`` gives them
    (the rule the bounds are set by)."""
    q1, _, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / statistics.median(values)
