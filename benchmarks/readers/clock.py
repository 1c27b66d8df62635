"""A statistic of a series the benchmark timed with its own clock
(``gen_lag_ms``: injection time minus due time; ``step_ms``: one training
step to the next).  ``{"series": ..., "statistic": "p95"}``."""

from benchmarks.lib.stats import series_statistic as read  # noqa: F401
