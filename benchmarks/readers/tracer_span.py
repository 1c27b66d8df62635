"""A statistic of a series taken from the program's own ``Tracer``: tick
durations (``tick_ms``), packed rows over the token budget per tick
(``occupancy_pct``), ``queued_at`` to ``admitted_at`` per request
(``queue_wait_ms``), each over the window.  ``{"series": ...,
"statistic": "p50"}``."""

from benchmarks.lib.stats import series_statistic as read  # noqa: F401
