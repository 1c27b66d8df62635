"""Everything is due before the window opens and the queue never drains:
the same serving loop, with a schedule of the ``backlog`` arrival kind."""

from benchmarks.lib.serve import run  # noqa: F401
