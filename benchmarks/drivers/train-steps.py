"""Training steps back to back for the length of the window."""

from benchmarks.lib.train import run  # noqa: F401
