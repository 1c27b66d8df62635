"""Open loop at the fixed rate of the traffic file: requests are due on a
schedule whatever the engine does, and each is timed from when it was due."""

from benchmarks.lib.serve import run  # noqa: F401
