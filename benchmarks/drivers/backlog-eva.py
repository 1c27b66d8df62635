"""Everything is due before the window opens and the queue never drains,
served by a model whose cache is a window leaf that does not page beside a
summary leaf that pages by chunk: the serving loop of ``lib/serve_eva.py``
with a schedule of the ``backlog`` kind."""

from benchmarks.lib.serve_eva import run  # noqa: F401
