"""Everything is due before the window opens and the queue never drains,
served by a model whose cache is one latent row per token: the serving
loop of ``lib/serve_latent.py`` with a schedule of the ``backlog`` kind."""

from benchmarks.lib.serve_latent import run  # noqa: F401
