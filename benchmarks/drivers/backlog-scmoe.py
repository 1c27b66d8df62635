"""Everything is due before the window opens and the queue never drains,
served by a model whose layer is two latent-attention sublayers, two dense
MLPs and a shortcut expert branch with zero-compute experts, its cache
two latent rows per token per layer: the serving loop of
``lib/serve_scmoe.py`` with a schedule of the ``backlog`` kind."""

from benchmarks.lib.serve_scmoe import run  # noqa: F401
