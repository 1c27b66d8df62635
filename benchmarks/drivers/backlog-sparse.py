"""Everything is due before the window opens and the queue never drains,
served by a model whose rows attend what a lightning indexer selects, from
a cache of two leaves on one table: the serving loop of
``lib/serve_sparse.py`` with a schedule of the ``backlog`` kind."""

from benchmarks.lib.serve_sparse import run  # noqa: F401
