"""Pin the JAX-internals seam behind the pipeline engine: ensure_varying
leans on jax._src.core.get_aval and lax.pcast, in the installed JAX's
spelling (distributed/spmd.py resolves them at import).  These tests fail
LOUDLY on an incompatible JAX instead of letting the varying-cast degrade
to a no-op (which would break shard_map pipelines silently)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, PartitionSpec as P

from paddle_tpu.distributed import spmd


class TestVMASeam:
    def test_seam_is_the_installed_spelling(self):
        """spmd re-exports the top-level shard_map and resolved the cast
        primitive and the aval accessor at import."""
        assert spmd.shard_map is jax.shard_map
        assert spmd._pcast is jax.lax.pcast
        assert isinstance(spmd._get_aval(jnp.ones(3)).vma, frozenset)

    def test_avals_track_varying_manual_axes(self):
        # ensure_varying reads aval.vma unconditionally
        assert hasattr(jax.core.ShapedArray((), np.dtype(np.float32)), "vma")

    def test_ensure_varying_marks_replicated_carry(self):
        """Inside shard_map, a replicated value must come back actually
        varying (axis in aval.vma) — the exact property the pipeline's scan
        carry needs; and an already-varying value must pass through (pcast
        rejects varying->varying, so a blind cast would raise)."""
        mesh = Mesh(np.array(jax.devices()[:2]), ("pipe",))
        seen = {}

        def body(x):
            rep = jnp.float32(1.0)  # replicated: vma == frozenset()
            v = spmd.ensure_varying(rep, "pipe")
            seen["was"] = spmd._get_aval(rep).vma
            seen["now"] = spmd._get_aval(v).vma
            v2 = spmd.ensure_varying(v, "pipe")  # idempotent on varying
            return x + v + v2

        out = spmd.shard_map(body, mesh=mesh, in_specs=P("pipe"),
                             out_specs=P("pipe"))(jnp.zeros(2))
        np.testing.assert_allclose(np.asarray(out), [2.0, 2.0])
        assert "pipe" not in seen["was"]
        assert "pipe" in seen["now"]

    def test_get_aval_raises_on_garbage_not_swallowed(self):
        """The old code wrapped get_aval in `except Exception: vma = None`,
        turning real incompatibilities into silent no-ops.  The seam must
        propagate failures."""
        with pytest.raises(Exception):
            spmd.ensure_varying(object(), "pipe")  # not a JAX value: loud
