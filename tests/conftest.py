"""Test harness config.

- Virtual 8-device CPU mesh (the reference's multi-GPU tests map to this —
  SURVEY.md §4: xla_force_host_platform_device_count replaces the 2-GPU gate).
- Highest matmul precision so numpy-oracle comparisons (OpTest-style) are
  meaningful; production keeps the TPU-default bf16 MXU path.
"""

import os
import sys

# The tests run on the CPU, on eight virtual devices.  The one exception is
# the ``tpu``-marked hardware suite, which runs on the chip only when BOTH
# ``PADDLE_TPU_TEST_TPU=1`` is set and ``-m tpu`` selects it; a plain
# ``pytest`` with the variable exported still gets the CPU.
def _tpu_selected(argv):
    """True when a -m marker expression selects tpu tests (``-m tpu``,
    ``-m=tpu``, ``-m "tpu and ..."`` — but not ``-m "not tpu"``)."""
    exprs = [a.split("=", 1)[1] for a in argv if a.startswith("-m=")]
    exprs += [a for i, a in enumerate(argv)
              if i > 0 and argv[i - 1] == "-m"]
    import re
    return any(re.search(r"(^|[ (])tpu([ )]|$)", e)
               and not re.search(r"not\s+tpu", e) for e in exprs)


_TPU_RUN = (os.environ.get("PADDLE_TPU_TEST_TPU") == "1"
            and _tpu_selected(sys.argv))

if not _TPU_RUN:
    os.environ["JAX_PLATFORMS"] = "cpu"
    flags = os.environ.get("XLA_FLAGS", "")
    if "host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = flags + " --xla_force_host_platform_device_count=8"

import jax  # noqa: E402

if not _TPU_RUN:
    # in case something imported jax before this file set the variable
    # (legal until the first backend starts)
    jax.config.update("jax_platforms", "cpu")
    jax.config.update("jax_default_matmul_precision", "highest")

import numpy as np  # noqa: E402
import pytest  # noqa: E402


@pytest.fixture(autouse=True)
def _seed_all():
    import paddle_tpu as paddle
    paddle.seed(2024)
    np.random.seed(2024)
    yield


@pytest.fixture
def lock_sanitizer():
    """Opt-in runtime lock-discipline recorder (docs/STATIC_ANALYSIS.md
    § Lock-discipline sanitizer).  Tests ``instrument()`` the objects
    under threaded exercise; any lock-order inversion or guarded-by
    violation recorded during the test fails it at teardown with every
    racing site listed."""
    from paddle_tpu.analysis import LockSanitizer
    san = LockSanitizer("pytest")
    yield san
    san.assert_clean()
