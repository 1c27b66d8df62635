"""``compile_real_sparse.py`` for the EvaByte cell: the serving tick of
``evabyte-serve-bytedocs`` at its real widths and the plain reference at
the longest request, compiled by the TPU's own compiler for a described
``v5e:2x2`` — no chip, nothing runs.  Prints ``memory_analysis()`` of each:
arguments + temporaries of the tick have to stay under 15.0 GB.

    JAX_PLATFORMS=cpu python3 benchmarks/tools/compile_real_eva.py \
        [tick] [reference]

A compile that passes is not a chip run.
"""

import json
import os
import sys
import time

os.environ.setdefault("TPU_LOG_DIR", "disabled")
os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ["ALLOW_MULTIPLE_LIBTPU_LOAD"] = "1"
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.sharding import SingleDeviceSharding  # noqa: E402

from benchmarks.lib import (harness, reference_evabyte,  # noqa: E402
                            serve_eva, weights_evabyte)
from benchmarks.tools.compile_real_latent import report  # noqa: E402

CELL = "evabyte-serve-bytedocs"


def main(which):
    from jax.experimental import topologies
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        cell = {c["name"]: c for c in json.load(f)["workloads"]}[CELL]
    cfg = harness.load_json("configs", cell["config"] + ".json")
    traffic = harness.load_json("traffic", cell["traffic"] + ".json")
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    one = SingleDeviceSharding(topo.devices[0])
    sds = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one)
    params = {n: sds(shape, jnp.bfloat16)
              for n, (shape, _) in weights_evabyte.param_table(cfg).items()}
    jax.default_backend = lambda: "tpu"     # the kernels ask; nothing runs
    eng = traffic["engine"]
    widest = eng["max_len"] // serve_eva.block_positions(cfg, eng)
    if "tick" in which:
        # the engine's own program, from an engine over abstract weights
        engine = serve_eva.build_engine(
            cfg, dict(eng, num_blocks=1, max_slots=1), {}, None)
        engine.NB, engine.S = eng["num_blocks"], eng["max_slots"]
        args = jax.eval_shape(lambda: engine._ragged_scratch_args(widest))
        args = jax.tree.map(
            lambda a: sds(a.shape, a.dtype) if hasattr(a, "shape") else a,
            (params,) + tuple(args[1:]))
        for C in (widest, 8):
            t0 = time.time()
            a = list(args)
            a[5] = sds((eng["max_slots"], C), jnp.int32)
            compiled = engine._build_ragged_step(
                eng["token_budget"], C).lower(*a).compile()
            report(f"tick C={C}", compiled, t0)
    if "reference" in which:
        longest = traffic["prompt_len"]["max"] + traffic["output_len"]["max"]
        pad_to = traffic["reference_pad_to"]
        L = -(-longest // pad_to) * pad_to
        t0 = time.time()
        compiled = jax.jit(
            lambda p, ids: reference_evabyte.hidden(cfg, p, ids)).lower(
            params, sds((L,), jnp.int32)).compile()
        report(f"reference L={L}", compiled, t0)


if __name__ == "__main__":
    main(sys.argv[1:] or ["tick", "reference"])
