"""``compile_real.py`` for the latent cell: the serving tick of
``pangu-serve-longdocs`` at its real widths and the plain reference at the
longest request, compiled by the TPU's own compiler for a described
``v5e:2x2`` — no chip, nothing runs.  Prints ``memory_analysis()`` of each.

    JAX_PLATFORMS=cpu python3 benchmarks/tools/compile_real_latent.py \
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

from benchmarks.lib import (harness, reference_pangu_moe,  # noqa: E402
                            serve_latent, weights_pangu)

CELL = "pangu-serve-longdocs"


def report(name, compiled, t0):
    ma = compiled.memory_analysis()
    gb = lambda b: round(b / 1e9, 3)
    print(json.dumps({
        "program": name, "compile_s": round(time.time() - t0, 1),
        "argument_gb": gb(ma.argument_size_in_bytes),
        "output_gb": gb(ma.output_size_in_bytes),
        "alias_gb": gb(ma.alias_size_in_bytes),
        "temp_gb": gb(ma.temp_size_in_bytes),
        "peak_estimate_gb": gb(ma.argument_size_in_bytes
                               + ma.output_size_in_bytes
                               - ma.alias_size_in_bytes
                               + ma.temp_size_in_bytes),
        "pallas_kernels": compiled.as_text().count("tpu_custom_call")}),
        flush=True)


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
              for n, (shape, _) in weights_pangu.param_table(cfg).items()}
    jax.default_backend = lambda: "tpu"     # the kernels ask; nothing runs
    if "tick" in which:
        eng = traffic["engine"]
        # the engine's own program, from an engine over abstract weights
        engine = serve_latent.build_engine(
            cfg, dict(eng, num_blocks=1), {}, None)
        engine.NB = eng["num_blocks"]
        args = jax.eval_shape(lambda: engine._ragged_scratch_args(
            eng["max_len"] // eng["block_size"]))
        args = jax.tree.map(
            lambda a: sds(a.shape, a.dtype) if hasattr(a, "shape") else a,
            (params,) + tuple(args[1:]))
        for C in (eng["max_len"] // eng["block_size"], 256):
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
            lambda p, ids: reference_pangu_moe.hidden(cfg, p, ids)).lower(
            params, sds((L,), jnp.int32)).compile()
        report(f"reference L={L}", compiled, t0)


if __name__ == "__main__":
    main(sys.argv[1:] or ["tick", "reference"])
