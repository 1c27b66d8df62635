"""``compile_real.py`` for the LongCat-Flash cell: the serving tick of
``longcat-serve-toolturns`` at its real widths — at the budget's 2,048
rows for each table width the traffic reaches and at the 16 rows of the
program that decode-only rounds run — and the plain reference at the
longest request, compiled by the TPU's own compiler for a described
``v5e:2x2``: no chip, nothing runs.  Prints ``memory_analysis()`` of each,
whether the program holds a ``conditional``, and every instruction outside
a fused computation whose result has a weight matrix's shape (a layer's
weights copied out of their stack).

    JAX_PLATFORMS=cpu python3 benchmarks/tools/compile_real_scmoe.py \
        [tick] [narrow] [reference] [--hlo DIR]

A compile that passes is not a chip run.
"""

import json
import os
import re
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
import numpy as np  # noqa: E402
from jax.sharding import SingleDeviceSharding  # noqa: E402

from benchmarks.lib import (harness, reference_longcat_flash,  # noqa: E402
                            serve_scmoe, weights_longcat)

CELL = "longcat-serve-toolturns"


def materialised(text, shapes):
    """Instructions of compiled HLO outside the fused computations whose
    result has one of ``shapes`` (leading 1s aside)."""
    hits, fused = [], False
    for line in text.splitlines():
        if line and not line.startswith(" "):
            fused = line.startswith("%fused_computation")
        m = re.match(r"\s+(?:ROOT )?(%[\w.\-]+) = \w+\[([\d,]+)\]", line)
        if not m or fused or " parameter(" in line:
            continue
        dims = tuple(int(d) for d in m.group(2).split(","))
        while len(dims) > 1 and dims[0] == 1:
            dims = dims[1:]
        if dims in shapes:
            hits.append(line.strip()[:160])
    return hits


def report(name, compiled, t0, weights=(), hlo=None):
    ma = compiled.memory_analysis()
    gb = lambda b: round(b / 1e9, 3)
    text = compiled.as_text()
    if hlo:
        os.makedirs(hlo, exist_ok=True)
        with open(os.path.join(hlo, name.replace(" ", "_") + ".hlo"),
                  "w") as f:
            f.write(text)
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
        "pallas_kernels": text.count("tpu_custom_call"),
        "conditionals": len(re.findall(r"\bconditional\(", text)),
        "weights_materialised": materialised(text, set(weights))}),
        flush=True)


def main(which):
    from jax.experimental import topologies
    hlo = which[which.index("--hlo") + 1] if "--hlo" in which else None
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        cell = {c["name"]: c for c in json.load(f)["workloads"]}[CELL]
    cfg = harness.load_json("configs", cell["config"] + ".json")
    traffic = harness.load_json("traffic", cell["traffic"] + ".json")
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    one = SingleDeviceSharding(topo.devices[0])
    sds = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one)
    table = weights_longcat.param_table(cfg)
    params = {n: sds(shape, jnp.bfloat16) for n, (shape, _) in table.items()}
    # a layer's slice of each large stack: a sublayer's matrix, the pair
    # of them a layer holds, an expert stack; either way round
    big = set()
    for n, (shape, _) in table.items():
        if n.startswith("layers_") and np.prod(shape[2:]) * 2 > 1e6:
            own = n[7:] in weights_longcat.SUBLAYER
            big |= {shape[1:], shape[2:]} if own else {shape[1:]}
    big |= {s[:-2] + s[:-3:-1] for s in big}
    jax.default_backend = lambda: "tpu"     # the kernels ask; nothing runs
    eng = traffic["engine"]
    if "tick" in which or "narrow" in which:
        # the engine's own program, from an engine over abstract weights
        engine = serve_scmoe.build_engine(
            cfg, dict(eng, num_blocks=1, max_slots=1), {}, None)
        engine.NB, engine.S = eng["num_blocks"], eng["max_slots"]
        engine.narrow_rows = -(-eng["max_slots"] // 8) * 8
        widest = eng["max_len"] // eng["block_size"]
        shapes = [(eng["token_budget"], C) for C in
                  (widest, eng["warm_table_widths"][0])] \
            if "tick" in which else []
        if "narrow" in which:
            shapes.append((engine.narrow_rows, widest))
        for T, C in shapes:
            t0 = time.time()
            args = jax.eval_shape(lambda: engine._ragged_scratch_args(C, T))
            args = jax.tree.map(
                lambda a: sds(a.shape, a.dtype) if hasattr(a, "shape")
                else a, (params,) + tuple(args[1:]))
            with jax.default_matmul_precision("default"):
                compiled = engine._build_ragged_step(T, C).lower(
                    *args).compile()
            report(f"tick T={T} C={C}", compiled, t0, big, hlo)
    if "reference" in which:
        longest = traffic["prompt_len"]["max"] + traffic["output_len"]["max"]
        pad_to = traffic["reference_pad_to"]
        L = -(-longest // pad_to) * pad_to
        t0 = time.time()
        compiled = jax.jit(lambda p, ids: reference_longcat_flash.hidden(
            cfg, p, ids, block=min(512, pad_to))).lower(
            params, sds((L,), jnp.int32)).compile()
        report(f"reference L={L}", compiled, t0, hlo=hlo)


if __name__ == "__main__":
    main(sys.argv[1:] or ["tick", "narrow", "reference"])
