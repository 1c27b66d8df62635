"""The third rehearsal: each cell's program at its real widths, compiled by
the TPU's own compiler for a described ``v5e:2x2`` — no chip, nothing runs.
Prints ``memory_analysis()`` per device, the kernels and the collectives in
the compiled program.  Run here, by hand, before a chip call:

    XLA_FLAGS=--xla_force_host_platform_device_count=4 JAX_PLATFORMS=cpu \
        python3 benchmarks/tools/compile_real.py [cell ...]

A compile that passes is not a chip run.
"""

import json
import os
import re
import sys
import time

os.environ.setdefault("TPU_LOG_DIR", "disabled")
os.environ.setdefault("JAX_PLATFORMS", "cpu")
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P  # noqa: E402
from jax.sharding import SingleDeviceSharding  # noqa: E402

from benchmarks.lib import harness, program, weights  # noqa: E402


def report(name, compiled, t0):
    ma = compiled.memory_analysis()
    text = compiled.as_text()
    colls = {k: len(re.findall(rf"= [^=]*\b{k}(?:-start)?\(", text))
             for k in ("all-gather", "all-reduce", "reduce-scatter",
                       "collective-permute", "all-to-all")}
    gb = lambda b: round(b / 1e9, 3)
    print(json.dumps({
        "cell": name, "compile_s": round(time.time() - t0, 1),
        "argument_gb": gb(ma.argument_size_in_bytes),
        "output_gb": gb(ma.output_size_in_bytes),
        "alias_gb": gb(ma.alias_size_in_bytes),
        "temp_gb": gb(ma.temp_size_in_bytes),
        "peak_estimate_gb": gb(ma.argument_size_in_bytes
                               + ma.output_size_in_bytes
                               - ma.alias_size_in_bytes
                               + ma.temp_size_in_bytes),
        "pallas_kernels": text.count("tpu_custom_call"),
        "collectives": colls}), flush=True)


def serving_tick(topo, cell):
    cfg = harness.load_json("configs", cell["config"] + ".json")
    eng = harness.load_json("traffic", cell["traffic"] + ".json")["engine"]
    model = program.meta_model(cfg)
    one = SingleDeviceSharding(topo.devices[0])
    sds = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one)
    params = {n: sds(shape, jnp.bfloat16)
              for n, (shape, _) in weights.gpt_param_table(cfg).items()}
    L, H, nh, *_ = weights.gpt_dims(cfg)
    T, S, bs = eng["token_budget"], eng["max_slots"], eng["block_size"]
    C = eng["max_len"] // bs
    pool = sds((L, eng["num_blocks"] + 1, bs, nh, H // nh), jnp.bfloat16)

    def tick(params, ck, cv, toks, row_seq, row_pos, table, pads, rows):
        h = model._embed_ragged(params, toks, row_seq, row_pos, pads)
        h, (ck, cv) = model.decode_ragged(params, h, (ck, cv), table,
                                          row_seq, row_pos, pads)
        logits = model.decode_logits(params, h[0, rows][:, None])[:, -1]
        return ck, cv, jnp.argmax(logits, -1)

    i32 = jnp.int32
    t0 = time.time()
    compiled = jax.jit(tick, donate_argnums=(1, 2)).lower(
        params, pool, pool, sds((T,), i32), sds((T,), i32), sds((T,), i32),
        sds((S, C), i32), sds((S,), i32), sds((S,), i32)).compile()
    report(cell["name"], compiled, t0)


def train_step(topo, cell):
    from paddle_tpu.distributed import spmd
    from paddle_tpu.distributed.grad_comm import resolve_policy
    from paddle_tpu.optimizer import AdamW
    cfg = harness.load_json("configs", cell["config"] + ".json")
    tr = harness.load_json("traffic", cell["traffic"] + ".json")
    chips = cell["chips"]
    degrees = ({"sharding_degree": chips} if chips > 1 else {})
    hcg = program.init_fleet(**degrees)
    names, shape = hcg.mesh.axis_names, hcg.mesh.devices.shape
    mesh = Mesh(np.array(topo.devices[:chips]).reshape(shape), names)
    model = program.meta_model(cfg, scan_unroll=tr.get("scan_unroll", 1))
    optimizer = AdamW(tr["optimizer"]["lr"],
                      weight_decay=tr["optimizer"]["weight_decay"])
    zero = tr.get("zero_stage", 0)
    remat = tr.get("remat", False)

    def loss_of(params, key, x, labels):
        h = model.embed_fn(params, x, key)
        h = model.scan_blocks(params, h, key, remat=remat, mesh=mesh)
        return model.head_loss_fn(params, h, labels)

    table = weights.gpt_param_table(cfg)

    def init_state(key):
        params = weights.build_gpt_params(table, jnp.float32, key)
        return {"params": params, "opt": optimizer.init_state(params),
                "buffers": {}}

    state_abs = jax.eval_shape(init_state, jax.random.key(0))
    p_specs = spmd.build_param_specs(state_abs["params"], mesh, model, zero)
    state_sh = spmd.build_state_shardings(state_abs, p_specs, mesh,
                                          max(zero, 1), state_abs["params"])
    step = spmd._make_gspmd_step(loss_of, optimizer, mesh, p_specs, True,
                                 resolve_policy(None))
    state = jax.tree.map(
        lambda a, s: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=s),
        state_abs, state_sh)
    rep = NamedSharding(mesh, P())
    B, L = tr["batch"], tr["seq_len"]
    x = jax.ShapeDtypeStruct((B, L), jnp.int32, sharding=rep)
    key = jax.eval_shape(lambda: jax.random.key(0))
    key = jax.ShapeDtypeStruct(key.shape, key.dtype, sharding=rep)
    lr = jax.ShapeDtypeStruct((), jnp.float32, sharding=rep)
    t0 = time.time()
    compiled = step.lower(state, lr, key, x, x).compile()
    report(cell["name"], compiled, t0)


def main(argv):
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    jax.default_backend = lambda: "tpu"   # the kernels ask; see the tests
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        cells = json.load(f)["workloads"]
    for cell in cells:
        if argv and cell["name"] not in argv:
            continue
        driver = harness.load_json(
            "traffic", cell["traffic"] + ".json")["driver"]
        (train_step if driver == "train-steps" else serving_tick)(topo, cell)


if __name__ == "__main__":
    main(sys.argv[1:])
