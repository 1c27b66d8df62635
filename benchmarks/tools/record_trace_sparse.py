"""Record the small trace kept as benchmarks/testdata/trace_sparse.xplane.pb:
a few ragged serving ticks of a small ``deepseek_v32`` share (1 dense + 2
expert layers, 8 heads, a 128 + 64 latent row, a 4-head indexer choosing
64 positions, 16 experts routed in 4 groups of which 4 are held), under
the benchmark's host spans and with a ``Tracer`` on the engine —
``record_trace_latent.py``'s way, for the readers this model brings
(``xplane_kernel_sparse`` and ``xplane_scope`` over the new regions).
Copies the ``.xplane.pb`` and the ticks' packs (``.ticks.json``) to
``chiprun_out/trace_probe/`` and prints what the readers read.  Run on
the chip; here it only shows the host's spans.
"""

import glob
import json
import os
import shutil
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

CFG = dict(vocab_size=2048, hidden_size=256, num_hidden_layers=3,
           first_k_dense_replace=1, num_attention_heads=8, q_lora_rank=128,
           kv_lora_rank=128, qk_nope_head_dim=64, qk_rope_head_dim=64,
           v_head_dim=64, intermediate_size=512, moe_intermediate_size=128,
           n_routed_experts=4, router_width=16, experts_held=[0, 4],
           n_shared_experts=1, num_experts_per_tok=3,
           routed_scaling_factor=2.5, norm_topk_prob=True, rms_norm_eps=1e-6,
           rope_theta=10000, max_position_embeddings=512,
           rope_scaling={"beta_fast": 32, "beta_slow": 1, "factor": 40,
                         "mscale": 1, "mscale_all_dim": 1,
                         "original_max_position_embeddings": 64,
                         "type": "yarn"},
           sandwich_norm=False, n_group=4, topk_group=2,
           topk_method="noaux_tc", index_topk=64, index_n_heads=4,
           index_head_dim=128, initializer_range=0.05, router_bias_std=0.05,
           compute_dtype="bfloat16")
ENGINE = dict(max_slots=4, max_len=512, block_size=16, num_blocks=128,
              token_budget=64)
AMONG = ["embed", "layers", "attn", "mlp", "kv_write", "head",
         "ragged_latent_attention", "router", "experts", "shared_expert",
         "indexer", "ragged_index_scores", "select",
         "ragged_sparse_latent_attention"]
PROMPTS = (130, 200)


def main():
    import jax
    import numpy as np
    import paddle_tpu as paddle
    from benchmarks.lib import serve_latent, serve_sparse, weights_dsv32, \
        xplane
    from paddle_tpu.telemetry import Tracer

    on_chip = jax.devices()[0].platform == "tpu"
    if not on_chip:
        paddle.set_flags({"FLAGS_paged_attn_interpret": True})
    params = weights_dsv32.make_params(CFG, 0, "bfloat16")
    tracer = Tracer()
    eng = serve_sparse.build_engine(CFG, ENGINE, params, tracer)
    rng = np.random.RandomState(0)
    for n in PROMPTS:
        eng.add_request(list(rng.randint(1, 2048, n)), 3)
    eng.run_to_completion()

    out = os.path.join("chiprun_out", "trace_probe")
    shutil.rmtree(out, ignore_errors=True)
    os.makedirs(out)
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    first = len(tracer.events("tick"))
    jax.profiler.start_trace(out, profiler_options=options)
    with jax.profiler.TraceAnnotation("bench_window"):
        for n in PROMPTS:
            with jax.profiler.TraceAnnotation("add_requests"):
                eng.add_request(list(rng.randint(1, 2048, n)), 3)
        while eng.pending():
            with jax.profiler.TraceAnnotation("engine_step"):
                eng.step()
    jax.profiler.stop_trace()

    path = glob.glob(os.path.join(out, "plugins", "profile", "*",
                                  "*.xplane.pb"))[0]
    kept = os.path.join(out, "trace_sparse.xplane.pb")
    shutil.copy(path, kept)
    shutil.rmtree(os.path.join(out, "plugins"))
    ticks = {e["tick"]: serve_latent.packed_rows(e)
             for e in tracer.events("tick")[first:] if e.get("rows")}
    with open(os.path.join(out, "trace_sparse.ticks.json"), "w") as f:
        json.dump({"config": CFG, "sparse_ticks": ticks,
                   "device_kind": jax.devices()[0].device_kind}, f)
    print("bytes", os.path.getsize(kept), "ticks", ticks)
    if not on_chip:
        return
    from types import SimpleNamespace
    from benchmarks.lib import harness
    red = xplane.Reduction(kept, host_spans=("engine_step",))
    ctx = SimpleNamespace(
        obs={"xplane": red, "sparse_ticks": ticks}, config=CFG,
        device_kind=jax.devices()[0].device_kind, note=print)
    scope = harness.load_module("readers", "xplane_scope")
    for s in AMONG:
        print("scope", s, scope.read({"scopes": [s], "among": AMONG}, ctx))
    reader = harness.load_module("readers", "xplane_kernel_sparse")
    for stem, count in (("ragged_index_scores", "index_scores"),
                        ("ragged_sparse_latent_attention", "sparse_latent")):
        print("kernel s, calls", stem, red.kernel_s({stem}))
        print("roofline", stem, reader.read(
            {"kernels": [stem], "opcount": count}, ctx))
    print("window_s", red.window_s, "busy_s", red.busy_s(), red.top_ops(12))


if __name__ == "__main__":
    main()
