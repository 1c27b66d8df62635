"""Record the small trace kept as benchmarks/testdata/trace_named.xplane.pb.

What ``record_trace.py`` records (training steps and a few ragged serving
ticks of a two-layer GPT, under the benchmark's own host spans), from a
program that names its regions and with a ``Tracer`` attached to the
engine, so that the trace also holds the engine's own ``engine.*`` spans.
The Python tracer is off: its events are most of the older file.  Copies
the ``.xplane.pb`` to ``chiprun_out/trace_probe/`` and prints what
``benchmarks/lib/xregion.py`` reads from it.  Run on the chip; here it
only shows the host's spans.
"""

import glob
import os
import shutil
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))


def main():
    import jax
    import jax.numpy as jnp
    import numpy as np
    import paddle_tpu as paddle
    from benchmarks.lib import xplane, xregion
    from paddle_tpu.distributed import fleet
    from paddle_tpu.models.gpt import GPTConfig, GPTModel, make_gpt_train_step
    from paddle_tpu.optimizer import AdamW
    from paddle_tpu.serving import RaggedPagedContinuousBatchingEngine
    from paddle_tpu.telemetry import Tracer

    on_chip = jax.devices()[0].platform == "tpu"
    if not on_chip:
        paddle.set_flags({"FLAGS_paged_attn_interpret": True})
    cfg = dict(vocab_size=2048, hidden_size=256, num_layers=2,
               num_attention_heads=4, max_position_embeddings=512,
               compute_dtype="bfloat16")
    paddle.seed(0)
    strategy = fleet.DistributedStrategy()
    strategy.hybrid_configs = {"dp_degree": 1, "mp_degree": 1, "pp_degree": 1}
    fleet.init(is_collective=True, strategy=strategy)
    model = GPTModel(GPTConfig(**cfg))
    step, state = make_gpt_train_step(
        model, AdamW(3e-4, weight_decay=0.01),
        fleet.get_hybrid_communicate_group(), remat=False)
    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.randint(0, 2048, (4, 512)))
    args = (jax.random.key(0), np.float32(3e-4), x, x)
    state, loss = step(state, *args)
    jax.block_until_ready(loss)

    params = {n: p.astype(jnp.bfloat16)
              for n, p in state["params"].items()}
    tracer = Tracer()
    eng = RaggedPagedContinuousBatchingEngine(
        GPTModel(GPTConfig(**cfg)), params, max_slots=4, max_len=512,
        block_size=16, prompt_buckets=list(range(16, 513, 16)),
        token_budget=64, tracer=tracer)
    for n in (40, 100, 17):
        eng.add_request(list(rng.randint(1, 2048, n)), 3)
    eng.run_to_completion()

    out = os.path.join("chiprun_out", "trace_probe")
    shutil.rmtree(out, ignore_errors=True)
    os.makedirs(out)
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(out, profiler_options=options)
    with jax.profiler.TraceAnnotation("bench_window"):
        for _ in range(2):
            with jax.profiler.TraceAnnotation("train_step"):
                state, loss = step(state, *args)
            with jax.profiler.TraceAnnotation("fetch_loss"):
                float(loss)
        for n in (40, 100, 17):
            with jax.profiler.TraceAnnotation("add_requests"):
                eng.add_request(list(rng.randint(1, 2048, n)), 3)
        while eng.pending():
            with jax.profiler.TraceAnnotation("engine_step"):
                eng.step()
    jax.profiler.stop_trace()

    path = glob.glob(os.path.join(out, "plugins", "profile", "*",
                                  "*.xplane.pb"))[0]
    kept = os.path.join(out, "trace_named.xplane.pb")
    shutil.copy(path, kept)
    shutil.rmtree(os.path.join(out, "plugins"))
    print("bytes", os.path.getsize(kept))
    print("ticks", [[e["tick"], e["rows"]] for e in tracer.events("tick")
                    if e.get("rows")][-8:])
    if not on_chip:
        return
    red = xplane.Reduction(kept, host_spans=("train_step", "engine_step"))
    named = xregion.Named(red)
    print("window_s", red.window_s, "busy_s", red.busy_s())
    print("regions", {r: round(v, 3) for r, v in named.shares().items()})
    print("unscoped", named.ops[xregion.UNSCOPED].most_common(12))
    print("kernels", {k: named.kernel_s(k) for k in xregion.KERNELS},
          "by file", red.kernel_s({"attention"}),
          red.kernel_s({"ragged_paged_attention"}))
    print("idle", dict(named.idle_by_phase()),
          "sum", sum(named.idle_by_phase().values()),
          "reduction", (red.window_s - red.busy_s()) * 1e9)
    print("rounds", [dict(r) for r in named.tick_phase_ms()])


if __name__ == "__main__":
    main()
