"""Record the small device trace kept under benchmarks/testdata/.

Runs a two-layer GPT through the same entry points the cells use (three
training steps, a few ragged serving ticks) under the JAX profiler with
the benchmark's own host spans, copies the ``.xplane.pb`` to
``chiprun_out/trace_probe/`` and prints what planes, lines and event names
the trace holds.  Run on the chip; here it only shows the CPU's planes.
"""

import collections
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
    from paddle_tpu.distributed import fleet
    from paddle_tpu.models.gpt import GPTConfig, GPTModel, make_gpt_train_step
    from paddle_tpu.optimizer import AdamW
    from paddle_tpu.serving import RaggedPagedContinuousBatchingEngine

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
    eng = RaggedPagedContinuousBatchingEngine(
        GPTModel(GPTConfig(**cfg)), params, max_slots=4, max_len=512,
        block_size=16, prompt_buckets=list(range(16, 513, 16)),
        token_budget=64)
    for n in (40, 100, 17):
        eng.add_request(list(rng.randint(1, 2048, n)), 3)
    eng.run_to_completion()

    out = os.path.join("chiprun_out", "trace_probe")
    shutil.rmtree(out, ignore_errors=True)
    os.makedirs(out)
    jax.profiler.start_trace(out)
    for _ in range(3):
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
    shutil.copy(path, os.path.join(out, "trace.xplane.pb"))
    shutil.rmtree(os.path.join(out, "plugins"))
    print("bytes", os.path.getsize(os.path.join(out, "trace.xplane.pb")))
    data = jax.profiler.ProfileData.from_file(
        os.path.join(out, "trace.xplane.pb"))
    for plane in data.planes:
        print("PLANE", repr(plane.name))
        for line in plane.lines:
            names = collections.Counter()
            dur = collections.Counter()
            n = 0
            first = None
            for ev in line.events:
                n += 1
                names[ev.name] += 1
                dur[ev.name] += ev.duration_ns
                if first is None:
                    first = (ev.start_ns, ev.duration_ns,
                             dict(list(ev.stats)[:6]))
            print("  LINE", repr(line.name), "events", n, "first", first)
            for name, d in dur.most_common(25):
                print("     ", names[name], d, name[:100])


if __name__ == "__main__":
    main()
