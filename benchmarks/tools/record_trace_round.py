"""Record the small trace kept as benchmarks/testdata/trace_round.xplane.pb
(and the rounds' ``tick`` events beside it, ``trace_round.ticks.json``).

A handful of ragged serving rounds of a two-layer GPT with a ``Tracer``
attached, rounds that carry a prefill chunk and rounds of decode rows only
(four slots, a budget of 64 rows, so the latter run the 8-row program),
from a program whose spans carry the round's kind and whose
``engine.dispatch`` has its three parts: what ``benchmarks/lib/xround.py``
reads.  Two windows are marked: ``bench_window`` round all of it, and
``cut_window``, which opens and closes inside a round (from a request's
token callback, which the engine calls in ``engine.unpack``), so that a
round is cut by each of its edges.  The Python tracer is off.  Copies the
files to ``chiprun_out/trace_probe/`` and prints what the reader makes of
them.  Run on the chip; here it only shows the host's spans.  A session
lays the device's line against the host's anew: where the printed line
says the program's first operation begins BEFORE ``engine.dispatch.call``
opens, the recording is one like ``trace_round_early.xplane.pb`` (kept for
the reader's test of that), not one to replace ``trace_round.xplane.pb``.
"""

import glob
import json
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
    from benchmarks.lib import xplane, xregion, xround
    from paddle_tpu.models.gpt import GPTConfig, GPTModel
    from paddle_tpu.serving import RaggedPagedContinuousBatchingEngine
    from paddle_tpu.telemetry import Tracer

    on_chip = jax.devices()[0].platform == "tpu"
    if not on_chip:
        paddle.set_flags({"FLAGS_paged_attn_interpret": True})
    cfg = dict(vocab_size=2048, hidden_size=256, num_layers=2,
               num_attention_heads=4, max_position_embeddings=512,
               compute_dtype="bfloat16")
    paddle.seed(0)
    model = GPTModel(GPTConfig(**cfg))
    params = {n: p._data.astype(jnp.bfloat16)
              for n, p in model.named_parameters()}
    tracer = Tracer()
    eng = RaggedPagedContinuousBatchingEngine(
        model, params, max_slots=4, max_len=512, block_size=16,
        prompt_buckets=list(range(16, 513, 16)), token_budget=64,
        tracer=tracer)
    assert eng.narrow_rows == 8, eng.narrow_rows
    rng = np.random.RandomState(0)

    def serve(on_token=None):
        for n in (40, 100, 17):
            eng.add_request(list(rng.randint(1, 2048, n)), 6,
                            on_token=on_token)
        while eng.pending():
            with jax.profiler.TraceAnnotation("engine_step"):
                eng.step()

    serve()     # every program the traced rounds run is compiled here
    first = tracer.events("tick")[-1]["tick"] + 1

    seen, cut = [], []

    def on_token(rid, token, done):
        seen.append(rid)
        if len(seen) == 2:          # inside an early round's unpack
            # (made here: an annotation made before the session is inert)
            cut.append(jax.profiler.TraceAnnotation("cut_window"))
            cut[0].__enter__()
        elif len(seen) == 14:       # inside a late one's
            cut[0].__exit__(None, None, None)

    out = os.path.join("chiprun_out", "trace_probe")
    shutil.rmtree(out, ignore_errors=True)
    os.makedirs(out)
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(out, profiler_options=options)
    with jax.profiler.TraceAnnotation("bench_window"):
        serve(on_token)
    jax.profiler.stop_trace()

    path = glob.glob(os.path.join(out, "plugins", "profile", "*",
                                  "*.xplane.pb"))[0]
    kept = os.path.join(out, "trace_round.xplane.pb")
    shutil.copy(path, kept)
    shutil.rmtree(os.path.join(out, "plugins"))
    ticks = [{k: e.get(k) for k in ("tick", "dur_s", "prefill_tokens",
                                    "decode_rows", "rows_run", "budget_used",
                                    "phases", "parts")}
             for e in tracer.events("tick") if e["tick"] >= first]
    with open(os.path.join(out, "trace_round.ticks.json"), "w") as f:
        json.dump(ticks, f, indent=1)
    print("bytes", os.path.getsize(kept))
    print("ticks", [[t["tick"], t["prefill_tokens"], t["decode_rows"],
                     t["rows_run"]] for t in ticks])
    if not on_chip:
        return
    for window in ("bench_window", "cut_window"):
        red = xplane.Reduction(kept, window_span=window,
                               host_spans=("engine_step",))
        rounds = xround.Rounds(xregion.Named(red), kept)
        print(window, "window_s", red.window_s, "busy_s", red.busy_s())
        print(" kinds", [[r.number, r.kind] for r in rounds.rounds])
        print(" ", rounds.describe())


if __name__ == "__main__":
    main()
