"""Find the knee of an open-loop serving cell once, on the chip: one
process, one set-up, ``--step-seconds`` at each of ``--rates``.  The knee is
the highest rate at which the queue at the end of the step is no longer
than at its middle.  Prints one JSON line per rate.

    python3 benchmarks/tools/sweep_rate.py --config cerebras-gpt-1.3b \
        --traffic chat-open --rates 1.5,2,2.5,3,3.5,4 --step-seconds 20

Make the steps several times as long as a request lives: at 20-30 s, with
requests that live 15-25 s, PR 24's sweep read a knee that a 45 s run at
four fifths of it showed to be past saturation (PERF.md).
"""

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", required=True)
    ap.add_argument("--traffic", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--step-seconds", type=float, default=20.0)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args()

    import jax
    from benchmarks.lib import harness, program, schedule, serve, stats
    from benchmarks.lib import weights
    from paddle_tpu.jit.aot import enable_persistent_compilation_cache
    from paddle_tpu.telemetry import Tracer
    if jax.devices()[0].platform != "tpu":
        sys.exit("needs a TPU")
    enable_persistent_compilation_cache()
    cfg = harness.load_json("configs", args.config + ".json")
    traffic = harness.load_json("traffic", args.traffic + ".json")
    params = weights.make_gpt_params(cfg, args.seed, "bfloat16")
    tracer = Tracer(capacity=1 << 22)
    eng = program.build_engine(cfg, traffic["engine"], params, tracer)
    serve.warm_up(eng, traffic["engine"], cfg["vocab_size"])
    now = time.monotonic
    for rate in [float(r) for r in args.rates.split(",")]:
        tr = dict(traffic, rate_rps=rate, ramp_s=0.0)
        sched = schedule.build_schedule(tr, args.step_seconds)
        prompts = schedule.prompt_tokens(sched, args.seed, cfg["vocab_size"])
        first, due_of = {}, {}

        def on_token(rid, token, done):
            if token is not None and rid not in first:
                first[rid] = now()

        t0, nxt, mid_q = now(), 0, None
        ticks0 = len(tracer.events("tick"))
        while True:
            t = now() - t0
            while nxt < len(sched) and sched[nxt].due_s <= t:
                rid = eng.add_request(prompts[nxt], sched[nxt].output_len,
                                      on_token=on_token)
                due_of[rid] = t0 + sched[nxt].due_s
                nxt += 1
            if mid_q is None and t >= args.step_seconds / 2:
                mid_q = len(eng._queue)
            if t >= args.step_seconds:
                break
            if eng.pending():
                eng.step()
            else:
                time.sleep(0.001)
        end_q = len(eng._queue)
        waiting = sum(1 for rid in due_of if rid not in first)
        ttft = [(first[r] - due_of[r]) * 1e3 for r in due_of if r in first]
        ticks = [e for e in tracer.events("tick")[ticks0:]
                 if e.get("budget_used")]
        print(json.dumps({
            "rate_rps": rate, "offered": len(sched), "queue_mid": mid_q,
            "queue_end": end_q, "no_first_token_yet": waiting,
            "ttft_p50_ms": stats.percentile(ttft, 50),
            "ttft_p90_ms": stats.percentile(ttft, 90),
            "tick_ms_p50": stats.percentile(
                [e["dur_s"] * 1e3 for e in ticks], 50),
            "occupancy": stats.reduce(
                [e["budget_used"] / e["token_budget"] for e in ticks],
                "mean"),
            "tok_s": sum(e["budget_used"] for e in ticks)
            / args.step_seconds,
            "active_end": int(eng._active.sum()),
            "blocks_high_water": eng.blocks_high_water}), flush=True)
        while eng.pending():        # drain before the next rate
            eng.step()
        eng.pop_finished()


if __name__ == "__main__":
    main()
