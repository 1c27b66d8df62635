"""Continuous-batching serving: requests join and leave a RUNNING decode
batch (paddle_tpu/serving.py — no reference counterpart; generation_utils
admits/retires whole batches).

Run (CPU):  JAX_PLATFORMS=cpu python examples/serve_continuous.py
Run (TPU):  python examples/serve_continuous.py   [--int8] [--mp N]

Shows the full serving story on one tiny model: staggered request budgets,
mid-flight admission, EOS retirement, the chunked host-sync knob, and the
int8 KV cache / tensor-parallel options.
"""
import argparse
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), ".."))

import numpy as np

import paddle_tpu as paddle


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--int8", action="store_true",
                    help="store the KV cache as int8 (half the HBM traffic)")
    ap.add_argument("--mp", type=int, default=1,
                    help="tensor-parallel degree (needs that many devices)")
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--ticks_per_sync", type=int, default=4)
    ap.add_argument("--speculative", action="store_true",
                    help="speculate inside the ragged paged engine (1-layer "
                         "draft): lossless, fewer rounds")
    ap.add_argument("--paged", action="store_true",
                    help="paged KV cache (block pool + tables): lazy HBM, "
                         "preemption, prefix caching")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp

    from paddle_tpu.models.gpt import GPTConfig, GPTModel
    from paddle_tpu.serving import ContinuousBatchingEngine

    paddle.seed(0)
    cfg = GPTConfig(vocab_size=512, hidden_size=128, num_layers=2,
                    num_attention_heads=4, max_position_embeddings=256,
                    compute_dtype="float32",
                    kv_cache_dtype="int8" if args.int8 else None)
    model = GPTModel(cfg)
    params = {n: p._data for n, p in model.named_parameters()}

    mesh = None
    if args.mp > 1:
        from jax.sharding import Mesh
        mesh = Mesh(np.array(jax.devices()[:args.mp]), ("model",))

    if (args.paged or args.speculative) and args.mp > 1:
        raise SystemExit("--paged and --speculative are single-mesh; "
                         "drop --mp")
    if args.speculative:
        dcfg = GPTConfig(vocab_size=512, hidden_size=128, num_layers=1,
                         num_attention_heads=4, max_position_embeddings=256,
                         compute_dtype="float32")
        draft = GPTModel(dcfg)
        dparams = {n: p._data for n, p in draft.named_parameters()}
        # speculation runs inside the ragged paged engine's tick, so the
        # cache is paged with or without --paged
        from paddle_tpu.serving import RaggedPagedContinuousBatchingEngine
        eng = RaggedPagedContinuousBatchingEngine(
            model, params, max_slots=args.slots, max_len=128,
            block_size=16, prompt_buckets=[16, 32], draft_model=draft,
            draft_params=dparams, draft_k=3)
    elif args.paged:
        from paddle_tpu.serving import PagedContinuousBatchingEngine
        # per-request sampling + prefix caching ride along: requests may
        # carry their own knobs, and repeated prompt prefixes reuse blocks
        eng = PagedContinuousBatchingEngine(
            model, params, max_slots=args.slots, max_len=128,
            block_size=16, prompt_buckets=[16, 32],
            ticks_per_sync=args.ticks_per_sync,
            enable_prefix_cache=True, per_request_sampling=True)
    else:
        eng = ContinuousBatchingEngine(
            model, params, max_slots=args.slots, max_len=128,
            prompt_buckets=[16, 32], ticks_per_sync=args.ticks_per_sync,
            mesh=mesh)

    rng = np.random.RandomState(0)
    t0 = time.time()
    # first wave: four requests with staggered budgets
    wave1 = [eng.add_request(list(rng.randint(1, 512, rng.randint(4, 17))),
                             int(n)) for n in (8, 16, 24, 32)]
    for _ in range(3):
        eng.step()
    # a second wave joins while the first is mid-decode
    perreq = args.paged and not args.speculative
    kw2 = [dict(repetition_penalty=1.5), dict()] if perreq else [{}, {}]
    wave2 = [eng.add_request(list(rng.randint(1, 512, rng.randint(4, 33))),
                             int(n), **k) for n, k in zip((12, 20), kw2)]
    out = eng.run_to_completion(max_ticks=10000)

    total = sum(len(v) for v in out.values())
    dt = time.time() - t0
    for rid in wave1 + wave2:
        print(f"request {rid}: {len(out[rid])} tokens, "
              f"first 8 = {out[rid][:8]}")
    extra = (f", spec rounds={eng.spec_rounds}" if args.speculative else "")
    if args.paged:
        extra += f", blocks hw={eng.blocks_high_water}"
        if not args.speculative:
            extra += f", prefix hits={eng.prefix_hits}"
    m = eng.metrics()
    print(f"\n{len(out)} requests, {total} tokens in {dt:.2f}s "
          f"({total / dt:.0f} tok/s) — slots={args.slots}, "
          f"ticks_per_sync={args.ticks_per_sync}, "
          f"kv={'int8' if args.int8 else 'fp'}, mp={args.mp}{extra}; "
          f"mean TTFT {m['mean_ttft_s'] * 1e3:.0f}ms, "
          f"mean latency {m['mean_latency_s'] * 1e3:.0f}ms")


if __name__ == "__main__":
    main()
