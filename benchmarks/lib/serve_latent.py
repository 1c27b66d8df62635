"""The serving driver for a model that is not GPT: one chip's share of a
``pangu_ultra_moe`` configuration through the same ragged paged engine.

``lib/program.py``, ``lib/weights.py`` and ``lib/reference_gpt.py`` are
written for GPT's keys and GPT's two cache leaves.  The loop, the window,
the ramp, the whole-tick ``serve_tok_s`` and the pack taken from the
``tick`` event's ``rows`` are ``lib/serve.py``'s (``offer``,
``whole_ticks``, ``packed_rows``, ``note_rounds``); this file has its own:
the engine's construction (through the public entry points), the warm-up
for a table whose widest bucket is not a power of two, the expert
counters, and ``correct`` against ``reference_pangu_moe``.
"""

import gc
import math
import time

import numpy as np

from . import harness, program, reference_pangu_moe, serve, weights_pangu


def pangu_config(cfg, **extra):
    """The program's ``PanguMoeConfig`` from the configuration file."""
    from paddle_tpu.models.pangu_moe import PanguMoeConfig
    same = ("vocab_size", "hidden_size", "num_hidden_layers",
            "first_k_dense_replace", "num_attention_heads", "q_lora_rank",
            "kv_lora_rank", "qk_nope_head_dim", "qk_rope_head_dim",
            "v_head_dim", "intermediate_size", "moe_intermediate_size",
            "n_shared_experts", "num_experts_per_tok",
            "routed_scaling_factor", "norm_topk_prob", "rms_norm_eps",
            "rope_theta", "max_position_embeddings")
    return PanguMoeConfig(
        **{k: cfg[k] for k in same}, n_routed_experts=cfg["router_width"],
        experts_held=range(*weights_pangu.held(cfg)),
        initializer_range=cfg.get("initializer_range", 0.02),
        compute_dtype=cfg.get("compute_dtype", "bfloat16"), **extra)


def meta_model(cfg):
    """The program's model object with no weights on the device (built
    under ``eval_shape``, as ``program.meta_model`` builds GPT's)."""
    import jax
    from paddle_tpu.core import rng
    from paddle_tpu.models.pangu_moe import PanguMoeModel
    holder = {}

    def build(key):
        with rng.rng_scope(key):
            holder["model"] = PanguMoeModel(pangu_config(cfg))
        return {n: p._data for n, p in holder["model"].named_parameters()}

    jax.eval_shape(build, jax.random.key(0))
    return holder["model"]


def build_engine(cfg, engine, params, tracer):
    from paddle_tpu.serving import RaggedPagedContinuousBatchingEngine
    bs = engine["block_size"]
    return RaggedPagedContinuousBatchingEngine(
        meta_model(cfg), params, max_slots=engine["max_slots"],
        max_len=engine["max_len"], block_size=bs,
        num_blocks=engine["num_blocks"],
        prompt_buckets=list(range(bs, engine["max_len"] + 1, bs)),
        token_budget=engine["token_budget"], tracer=tracer)


def warm_up(eng, engine_cfg, vocab):
    """One request long enough for the widest table bucket the traffic
    reaches: its prefill passes through every bucket from its first
    chunk's (``token_budget`` rows) up, so one request compiles them all.
    Buckets below the first chunk's go through ``serve.warm_up``.  (A
    prompt of C/2 + 1 blocks lands in bucket C only where C is a power of
    two; the last bucket, ``max_len // block_size``, need not be.)"""
    from paddle_tpu.jit.bucketing import pow2_bucket
    bs = engine_cfg["block_size"]
    grid = program.table_widths(engine_cfg)
    widths = sorted(engine_cfg.get("warm_table_widths") or grid)
    first = pow2_bucket(-(-engine_cfg["token_budget"] // bs), grid[-1])
    small = [C for C in widths if C < first]
    if small:
        serve.warm_up(eng, dict(engine_cfg, warm_table_widths=small), vocab)
    if widths[-1] >= first:
        below = max([C for C in grid if C < widths[-1]], default=0)
        n = min((below + 1) * bs, engine_cfg["max_len"] - 2)
        eng.add_request([1 + (i % (vocab - 1)) for i in range(n)], 2)
        eng.run_to_completion()
        eng.pop_finished()


def run(ctx):
    from paddle_tpu.telemetry import Tracer

    cfg, traffic = ctx.config, ctx.traffic
    if ctx.rehearse:            # rehearse-overrides.json speaks GPT's keys
        cfg = ctx.config = harness.merge(cfg, traffic["rehearse"]["config"])
    ecfg = traffic["engine"]
    pangu_config(cfg)       # a program without this model fails here, at once
    params = weights_pangu.make_params(cfg, ctx.seed, cfg["compute_dtype"])
    tracer = Tracer(capacity=1 << 22)
    eng = build_engine(cfg, ecfg, params, tracer)
    with ctx.span("warm_up"):
        warm_up(eng, ecfg, cfg["vocab_size"])
    ctx.note(f"engine warmed: {eng.metrics()['compile_misses']} programs, "
             f"{time.monotonic() - ctx.t_start:.1f}s since start; "
             f"{weights_pangu.param_count(cfg)} parameters")

    live, in_window, (t_begin, w_open, w_close, t_end) = serve.offer(
        ctx, eng, cfg["vocab_size"])

    # ------------------------------------------------------ end to end --
    ttft = [(r.times[0] - r.due) * 1e3 if r.tokens else math.inf
            for r in in_window]
    ticks, counted, span_s = serve.whole_ticks(tracer, t_begin, w_open,
                                               w_close)
    e2e = {"serve_tok_s": (sum(k["budget_used"] for k in counted) / span_s
                           if counted else None)}
    failed = sum(1 for x in ttft if math.isinf(x))
    ctx.note(f"requests due in window {len(in_window)} unserved {failed} "
             f"ticks_counted {len(counted)} span_s "
             f"{span_s:.3f} end_after_close_s "
             f"{t_end - w_close:.3f}")

    # -------------------------------------------------- what readers read --
    in_win = [k for k in ticks if w_open <= k["end"] < w_close]
    lines = {t.rid: t for t in tracer.timelines()}
    slots = (len(range(*weights_pangu.held(cfg)))
             * weights_pangu.stack_layers(cfg)["moe"])
    obs = ctx.obs
    obs["series"].update({
        "gen_lag_ms": [(r.injected - r.due) * 1e3 for r in in_window
                       if r.injected is not None],
        "tick_ms": [k["dur_s"] * 1e3 for k in in_win],
        "occupancy_pct": [100.0 * k["budget_used"] / k["token_budget"]
                          for k in in_win],
        "queue_wait_ms": [
            (lines[r.rid].admitted_at - lines[r.rid].queued_at) * 1e3
            for r in in_window if r.rid in lines
            and lines[r.rid].admitted_at is not None],
        # the fullest held expert of a tick over the mean of all of them
        "expert_rows_max_over_mean": [
            k["expert_rows_max"] * slots / k["expert_rows"]
            for k in in_win if k.get("expert_rows")],
    })
    m = eng.metrics()
    obs["counters"].update({
        "blocks_high_water": eng.blocks_high_water,
        "pool_blocks": ecfg["num_blocks"], "preemptions": eng.preemptions,
        "ragged_steps": m["ragged_steps"], "mixed_steps": m["mixed_steps"],
        "events_dropped": tracer.events_dropped})
    pairs = sum(k.get("expert_pairs", 0) for k in in_win)
    ctx.note(f"expert pairs routed in the window {pairs}, computed here "
             f"{sum(k.get('expert_rows', 0) for k in in_win)}, fullest "
             f"expert of a tick "
             f"{max((k.get('expert_rows_max', 0) for k in in_win), default=0)}"
             f"; at the engine's start {tracer.events('cache')}")
    serve.note_rounds(ctx, counted)
    if ctx.trace:
        obs["latent_ticks"] = {k["tick"]: packed_rows(k) for k in ticks}
    ctx.read_memory()

    # --------------------------------------------------------- correct --
    # finished, or still running with 16 tokens served; what the window
    # produced either way
    done = [r for r in live if not r.replays
            and len(r.tokens) >= min(r.out_len, 16)]
    eng.caches = None
    del eng, tracer, ticks, lines
    gc.collect()
    check_served(ctx, cfg, params, done)
    ctx.check("compiles_in_window", ctx.compiles_in_window, 0)
    ctx.check("tracer_events_dropped", obs["counters"]["events_dropped"], 0)
    attempted = len(in_window) or sum(1 for r in live if r.rid is not None)
    return {"end_to_end": e2e, "attempted": attempted, "failed": failed}


def packed_rows(tick):
    """``serve.packed_rows`` as this cell's roofline has counted since PR
    28: a first chunk's left-pad rows taken out, a decode row's keys as
    the engine states them (its bucket's pad positions, under 16 of 4 k
    and more, among them)."""
    return serve.packed_rows(tick, {})


def check_served(ctx, cfg, params, done):
    """``serve.check_served`` against this model's reference: the widest
    gap by which a served token's logit lies below the reference's best,
    over a seeded sample of requests, the longest among them — at the
    positions whose routing is not a near-tie; the share of those that
    are is counted and held under a limit of its own."""
    if not done:
        ctx.check("served_requests_to_compare", 0, None, at_least=1)
        return
    rng = np.random.Generator(np.random.PCG64(ctx.seed))
    n = ctx.traffic.get("compare_requests", 4)
    longest = max(done, key=lambda r: len(r.prompt) + len(r.tokens))
    rest = [r for r in done if r is not longest]
    pick = [longest] + [rest[i] for i in
                        rng.permutation(len(rest))[:max(n - 1, 0)]]
    eps = ctx.limits["route_margin_eps"]
    pad_to = ctx.traffic.get("reference_pad_to", 1024)
    t0 = time.monotonic()
    got = served_gap(cfg, params, pick, eps, pad_to=pad_to)
    ctx.note(f"compared {len(pick)} requests, {got['tokens']} served "
             f"tokens, longest {len(longest.prompt)}+{len(longest.tokens)}, "
             f"reference took {time.monotonic() - t0:.1f}s")
    ctx.check("served_logit_gap", got["widest"],
              ctx.limits["served_logit_gap"])
    ctx.check("route_near_tie_share", got["near"] / got["tokens"],
              ctx.limits["route_near_tie_share"])
    # a widest gap at a margin just over the epsilon is a route that the
    # program's rounding flipped, not a wrong token (PERF.md, PR 32)
    ctx.note(f"served_mean_gap {got['mean']!r} widest_gap_at_route_margin "
             f"{got['widest_margin']!r} widest_at_a_near_tie "
             f"{got['widest_near']!r} smallest_margin {got['margin_min']!r} "
             f"(printed, not compared)")
    if ctx.control:
        low = served_gap(cfg, params, pick, eps, lower="int8", pad_to=pad_to)
        ctx.note(f"control served_logit_gap {low['widest']!r}")
        ctx.note(f"control served_mean_gap {low['mean']!r}")
        b16 = served_gap(cfg, params, pick, eps, lower="bfloat16",
                         pad_to=pad_to)
        ctx.note(f"bfloat16 reference: served_logit_gap {b16['widest']!r}, "
                 f"route margin moved by p50 {b16['margin_moved'][0]!r} "
                 f"p99 {b16['margin_moved'][1]!r} max "
                 f"{b16['margin_moved'][2]!r} (the rounding of the scores: "
                 f"route_margin_eps is set from it)")


def served_gap(cfg, params, requests, eps, lower=None, pad_to=1024):
    """{"widest", "mean", "tokens", "near", ...}: by how much a served
    token's logit lies below the float32 reference's best, at its widest
    and on average, over the served positions whose route margin (the
    held experts' distance from the edge of the top k, the smallest over
    the expert layers, in the float32 reference) is at least ``eps``;
    ``near`` counts the others.  With
    ``lower`` the token compared at each position is the one the lower
    precision puts first (the control of ``correct``), and
    ``margin_moved`` is (p50, p99, max) of how far the lower precision
    moved the margins."""
    import jax
    import jax.numpy as jnp
    ref = reference_pangu_moe
    block = min(512, pad_to)
    out_pad = -(-max(len(r.tokens) for r in requests) // 128) * 128

    def one(params, ids, start, toks, lo, hi):
        def rows(lower):
            h, margin = ref.hidden(cfg, params, ids, lower, block=block)
            h = jax.lax.dynamic_slice_in_dim(h, start, out_pad, axis=0)
            margin = jax.lax.dynamic_slice_in_dim(margin, start, out_pad, 0)
            return ref._matmul(h, params["lm_head"], lower), margin
        logits, margin = rows(None)
        moved = jnp.zeros_like(margin)
        if lower is not None:
            low, low_margin = rows(lower)
            toks = jnp.argmax(low, axis=-1)
            moved = jnp.abs(low_margin - margin)
        got = jnp.take_along_axis(logits, toks[:, None], axis=-1)[:, 0]
        at = jnp.arange(out_pad)
        served = (at >= lo) & (at < hi)
        gaps = jnp.where(served, logits.max(-1) - got, 0.0)
        near = served & (margin < eps)
        far = jnp.where(near, 0.0, gaps)
        return (far.max(), margin[jnp.argmax(far)], far.sum(), near.sum(),
                jnp.where(near, gaps, 0.0).max(),
                jnp.where(served, margin, jnp.inf).min(),
                jnp.where(served, moved, jnp.nan))

    fn = jax.jit(one)
    widest = summed = near = widest_near = 0.0
    widest_margin = None
    total, margin_min, moved = 0, math.inf, []
    for r in requests:
        served = list(r.tokens)
        ids = r.prompt + served[:-1]
        L = max(-(-len(ids) // pad_to) * pad_to, out_pad)
        # row ``start + j`` of the hidden states predicts served token j
        start = min(len(r.prompt) - 1, L - out_pad)
        lo = len(r.prompt) - 1 - start
        toks = np.zeros(out_pad, np.int32)
        toks[lo:lo + len(served)] = served
        ids = np.asarray(ids + [0] * (L - len(ids)), np.int32)
        t0 = time.monotonic()
        g, gm, gsum, n, gn, mm, mv = fn(
            params, jnp.asarray(ids), start, jnp.asarray(toks), lo,
            lo + len(served))
        if float(g) > widest or widest_margin is None:
            widest_margin = float(gm)
        widest, summed = max(widest, float(g)), summed + float(gsum)
        print(f"[bench] reference over {L} positions "
              f"({len(served)} served): {time.monotonic() - t0:.1f}s",
              flush=True)
        near, widest_near = near + int(n), max(widest_near, float(gn))
        margin_min = min(margin_min, float(mm))
        total += len(served)
        moved.append(np.asarray(mv))
    moved = np.concatenate(moved)
    moved = moved[~np.isnan(moved)]
    return {"widest": widest, "mean": summed / max(total - near, 1),
            "tokens": total, "near": near, "widest_near": widest_near,
            "widest_margin": widest_margin, "margin_min": margin_min,
            "margin_moved": [float(np.percentile(moved, q))
                             for q in (50, 99, 100)]}
