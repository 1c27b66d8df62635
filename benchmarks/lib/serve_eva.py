"""The serving driver of an ``evabyte`` configuration: the first pipeline
stage through the ragged paged engine, its cache a window leaf that does
not page beside a summary leaf that pages by chunk.

The loop, the window, the ramp, the whole-tick ``serve_tok_s`` and the
pack taken from the ``tick`` event's ``rows`` are ``lib/serve.py``'s, the
warm-up through every table bucket ``lib/serve_latent.py``'s; both are
imported.  This file has its own: the model's construction from the
configuration file (the program's ``EvaByteModel``), the three counters of
the model's tick, and ``correct`` against ``reference_evabyte``:

- ``served_logit_gap``: prompt + served tokens of a few requests, the
  longest among them, through the reference's full forward pass in
  ``window_size``-row blocks; the WIDEST gap by which a served token's
  logit (head 0) lies under the reference's best.  A dense model: no
  routing and no selection, so nothing cascades and the widest gap is
  usable, as in the GPT cell.

With ``--control`` the controls that must fail (the reference's matrices
in int8; summaries off; the previous window's summaries only) and the
witness that must pass (the reference rounded to bfloat16), each through
the same check: such a run ends ``correct: false`` by design.
"""

import gc
import math
import time

import numpy as np

from . import harness, reference_evabyte, serve, serve_latent, \
    weights_evabyte

CONTROLS = (("control_int8", dict(lower="int8")),
            ("control_no_summaries", dict(summaries="off")),
            ("control_previous_window", dict(summaries="previous")),
            ("witness_bfloat16", dict(lower="bfloat16")))


def model_config(cfg):
    """The program's configuration object from the configuration file."""
    from paddle_tpu.models.evabyte import EvaByteConfig
    same = ("vocab_size", "hidden_size", "num_hidden_layers",
            "num_attention_heads", "intermediate_size", "num_pred_heads",
            "chunk_size", "window_size", "max_position_embeddings",
            "rope_theta", "rms_norm_eps", "norm_add_unit_offset", "init_std")
    return EvaByteConfig(**{k: cfg[k] for k in same},
                         compute_dtype=cfg.get("compute_dtype", "bfloat16"))


def meta_model(cfg):
    """The program's model object with no weights on the device."""
    import jax
    from paddle_tpu.core import rng
    from paddle_tpu.models.evabyte import EvaByteModel
    holder = {}

    def build(key):
        with rng.rng_scope(key):
            holder["model"] = EvaByteModel(model_config(cfg))
        return {n: p._data for n, p in holder["model"].named_parameters()}

    jax.eval_shape(build, jax.random.key(0))
    return holder["model"]


def block_positions(cfg, engine):
    """Positions one block of the summary leaf names."""
    return engine["block_size"] * cfg["chunk_size"]


def build_engine(cfg, engine, params, tracer):
    from paddle_tpu.serving import RaggedPagedContinuousBatchingEngine
    bs = engine["block_size"]
    return RaggedPagedContinuousBatchingEngine(
        meta_model(cfg), params, max_slots=engine["max_slots"],
        max_len=engine["max_len"], block_size=bs,
        num_blocks=engine["num_blocks"],
        prompt_buckets=list(range(bs, engine["max_len"] + 1, bs)),
        token_budget=engine["token_budget"], tracer=tracer)


def run(ctx):
    from paddle_tpu.telemetry import Tracer

    cfg, traffic = ctx.config, ctx.traffic
    if ctx.rehearse:            # rehearse-overrides.json speaks GPT's keys
        cfg = ctx.config = harness.merge(cfg, traffic["rehearse"]["config"])
    ecfg = traffic["engine"]
    # a program without this model fails here, at once
    model_config(cfg)
    params = weights_evabyte.make_params(cfg, ctx.seed, cfg["compute_dtype"])
    tracer = Tracer(capacity=1 << 22)
    eng = build_engine(cfg, ecfg, params, tracer)
    with ctx.span("warm_up"):
        # the table counts blocks of ``block_positions`` positions
        serve_latent.warm_up(
            eng, dict(ecfg, block_size=block_positions(cfg, ecfg)),
            cfg["vocab_size"])
    ctx.note(f"engine warmed: {eng.metrics()['compile_misses']} programs, "
             f"{time.monotonic() - ctx.t_start:.1f}s since start; "
             f"{weights_evabyte.param_count(cfg)} parameters")

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
    })
    m = eng.metrics()
    window_keys = sum(k.get("eva_window_keys", 0) for k in in_win)
    summary_keys = sum(k.get("eva_summary_keys", 0) for k in in_win)
    obs["counters"].update({
        "blocks_high_water": eng.blocks_high_water,
        "pool_blocks": ecfg["num_blocks"], "preemptions": eng.preemptions,
        "ragged_steps": m["ragged_steps"], "mixed_steps": m["mixed_steps"],
        "events_dropped": tracer.events_dropped,
        # over the window's rounds: the keys the rows attended, exact and
        # summaries (the program's counters, summed over the layers)
        "eva_summary_keys": summary_keys,
        "eva_keys": window_keys + summary_keys,
        "eva_chunks_closed": sum(k.get("eva_chunks_closed", 0)
                                 for k in in_win)})
    finished = sum(1 for r in live if r.tokens
                   and len(r.tokens) >= r.out_len
                   and w_open <= r.times[-1] < w_close)
    ctx.note(f"window keys attended in the window {window_keys}, summary "
             f"keys {summary_keys}, chunks closed "
             f"{obs['counters']['eva_chunks_closed']}; requests finished "
             f"in the window {finished}; preemptions {eng.preemptions}; at "
             f"the engine's start {tracer.events('cache')}")
    serve.note_rounds(ctx, counted)
    if ctx.trace:
        obs["eva_ticks"] = {k["tick"]: serve_latent.packed_rows(k)
                            for k in ticks}
    ctx.read_memory()

    # --------------------------------------------------------- correct --
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


def gap_limit(ctx):
    """The cell's limit of ``served_logit_gap``; a rehearsal — float32 and
    tiny, so exact where the chip's bfloat16 is not — states its own in the
    traffic file's ``rehearse`` section."""
    return ctx.traffic.get("limits", ctx.limits)["served_logit_gap"]


def check_served(ctx, cfg, params, done):
    """``serve.check_served`` against this model's reference; with
    ``--control`` each control and the witness through the same check."""
    if not done:
        ctx.check("served_requests_to_compare", 0, None, at_least=1)
        return
    rng = np.random.Generator(np.random.PCG64(ctx.seed))
    n = ctx.traffic.get("compare_requests", 3)
    longest = max(done, key=lambda r: len(r.prompt) + len(r.tokens))
    rest = [r for r in done if r is not longest]
    pick = [longest] + [rest[i] for i in
                        rng.permutation(len(rest))[:max(n - 1, 0)]]
    pad_to = ctx.traffic.get("reference_pad_to", cfg["window_size"])
    t0 = time.monotonic()
    got = served_gap(cfg, params, pick, pad_to)
    ctx.note(f"compared {len(pick)} requests, {got['tokens']} served "
             f"tokens, longest {len(longest.prompt)}+{len(longest.tokens)}, "
             f"reference took {time.monotonic() - t0:.1f}s")
    ctx.check("served_logit_gap", got["widest"], gap_limit(ctx))
    ctx.note(f"served_mean_gap {got['mean']!r} gap p50 p90 p99 "
             f"{got['quantiles']!r} (printed, not compared)")
    if ctx.control:
        for name, kw in CONTROLS:
            low = served_gap(cfg, params, pick, pad_to, **kw)
            ctx.check(f"{name}.served_logit_gap", low["widest"],
                      gap_limit(ctx))
            ctx.note(f"{name}: served_mean_gap {low['mean']!r} gap p50 p90 "
                     f"p99 {low['quantiles']!r}")


def served_gap(cfg, params, requests, pad_to, lower=None, summaries="all"):
    """{"widest", "mean", "tokens", "quantiles"}: by how much a served
    token's logit lies below the float32 reference's best, over the
    served positions.  With ``lower`` or ``summaries`` (a control) the
    token compared at each position is the one the control puts first."""
    import jax
    import jax.numpy as jnp
    ref = reference_evabyte
    out_pad = -(-max(len(r.tokens) for r in requests) // 128) * 128
    control = lower is not None or summaries != "all"

    def one(params, ids, start, toks, lo, hi):
        def rows(lower, summaries):
            h = ref.hidden(cfg, params, ids, lower, summaries=summaries)
            h = jax.lax.dynamic_slice_in_dim(h, start, out_pad, axis=0)
            return ref.logits(cfg, params, h, lower)
        logits = rows(None, "all")
        if control:
            toks = jnp.argmax(rows(lower, summaries), axis=-1)
        got = jnp.take_along_axis(logits, toks[:, None], axis=-1)[:, 0]
        at = jnp.arange(out_pad)
        served = (at >= lo) & (at < hi)
        gaps = jnp.where(served, logits.max(-1) - got, 0.0)
        return gaps.max(), gaps.sum(), jnp.where(served, gaps, jnp.nan)

    fn = jax.jit(one)
    widest, summed, total, every = 0.0, 0.0, 0, []
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
        g, gsum, gaps = fn(params, jnp.asarray(ids), start,
                           jnp.asarray(toks), lo, lo + len(served))
        widest, summed = max(widest, float(g)), summed + float(gsum)
        print(f"[bench] reference ({lower or 'float32'}, summaries "
              f"{summaries}) over {L} positions ({len(served)} served): "
              f"{time.monotonic() - t0:.1f}s", flush=True)
        total += len(served)
        gaps = np.asarray(gaps)
        every.append(gaps[~np.isnan(gaps)])
    every = np.concatenate(every)
    return {"widest": widest, "mean": summed / max(total, 1),
            "tokens": total,
            "quantiles": [float(np.percentile(every, q))
                          for q in (50, 90, 99)] if every.size else []}
