"""The serving driver of a ``longcat_flash`` configuration: one chip's
share through the ragged paged engine — two latent-attention sublayers and
two dense MLPs a layer, a shortcut expert branch routed over real and
zero-compute experts, two latent rows per token per layer in ONE pool.

The loop, the window, the ramp, the whole-tick ``serve_tok_s`` and the
pack taken from the ``tick`` event's ``rows`` are ``lib/serve.py``'s
(``offer``, ``whole_ticks``, ``packed_rows``, ``note_rounds``), the warm-up
through every table bucket ``lib/serve_latent.py``'s; both are imported.
This file has its own: the model's construction from the configuration
file (the program's ``LongcatFlashModel``; made BEFORE any weight, so a
program without the model exits at once), the four counters of the model's
tick, and ``correct`` against ``reference_longcat_flash`` by the latent
cell's rule:

- ``served_logit_gap``: prompt + served tokens of a few requests, the
  longest among them, through the reference's full forward pass; the
  WIDEST gap by which a served token's logit lies under the reference's
  best, over the served positions whose route margin is at least
  ``route_margin_eps``;
- ``route_near_tie_share``: the share of the served positions whose margin
  lies under it: counted, and left out of the gap.  A margin is the
  distance from the edge of the top 12 of any router output whose choice
  changes THIS chip's sum: a held real expert or any zero-compute expert.

With ``--control`` the reference in int8 (must fail) and in bfloat16 (the
witness the epsilon is set from) through the same comparison.
"""

import gc
import math
import time

import numpy as np

from . import (harness, reference_longcat_flash, serve, serve_latent,
               weights_longcat)


def model_config(cfg, **extra):
    """The program's ``LongcatFlashConfig`` from the configuration file."""
    from paddle_tpu.models.longcat_flash import LongcatFlashConfig
    same = ("vocab_size", "hidden_size", "num_layers", "num_attention_heads",
            "ffn_hidden_size", "q_lora_rank", "kv_lora_rank",
            "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim",
            "mla_scale_q_lora", "mla_scale_kv_lora",
            "expert_ffn_hidden_size", "moe_topk", "zero_expert_num",
            "zero_expert_type", "routed_scaling_factor", "rms_norm_eps",
            "rope_theta", "max_position_embeddings")
    return LongcatFlashConfig(
        **{k: cfg[k] for k in same},
        n_routed_experts=weights_longcat.real_experts(cfg),
        experts_held=range(*weights_longcat.held(cfg)),
        lora_norm_eps=cfg.get("lora_norm_eps", 1e-6),
        initializer_range=cfg.get("initializer_range", 0.02),
        compute_dtype=cfg.get("compute_dtype", "bfloat16"), **extra)


def meta_model(cfg):
    """The program's model object with no weights on the device (built
    under ``eval_shape``, as ``program.meta_model`` builds GPT's)."""
    import jax
    from paddle_tpu.core import rng
    from paddle_tpu.models.longcat_flash import LongcatFlashModel
    holder = {}

    def build(key):
        with rng.rng_scope(key):
            holder["model"] = LongcatFlashModel(model_config(cfg))
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


def run(ctx):
    from paddle_tpu.telemetry import Tracer

    cfg, traffic = ctx.config, ctx.traffic
    if ctx.rehearse:            # rehearse-overrides.json speaks GPT's keys
        cfg = ctx.config = harness.merge(cfg, traffic["rehearse"]["config"])
    ecfg = traffic["engine"]
    model_config(cfg)       # a program without this model fails here, at once
    params = weights_longcat.make_params(cfg, ctx.seed, cfg["compute_dtype"])
    tracer = Tracer(capacity=1 << 22)
    eng = build_engine(cfg, ecfg, params, tracer)
    with ctx.span("warm_up"):
        serve_latent.warm_up(eng, ecfg, cfg["vocab_size"])
    ctx.note(f"engine warmed: {eng.metrics()['compile_misses']} programs, "
             f"{time.monotonic() - ctx.t_start:.1f}s since start; "
             f"{weights_longcat.param_count(cfg)} parameters; decode-only "
             f"rounds on a program of {eng.narrow_rows} rows")

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
    layers = cfg["num_layers"]
    slots = len(range(*weights_longcat.held(cfg))) * layers
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
    real_pairs = sum(k.get("expert_pairs", 0) for k in in_win)
    zero_pairs = sum(k.get("zero_pairs", 0) for k in in_win)
    held_pairs = sum(k.get("expert_rows", 0) for k in in_win)
    rows = sum(k["budget_used"] for k in in_win)
    obs["counters"].update({
        "blocks_high_water": eng.blocks_high_water,
        "pool_blocks": ecfg["num_blocks"], "preemptions": eng.preemptions,
        "ragged_steps": m["ragged_steps"], "mixed_steps": m["mixed_steps"],
        "narrow_steps": m["narrow_steps"],
        "events_dropped": tracer.events_dropped,
        # of the pairs routed over the window's real rows and the layers:
        # those that went to zero-compute experts, all of them, and the
        # pairs a row sends to REAL experts a layer (of moe_topk)
        "zero_pairs": zero_pairs, "routed_pairs": real_pairs + zero_pairs})
    if rows:
        obs["counters"]["real_pairs_per_row"] = real_pairs / (rows * layers)
    R = ecfg["token_budget"] * min(cfg["moe_topk"], cfg["n_routed_experts"])
    ctx.note(f"pairs routed in the window: to real experts {real_pairs}, to "
             f"zero-compute experts {zero_pairs}, computed here {held_pairs} "
             f"({held_pairs / max(len(in_win) * layers, 1):.1f} a round a "
             f"layer, in a buffer of {R} rows at the budget's width); "
             f"fullest expert of a tick "
             f"{max((k.get('expert_rows_max', 0) for k in in_win), default=0)}"
             f"; at the engine's start {tracer.events('cache')}")
    serve.note_rounds(ctx, counted)
    if ctx.trace:
        obs["latent_ticks"] = {k["tick"]: serve.packed_rows(k, {})
                               for k in ticks}
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


def check_served(ctx, cfg, params, done):
    """``serve_latent.check_served`` against this model's reference."""
    if not done:
        ctx.check("served_requests_to_compare", 0, None, at_least=1)
        return
    rng = np.random.Generator(np.random.PCG64(ctx.seed))
    n = ctx.traffic.get("compare_requests", 3)
    longest = max(done, key=lambda r: len(r.prompt) + len(r.tokens))
    rest = [r for r in done if r is not longest]
    pick = [longest] + [rest[i] for i in
                        rng.permutation(len(rest))[:max(n - 1, 0)]]
    eps = ctx.limits["route_margin_eps"]
    pad_to = ctx.traffic.get("reference_pad_to", 1024)
    t0 = time.monotonic()
    at = served_positions(cfg, params, pick, pad_to=pad_to)
    got = served_gap(at, eps)
    ctx.note(f"compared {len(pick)} requests, {got['tokens']} served "
             f"tokens, longest {len(longest.prompt)}+{len(longest.tokens)}, "
             f"reference took {time.monotonic() - t0:.1f}s")
    ctx.check("served_logit_gap", got["widest"],
              ctx.limits["served_logit_gap"])
    ctx.check("route_near_tie_share", got["near"] / got["tokens"],
              ctx.limits["route_near_tie_share"])
    ctx.note(f"served_mean_gap {got['mean']!r} widest_gap_at_route_margin "
             f"{got['widest_margin']!r} widest_at_a_near_tie "
             f"{got['widest_near']!r} smallest_margin {got['margin_min']!r} "
             f"(printed, not compared)")
    if ctx.control:
        low = served_positions(cfg, params, pick, lower="int8",
                               pad_to=pad_to)
        b16 = served_positions(cfg, params, pick, lower="bfloat16",
                               pad_to=pad_to)
        ctx.check("control_int8_served_logit_gap",
                  served_gap(low, eps)["widest"],
                  ctx.limits["served_logit_gap"])
        # what the limits are set from: each reading at the epsilon that
        # is, and at its neighbours
        for name, some in (("served", at), ("control int8", low),
                           ("witness bfloat16", b16)):
            for e in (0.0, eps / 2, eps, 2 * eps, 4 * eps):
                g = served_gap(some, e)
                ctx.note(f"{name} at eps {e:.3g}: served_logit_gap "
                         f"{g['widest']!r} mean {g['mean']!r} near "
                         f"{g['near']} of {g['tokens']} widest_at_a_near_tie "
                         f"{g['widest_near']!r}")
            if some is not at:
                ctx.note(f"{name} moved the route margins by p50 / p99 / "
                         f"max {served_gap(some, eps)['margin_moved']!r} "
                         f"(route_margin_eps is set from the witness's)")


def served_positions(cfg, params, requests, lower=None, pad_to=1024):
    """What ``correct`` is decided from, for every served position of
    ``requests``: {"gap": by how much the served token's logit lies below
    the float32 reference's best, "margin": the position's route margin in
    that reference (the held and zero-compute experts' distance from the
    edge of the top k, the smallest over the layers), "moved"}.  With
    ``lower`` the token compared at each position is the one the lower
    precision puts first (the control of ``correct``), and ``moved`` is
    how far the lower precision moved the margin (else zeros)."""
    import jax
    import jax.numpy as jnp
    ref = reference_longcat_flash
    block = min(512, pad_to)
    out_pad = -(-max(len(r.tokens) for r in requests) // 128) * 128

    def one(params, ids, start, toks):
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
        return logits.max(-1) - got, margin, moved

    fn = jax.jit(one)
    out = {"gap": [], "margin": [], "moved": []}
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
        got = fn(params, jnp.asarray(ids), start, jnp.asarray(toks))
        for name, values in zip(out, got):
            out[name].append(np.asarray(values)[lo:lo + len(served)])
        print(f"[bench] reference over {L} positions "
              f"({len(served)} served): {time.monotonic() - t0:.1f}s",
              flush=True)
    return {name: np.concatenate(v) for name, v in out.items()}


def served_gap(at, eps):
    """``served_positions`` read at one epsilon: the WIDEST and the mean
    gap over the positions whose route margin is at least ``eps``
    (``widest_margin``: the margin where the widest lies), how many are
    ``near`` (under it) and the widest gap among those, the smallest
    margin, and (p50, p99, max) of ``moved``."""
    gap, margin = at["gap"], at["margin"]
    near = margin < eps
    far = np.where(near, 0.0, gap)
    return {"widest": float(far.max()), "mean": float(
                far.sum() / max(len(gap) - near.sum(), 1)),
            "tokens": len(gap), "near": int(near.sum()),
            "widest_near": float(np.where(near, gap, 0.0).max()),
            "widest_margin": float(margin[np.argmax(far)]),
            "margin_min": float(margin.min()),
            "margin_moved": [float(np.percentile(at["moved"], q))
                             for q in (50, 99, 100)]}
