"""The serving driver of a ``deepseek_v32`` configuration: one chip's share
through the ragged paged engine, a lightning indexer choosing what each
row attends.

The loop, the window, the ramp, the whole-tick ``serve_tok_s`` and the
pack taken from the ``tick`` event's ``rows`` are ``lib/serve.py``'s, the
warm-up through every table bucket ``lib/serve_latent.py``'s; both are
imported.  This file has its own: the model's construction from the
configuration file (the program's ``PanguMoeModel`` under this file's
keys), the indexer's counters, and ``correct`` against
``reference_deepseek_v32``:

- ``served_mean_logit_gap``: prompt + served tokens of a few requests,
  the longest among them, through the reference's full forward pass; the
  gap by which a served token's logit lies under the reference's best,
  on average over the served positions whose routing is not a near-tie.
  The MEAN and not the widest, as the other serving cells compare: a
  top-k over near-continuous scores is discontinuous, with untrained
  weights a position at its edge carries as much attention as any other,
  and a row that attends another key feeds the next layer's indexer
  another input — so bfloat16 against float32 moves 0.6% of the first
  layer's selection and 17-21% of the fifth's (the float32 reference
  rounded to bfloat16 does the same), and the widest gap of a sound run
  reads 0.7-2.7 where the int8 control reads 2.0-3.1 (PERF.md, PR 36).
  The widest is printed;
- ``route_near_tie_share``: the share of positions that are near-ties;
- ``selection_overlap``: the same requests once more through the
  program's own ``decode_ragged`` over a fresh pool, each in a slot of
  its own and their served rows together in one pack (the tick's
  functions, the selection returned: ``selection_of``), and at the
  served positions, in every layer, the positions both the program
  and the reference selected over the larger of the two sets.  A program
  that selects nothing reads ``min(k, t + 1) / (t + 1)``; one that
  selects by position reads what chance gives;
- ``selection_overlap_first_layer``: the same of the first layer alone,
  whose input is the embedding on both sides: no selection upstream, so
  it reads what the indexer's own arithmetic costs, and is the check a
  lower precision fails first.
"""

import functools
import gc
import math
import time

import numpy as np

from . import harness, reference_deepseek_v32, serve, serve_latent, \
    weights_dsv32


def model_config(cfg):
    """The program's configuration object from the configuration file."""
    from paddle_tpu.models.pangu_moe import PanguMoeConfig
    same = ("vocab_size", "hidden_size", "num_hidden_layers",
            "first_k_dense_replace", "num_attention_heads", "q_lora_rank",
            "kv_lora_rank", "qk_nope_head_dim", "qk_rope_head_dim",
            "v_head_dim", "intermediate_size", "moe_intermediate_size",
            "n_shared_experts", "num_experts_per_tok",
            "routed_scaling_factor", "norm_topk_prob", "rms_norm_eps",
            "rope_theta", "max_position_embeddings", "sandwich_norm",
            "n_group", "topk_group", "topk_method", "index_topk",
            "index_n_heads", "index_head_dim")
    scaling = {k: v for k, v in cfg["rope_scaling"].items() if k != "mscale"}
    return PanguMoeConfig(
        **{k: cfg[k] for k in same}, rope_scaling=scaling,
        n_routed_experts=cfg["router_width"],
        experts_held=range(*weights_dsv32.held(cfg)),
        initializer_range=cfg.get("initializer_range", 0.02),
        compute_dtype=cfg.get("compute_dtype", "bfloat16"))


def meta_model(cfg):
    """The program's model object with no weights on the device."""
    import jax
    from paddle_tpu.core import rng
    from paddle_tpu.models.pangu_moe import PanguMoeModel
    holder = {}

    def build(key):
        with rng.rng_scope(key):
            holder["model"] = PanguMoeModel(model_config(cfg))
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
    # a program without this model (no such keys) fails here, at once
    model_config(cfg)
    params = weights_dsv32.make_params(cfg, ctx.seed, cfg["compute_dtype"])
    tracer = Tracer(capacity=1 << 22)
    eng = build_engine(cfg, ecfg, params, tracer)
    with ctx.span("warm_up"):
        serve_latent.warm_up(eng, ecfg, cfg["vocab_size"])
    ctx.note(f"engine warmed: {eng.metrics()['compile_misses']} programs, "
             f"{time.monotonic() - ctx.t_start:.1f}s since start; "
             f"{weights_dsv32.param_count(cfg)} parameters")

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
    slots = (len(range(*weights_dsv32.held(cfg)))
             * weights_dsv32.stack_layers(cfg)["moe"])
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
        "expert_rows_max_over_mean": [
            k["expert_rows_max"] * slots / k["expert_rows"]
            for k in in_win if k.get("expert_rows")],
    })
    m = eng.metrics()
    obs["counters"].update({
        "blocks_high_water": eng.blocks_high_water,
        "pool_blocks": ecfg["num_blocks"], "preemptions": eng.preemptions,
        "ragged_steps": m["ragged_steps"], "mixed_steps": m["mixed_steps"],
        "events_dropped": tracer.events_dropped,
        # over the window's rounds: the kv positions the indexer's rows
        # scored and those they went on to attend (the program's counters)
        "index_candidates": sum(k.get("index_candidates", 0)
                                for k in in_win),
        "index_selected": sum(k.get("index_selected", 0) for k in in_win)})
    ctx.note(f"index candidates in the window "
             f"{obs['counters']['index_candidates']}, selected "
             f"{obs['counters']['index_selected']}; expert pairs routed "
             f"{sum(k.get('expert_pairs', 0) for k in in_win)}, computed "
             f"here {sum(k.get('expert_rows', 0) for k in in_win)}; at the "
             f"engine's start {tracer.events('cache')}")
    serve.note_rounds(ctx, counted)
    if ctx.trace:
        obs["sparse_ticks"] = {k["tick"]: serve_latent.packed_rows(k)
                               for k in ticks}
    ctx.read_memory()

    # --------------------------------------------------------- correct --
    done = [r for r in live if not r.replays
            and len(r.tokens) >= min(r.out_len, 16)]
    model = eng.model
    eng.caches = None
    del eng, tracer, ticks, lines
    gc.collect()
    check_served(ctx, cfg, model, params, done)
    ctx.check("compiles_in_window", ctx.compiles_in_window, 0)
    ctx.check("tracer_events_dropped", obs["counters"]["events_dropped"], 0)
    attempted = len(in_window) or sum(1 for r in live if r.rid is not None)
    return {"end_to_end": e2e, "attempted": attempted, "failed": failed}


def check_served(ctx, cfg, model, params, done):
    """The comparisons of this cell (the module's docstring) over a seeded
    sample of requests, the longest among them; with ``--control`` each
    control and the witness through the same checks."""
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
    pad_to = ctx.traffic.get("reference_pad_to", 4096)
    t0 = time.monotonic()
    mine = program_selection(model, params, pick, ctx.traffic["engine"])
    ctx.note(f"the program's selection of {len(pick)} requests once more: "
             f"{time.monotonic() - t0:.1f}s")
    t0 = time.monotonic()
    ref = reference_rows(cfg, params, pick, pad_to)
    got = served_gap(pick, eps, pad_to, ref, mine=mine)
    ctx.note(f"compared {len(pick)} requests, {got['tokens']} served "
             f"tokens, longest {len(longest.prompt)}+{len(longest.tokens)}, "
             f"reference took {time.monotonic() - t0:.1f}s")
    compare(ctx, got)
    ctx.note(f"served_logit_gap {got['widest']!r} widest_gap_at_route_margin "
             f"{got['widest_margin']!r} widest_at_a_near_tie "
             f"{got['widest_near']!r} smallest_margin {got['margin_min']!r} "
             f"lowest_row_overlap {got['overlap_min']!r} overlap_by_layer "
             f"{got['overlap_by_layer']!r} gap p50 p90 p99 "
             f"{got['gap_quantiles']!r} route margin moved by "
             f"{got['margin_moved']!r} (printed, not compared)")
    if ctx.control:
        # each control (one more reference pass, against the float32 one
        # kept above) through the checks the run itself went through.  The
        # three controls must each fail one, so a run with ``--control``
        # ends ``correct: false`` by these lines; the witness — the
        # reference rounded to the precision the configuration states,
        # which no sound program can be nearer than — must pass them all
        for name, kw in (("control_int8", dict(lower="int8")),
                         ("control_dense", dict(select="dense")),
                         ("control_recent", dict(select="recent")),
                         ("witness_bfloat16", dict(lower="bfloat16"))):
            low = served_gap(pick, eps, pad_to, ref, low=reference_rows(
                cfg, params, pick, pad_to, **kw))
            compare(ctx, low, name + ".")
            ctx.note(f"{name}: served_logit_gap {low['widest']!r} "
                     f"gap p50 p90 p99 {low['gap_quantiles']!r} near "
                     f"{low['near']} of {low['tokens']} overlap by layer "
                     f"{low['overlap_by_layer']!r} (its own selection "
                     f"against the reference's) route margin moved by "
                     f"p50 p99 max {low['margin_moved']!r}")


def compare(ctx, got, prefix=""):
    """The four numbers of ``served_gap`` beside their limits."""
    ctx.check(prefix + "served_mean_logit_gap", got["mean"],
              ctx.limits["served_mean_logit_gap"])
    ctx.check(prefix + "route_near_tie_share", got["near"] / got["tokens"],
              ctx.limits["route_near_tie_share"])
    ctx.check(prefix + "selection_overlap", got["overlap"], None,
              at_least=ctx.limits["selection_overlap"])
    ctx.check(prefix + "selection_overlap_first_layer",
              got["overlap_by_layer"][0], None,
              at_least=ctx.limits["selection_overlap_first_layer"])


def _out_pad(requests):
    return -(-max(len(r.tokens) for r in requests) // 128) * 128


def selection_tick(model, engine_cfg, slots, rows):
    """``model.decode_ragged`` — the tick's function — over a pool of
    ``slots`` sequences (table row ``i`` = blocks ``1 + i * C ...``), which
    beside the pools returns the mask the attention applied to pack rows
    [0, slots * rows) in every layer, bit-packed along the kv positions:
    ``tick(params, pools, toks, seq, pos) -> (pools, mask)``."""
    import jax
    import jax.numpy as jnp
    C = engine_cfg["max_len"] // engine_cfg["block_size"]
    table = 1 + jnp.arange(slots * C, dtype=jnp.int32).reshape(slots, C)
    no_pad = jnp.zeros((slots,), jnp.int32)

    @functools.partial(jax.jit, donate_argnums=(1,))
    def tick(params, pools, toks, seq, pos):
        h = model._embed_ragged(params, toks, None, None, None)
        out = model.decode_ragged(params, h, pools, table, seq, pos, no_pad,
                                  selection_of=(0, slots * rows))
        return out[1], jnp.packbits(out[3], axis=-1)
    return tick


def program_selection(model, params, requests, engine_cfg):
    """For each request, the program's own selection at its served
    positions: prompt + served tokens through ``selection_tick`` over a
    fresh pool in which every request has a slot of its own, packed as
    the engine packs (real rows first, padding rows at sequence -1).
    Each request's rows but its last ``rows`` go in chunks of the token
    budget; the last ``rows`` of ALL of them share the final pack, as the
    live slots share a timed tick, and that pack's mask is kept.  Returns
    [(the mask (layers, rows, C * bs / 8) on the device; the kv position
    of its first row)]."""
    import jax.numpy as jnp
    from paddle_tpu.models._decode import build_pools
    bs, T = engine_cfg["block_size"], engine_cfg["token_budget"]
    C, S = engine_cfg["max_len"] // bs, len(requests)
    rows = min(_out_pad(requests), T // S)
    tick = selection_tick(model, engine_cfg, S, rows)

    def pack(parts):
        """One pack of [(slot, ids, first position)], real rows first."""
        toks = np.zeros(T, np.int32)
        seq, pos = np.full(T, -1, np.int32), np.full(T, -1, np.int32)
        n = 0
        for slot, ids, first in parts:
            at = slice(n, n + len(ids))
            toks[at], seq[at] = ids, slot
            pos[at] = first + np.arange(len(ids))
            n += len(ids)
        return jnp.asarray(toks), jnp.asarray(seq), jnp.asarray(pos)

    pools = build_pools(model.cache_spec(), (S * C + 1, bs))
    every = [np.asarray(r.prompt + list(r.tokens)[:-1], np.int32)
             for r in requests]
    for slot, (r, ids) in enumerate(zip(requests, every)):
        n = len(ids) - rows
        assert 0 <= n and len(ids) <= C * bs and len(r.tokens) <= rows, \
            (len(ids), len(r.tokens), rows)
        # chunks of T positions ending at n, the first one short
        edges = [0] + list(range(n, 0, -T))[::-1]
        for a, b in zip(edges, edges[1:]):
            pools, _ = tick(params, pools, *pack([(slot, ids[a:b], a)]))
    pools, sel = tick(params, pools, *pack(
        [(slot, ids[-rows:], len(ids) - rows)
         for slot, ids in enumerate(every)]))
    del pools
    return [(sel[:, slot * rows:(slot + 1) * rows], len(ids) - rows)
            for slot, ids in enumerate(every)]


def reference_rows(cfg, params, requests, pad_to, lower=None,
                   select="indexer"):
    """The reference's full forward pass over prompt + served tokens of
    each request, padded to a multiple of ``pad_to``, and of it the
    ``_out_pad`` rows that end with the one predicting the last served
    token: [(logits (rows, V), route margin (rows,), each layer's
    selection (layers, rows, L / 8) bit-packed)], on the device.
    ``lower`` / ``select``: a control (``reference_deepseek_v32``)."""
    import jax
    import jax.numpy as jnp
    ref = reference_deepseek_v32
    segments = 4
    block = min(256, pad_to // segments)

    @jax.jit
    def rows(params, ids, start):
        h, margin, chosen = ref.hidden(
            cfg, params, ids, lower, block=block, segments=segments,
            select=select, window=(start, _out_pad(requests)))
        return (ref._matmul(h, params["lm_head"], lower), margin,
                jnp.packbits(chosen, axis=-1))

    out = []
    for r in requests:
        ids, start, _ = _placed(r, requests, pad_to)
        t0 = time.monotonic()
        out.append(jax.block_until_ready(
            rows(params, jnp.asarray(ids), start)))
        print(f"[bench] reference ({lower or 'float32'}, {select}) over "
              f"{len(ids)} positions ({len(r.tokens)} served): "
              f"{time.monotonic() - t0:.1f}s", flush=True)
    return out


def _placed(r, requests, pad_to):
    """(prompt + served tokens but the last, padded; the row ``start``
    from which ``_out_pad`` rows are read; ``lo``: row ``start + lo``
    predicts the first served token)."""
    out_pad = _out_pad(requests)
    ids = r.prompt + list(r.tokens)[:-1]
    L = max(-(-len(ids) // pad_to) * pad_to, out_pad)
    start = min(len(r.prompt) - 1, L - out_pad)
    return (np.asarray(ids + [0] * (L - len(ids)), np.int32), start,
            len(r.prompt) - 1 - start)


def served_gap(requests, eps, pad_to, ref, mine=None, low=None):
    """``serve_latent.served_gap`` over ``ref``, the float32
    ``reference_rows`` of ``requests``, and the overlap of selections:
    {"widest", "mean", "tokens", "near", ..., "overlap", "overlap_min"}.
    With ``mine`` (``program_selection`` of the same requests) the tokens
    are the served ones and the overlap is of the program's selection with
    the reference's.  With ``low`` (a control's ``reference_rows``) the
    token compared at each position is the one the control puts first,
    and the overlap is of the control's own selection with the
    reference's."""
    import jax
    import jax.numpy as jnp
    out_pad = _out_pad(requests)

    @jax.jit
    def one(logits, margin, chosen, toks, lo, hi, theirs, shift, low):
        at = jnp.arange(out_pad)
        served = (at >= lo) & (at < hi)
        moved = jnp.zeros_like(margin)
        chosen, theirs = (jnp.unpackbits(x, axis=-1) != 0
                          for x in (chosen, theirs))
        if low is not None:
            toks, moved = jnp.argmax(low[0], -1), jnp.abs(low[1] - margin)
        else:
            # the program's rows begin ``shift`` rows after the
            # reference's: bring them under the reference's (rows outside
            # it are not served rows)
            n = min(theirs.shape[2], chosen.shape[2])
            theirs = jnp.roll(jnp.pad(
                theirs[:, :, :n], ((0, 0), (0, out_pad), (0, 0))),
                shift, axis=1)[:, :out_pad]
            chosen = chosen[:, :, :n]
        both = jnp.sum(chosen & theirs, -1)
        larger = jnp.maximum(jnp.sum(chosen, -1), jnp.sum(theirs, -1))
        share = jnp.where(served[None], both / jnp.maximum(larger, 1), 1.0)
        got = jnp.take_along_axis(logits, toks[:, None], axis=-1)[:, 0]
        gaps = jnp.where(served, logits.max(-1) - got, 0.0)
        near = served & (margin < eps)
        far = jnp.where(near, 0.0, gaps)
        return (far.max(), margin[jnp.argmax(far)], far.sum(), near.sum(),
                jnp.where(near, gaps, 0.0).max(),
                jnp.where(served, margin, jnp.inf).min(),
                jnp.where(served, moved, jnp.nan),
                jnp.sum(jnp.where(served[None], both, 0), -1),
                jnp.sum(jnp.where(served[None], larger, 0), -1),
                share.min(), jnp.where(served & ~near, gaps, jnp.nan))

    widest = summed = near = widest_near = 0.0
    widest_margin = None
    total, margin_min, moved, both, larger, share_min, far = \
        0, math.inf, [], 0, 0, 1.0, []
    for i, r in enumerate(requests):
        served = list(r.tokens)
        _, start, lo = _placed(r, requests, pad_to)
        toks = np.zeros(out_pad, np.int32)
        toks[lo:lo + len(served)] = served
        theirs, shift = (low[i][2], 0) if low is not None \
            else (mine[i][0], mine[i][1] - start)
        g, gm, gsum, n, gn, mm, mv, b, lg, sm, fg = one(
            *ref[i], jnp.asarray(toks), lo, lo + len(served), theirs, shift,
            None if low is None else low[i][:2])
        if float(g) > widest or widest_margin is None:
            widest_margin = float(gm)
        widest, summed = max(widest, float(g)), summed + float(gsum)
        near, widest_near = near + int(n), max(widest_near, float(gn))
        margin_min = min(margin_min, float(mm))
        total += len(served)
        both, larger = both + np.asarray(b), larger + np.asarray(lg)
        share_min = min(share_min, float(sm))
        moved.append(np.asarray(mv))
        far.append(np.asarray(fg))
    moved = np.concatenate(moved)
    moved = moved[~np.isnan(moved)]
    far = np.concatenate(far)
    far = far[~np.isnan(far)]
    return {"widest": widest, "mean": summed / max(total - near, 1),
            "tokens": total, "near": near, "widest_near": widest_near,
            "widest_margin": widest_margin, "margin_min": margin_min,
            "margin_moved": [float(np.percentile(moved, q))
                             for q in (50, 99, 100)],
            "overlap": float(both.sum() / max(larger.sum(), 1)),
            "overlap_min": share_min,
            "overlap_by_layer": (both / np.maximum(larger, 1)).tolist(),
            "gap_quantiles": [float(np.percentile(far, q))
                              for q in (50, 90, 99)] if far.size else []}
