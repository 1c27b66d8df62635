"""The serving driver: one process, one loop, a fixed schedule.

The loop injects every request whose due time has passed, then calls
``engine.step()``.  There is no generator thread: how late an injection
ran is measured (``gen_lag_ms``), so a starved generator cannot pass for a
fast server.  The generator starts ``ramp_s`` before the window, inside
set-up, so the window opens on a loaded engine; requests due inside the
window are followed to their first token for at most ``drain_s`` after it.
"""

import gc
import math
import time

import numpy as np

from . import program, reference_gpt, schedule, stats, weights


class Live:
    """One scheduled request as the benchmark sees it."""

    __slots__ = ("due", "prompt", "out_len", "rid", "injected", "tokens",
                 "times", "replays")

    def __init__(self, due, prompt, out_len):
        self.due, self.prompt, self.out_len = due, prompt, out_len
        self.rid = self.injected = None
        self.tokens, self.times, self.replays = [], [], 0


def warm_up(eng, engine_cfg, vocab):
    """Serve one short request alone for every table-width bucket this
    cell's traffic reaches (``warm_table_widths`` of the traffic file; all
    of the engine's if it names none), so every program a tick can
    dispatch is compiled, or loaded from the cache, before the ramp.  A
    prompt of (C/2 + 1) blocks lands in bucket C; through the public entry
    points only.  A width left out and reached all the same compiles
    inside the window, and the run is then not correct."""
    bs = engine_cfg["block_size"]
    widths = engine_cfg.get("warm_table_widths") or \
        program.table_widths(engine_cfg)
    for C in widths:
        n = bs if C == 1 else (C // 2 + 1) * bs
        eng.add_request([1 + (i % (vocab - 1)) for i in range(n)], 2)
        eng.run_to_completion()
    eng.pop_finished()


def offer(ctx, eng, vocab):
    """Offer the cell's schedule to ``eng`` through the ramp and the
    window: the one loop of both serving drivers.  Returns every scheduled
    request, those due inside the window, and (ramp start, window open,
    window close, end of the loop) on ``time.monotonic``."""
    traffic = ctx.traffic
    sched = schedule.build_schedule(traffic, ctx.seconds)
    prompts = schedule.prompt_tokens(sched, ctx.seed, vocab)
    ctx.note(f"schedule digest {schedule.digest(sched)} "
             f"requests {len(sched)} seed {ctx.seed}")
    ramp, drain = traffic.get("ramp_s", 0.0), traffic.get("drain_s", 0.0)

    now = time.monotonic
    by_rid = {}

    def on_token(rid, token, done):
        r = by_rid[rid]
        if token is None:           # preempted: the stream starts over
            r.tokens, r.times = [], []
            r.replays += 1
            return
        r.tokens.append(int(token))
        r.times.append(now())

    def inject(r):
        r.injected = now()
        r.rid = eng.add_request(r.prompt, r.out_len, on_token=on_token)
        by_rid[r.rid] = r

    # a backlog is due before the ramp starts, and is in the engine's queue
    # before the ramp's clock does: handing over a long one takes the host
    # a good part of a second, and the ramp is the engine's to run in
    live = [Live(s.due_s, p, s.output_len) for s, p in zip(sched, prompts)]
    nxt = 0
    while nxt < len(live) and live[nxt].due <= -ramp:
        inject(live[nxt])
        nxt += 1
    t_begin = now()
    if nxt:
        ctx.note(f"backlog queued: {nxt} requests in "
                 f"{t_begin - live[0].injected:.3f}s")
    w_open, w_close = t_begin + ramp, t_begin + ramp + ctx.seconds
    for r in live:
        r.due += w_open
    in_window = [r for r in live if w_open <= r.due < w_close]
    misses0 = eng.metrics()["compile_misses"]
    opened, trace_at = False, w_close - ctx.trace_s
    while True:
        t = now()
        if not opened and t >= w_open:
            opened = True
            ctx.open_window(t)
        if ctx.trace and not ctx.tracing and t >= trace_at and t < w_close:
            ctx.start_trace()
        if nxt < len(live) and live[nxt].due <= t:
            with ctx.span("add_requests"):
                while nxt < len(live) and live[nxt].due <= t:
                    inject(live[nxt])
                    nxt += 1
        if t >= w_close:
            if ctx.tracing:
                ctx.stop_trace()
            waiting = [r for r in in_window if not r.tokens]
            if not waiting or t >= w_close + drain:
                break
        if eng.pending():
            with ctx.span("engine_step"):
                eng.step()
        else:
            pause = (live[nxt].due - now()) if nxt < len(live) else 0.001
            time.sleep(min(max(pause, 0.0), 0.001))
    t_end = now()
    if traffic["arrival"] == "backlog":     # it must never drain
        ctx.check("backlog_requests_left_at_close",
                  sum(1 for r in live if len(r.tokens) < r.out_len), None,
                  at_least=1)
    ctx.close_window(compiles=eng.metrics()["compile_misses"] - misses0)
    return live, in_window, (t_begin, w_open, w_close, t_end)


def whole_ticks(tracer, t_begin, w_open, w_close):
    """(the ticks since the ramp began, those the rate counts, the seconds
    they took).  Whole ticks only: from the end of the tick in flight
    when the window opened to the end of the one in flight when it
    closed."""
    ticks = [dict(e, end=tracer.t0 + e["ts"],
                  start=tracer.t0 + e["ts"] - e["dur_s"])
             for e in tracer.events("tick") if e.get("budget_used")
             and tracer.t0 + e["ts"] - e["dur_s"] >= t_begin]
    ends = [k["end"] for k in ticks]
    a = min([e for e in ends if e >= w_open], default=None)
    b = min([e for e in ends if e >= w_close], default=max(ends, default=0))
    counted = [k for k in ticks if a is not None and a < k["end"] <= b]
    return ticks, counted, (b - a) if counted else 0.0


def run(ctx):
    from paddle_tpu.telemetry import Tracer

    cfg, traffic = ctx.config, ctx.traffic
    ecfg = traffic["engine"]
    params = weights.make_gpt_params(cfg, ctx.seed, "bfloat16")
    tracer = Tracer(capacity=1 << 22)
    eng = program.build_engine(cfg, ecfg, params, tracer)
    with ctx.span("warm_up"):
        warm_up(eng, ecfg, cfg["vocab_size"])
    ctx.note(f"engine warmed: {eng.metrics()['compile_misses']} programs, "
             f"{time.monotonic() - ctx.t_start:.1f}s since start")

    live, in_window, (t_begin, w_open, w_close, t_end) = offer(
        ctx, eng, cfg["vocab_size"])

    # ------------------------------------------------------ end to end --
    ttft = [(r.times[0] - r.due) * 1e3 if r.tokens else math.inf
            for r in in_window]
    gaps = [(b - a) * 1e3 for r in live
            for a, b in zip(r.times, r.times[1:]) if w_open <= b < w_close]
    ticks, counted, span_s = whole_ticks(tracer, t_begin, w_open, w_close)
    e2e = {"ttft_p90_ms": stats.percentile(ttft, 90),
           "itl_p95_ms": stats.percentile(gaps, 95),
           "serve_tok_s": (sum(k["budget_used"] for k in counted) / span_s
                           if counted else None)}
    failed = sum(1 for x in ttft if math.isinf(x))
    inside = sum(1 for r in in_window if r.tokens
                 and (r.times[0] - r.due) <= 1.0
                 and all(y - x <= 0.2 for x, y in zip(r.times, r.times[1:])))
    ctx.note(f"requests due in window {len(in_window)} unserved {failed} "
             f"ttft_p50_ms {stats.percentile(ttft, 50)} itl_p50_ms "
             f"{stats.percentile(gaps, 50)} share_inside_1s_200ms "
             f"{inside / max(len(in_window), 1):.4f} gaps {len(gaps)} "
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
    obs["counters"].update({
        "blocks_high_water": eng.blocks_high_water,
        "pool_blocks": ecfg["num_blocks"], "preemptions": eng.preemptions,
        "ragged_steps": m["ragged_steps"], "mixed_steps": m["mixed_steps"],
        "events_dropped": tracer.events_dropped})
    note_rounds(ctx, counted)
    if ctx.trace and ctx.trace_window:
        # the buckets are the multiples of the block (program.build_engine)
        pads = {r.rid: -len(r.prompt) % ecfg["block_size"] for r in live}
        obs["ragged_ticks"] = traced_packs(ticks, ctx.trace_window, pads)
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
    # an open loop is judged by the requests due in the window, a backlog
    # by everything it was handed
    attempted = len(in_window) or sum(1 for r in live if r.rid is not None)
    return {"end_to_end": e2e, "attempted": attempted, "failed": failed}


def packed_rows(tick, pads):
    """[(real rows, keys the last of them attends)] per sequence of one
    ``tick`` event, as the engine recorded the pack (``rows``: ``[rid,
    rows, kv_end]``, the decode rows first).  The bucket's left-pad rows
    attend nothing and are taken out: a first chunk's rows include them,
    and a decode row's ``kv_end`` counts them as positions (``pads``:
    request id -> left-pad rows of its bucket; none where it is not
    known)."""
    out = []
    for i, (rid, n, kv) in enumerate(tick["rows"]):
        if i < tick.get("decode_rows", 0):
            kv -= pads.get(rid, 0)
        if kv > 0:
            out.append((min(n, kv), kv))
    return out


def traced_packs(ticks, window, pads):
    """The pack of every tick that lies wholly inside the traced window:
    what the kernel's traced calls worked on."""
    t0, t1 = window
    return [packed_rows(k, pads) for k in ticks
            if t0 <= k["start"] and k["end"] <= t1]


def note_rounds(ctx, counted):
    """The window's counted rounds, those that carry a prefill chunk and
    those of decode rows only: how many, their rows, seconds, p50 and
    max, and the seconds by phase.  Printed, no metric."""
    for kind, some in (("with a chunk", [k for k in counted
                                          if k.get("prefill_tokens")]),
                       ("decode only", [k for k in counted
                                        if not k.get("prefill_tokens")])):
        ms = sorted(k["dur_s"] * 1e3 for k in some)
        phases = {p: sum(k["phases"].get(p, 0.0) for k in some)
                  for p in (some[0]["phases"] if some else ())}
        ctx.note(f"counted rounds {kind}: {len(some)}, rows "
                 f"{sum(k['budget_used'] for k in some)}, "
                 f"{sum(ms) / 1e3:.3f}s, ms p50 "
                 f"{stats.percentile(ms, 50)} max {ms[-1] if ms else None}; "
                 "seconds by phase " + ", ".join(
                     f"{p} {v:.3f}" for p, v in phases.items()))


def check_served(ctx, cfg, params, done):
    """The widest gap by which a served token's logit lies below the
    reference's best, over a seeded sample of the requests the window
    finished, the longest among them."""
    if not done:
        ctx.check("served_requests_to_compare", 0, None, at_least=1)
        return
    rng = np.random.Generator(np.random.PCG64(ctx.seed))
    n = ctx.traffic.get("compare_requests", 6)
    longest = max(done, key=lambda r: len(r.prompt) + len(r.tokens))
    rest = [r for r in done if r is not longest]
    pick = [longest] + [rest[i] for i in
                        rng.permutation(len(rest))[:max(n - 1, 0)]]
    widest, mean, tokens = served_gap(cfg, params, pick)
    ctx.note(f"compared {len(pick)} requests, {tokens} served tokens, "
             f"longest {len(longest.prompt)}+{len(longest.tokens)}")
    ctx.check("served_logit_gap", widest, ctx.limits["served_logit_gap"])
    ctx.note(f"served_mean_gap {mean!r} (printed, not compared: over a few "
             f"hundred tokens it counts a handful of near-ties)")
    if ctx.control:
        widest, mean, _ = served_gap(cfg, params, pick, lower="int8")
        ctx.note(f"control served_logit_gap {widest!r}")
        ctx.note(f"control served_mean_gap {mean!r}")


def served_gap(cfg, params, requests, lower=None, pad_to=512):
    """(widest gap, mean gap, tokens compared): by how much a served
    token's logit lies below the reference's best, at its widest and on
    average over the served tokens.  With ``lower`` the token compared at
    each position is the one the lower precision puts first, read against
    the float32 reference (the control of ``correct``)."""
    import jax
    import jax.numpy as jnp
    n_pos = cfg["n_positions"]
    out_pad = min(-(-max(len(r.tokens) for r in requests) // 128) * 128,
                  n_pos)
    head = lambda p: p["wte"].astype(jnp.float32).T

    def one(params, ids, start, toks, lo, hi):
        def rows(lower):
            h = reference_gpt.hidden(cfg, params, ids[None], lower)[0]
            h = jax.lax.dynamic_slice_in_dim(h, start, out_pad, axis=0)
            return reference_gpt._matmul(h, head(params), lower)
        ref = rows(None)
        if lower is not None:
            toks = jnp.argmax(rows(lower), axis=-1)
        got = jnp.take_along_axis(ref, toks[:, None], axis=-1)[:, 0]
        at = jnp.arange(out_pad)
        gaps = jnp.where((at >= lo) & (at < hi), ref.max(-1) - got, 0.0)
        return gaps.max(), gaps.sum()

    fn = jax.jit(one)
    worst, summed, total = 0.0, 0.0, 0
    for r in requests:
        served = list(r.tokens)
        ids = r.prompt + served[:-1]
        L = min(max(-(-len(ids) // pad_to) * pad_to, out_pad), n_pos)
        # row ``start + j`` of the hidden states predicts served token j
        start = min(len(r.prompt) - 1, L - out_pad)
        lo = len(r.prompt) - 1 - start
        toks = np.zeros(out_pad, np.int32)
        toks[lo:lo + len(served)] = served
        ids = np.asarray(ids + [0] * (L - len(ids)), np.int32)
        g, gsum = fn(params, jnp.asarray(ids), start, jnp.asarray(toks),
                     lo, lo + len(served))
        worst, summed = max(worst, float(g)), summed + float(gsum)
        total += len(served)
    return worst, summed / total, total
