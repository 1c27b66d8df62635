"""A sparse-attention kernel's share of its roofline, from the trace.

``{"kernels": [<source file>], "opcount": "index_scores" |
"sparse_latent"}``: ``xplane_kernel_latent``'s reading for the two kernels
of DeepSeek sparse attention — over the engine rounds (``engine.tick``
spans) wholly inside the traced window, the least time the chip could take
for each round's pack (``opcount_sparse`` over the ``rows`` the engine
recorded on that round's ``tick`` event, ``ctx.obs["sparse_ticks"]``,
times the layers) over the time of the kernel's calls that began inside
the round.  A round of a program too narrow for a selection (its table
holds no more than ``index_topk`` positions) calls neither kernel and is
left out in silence; any other round whose calls are not one per layer is
left out and the run's log says so.  None where the trace holds no such
kernel (a program without the mechanism), the driver left no rows, or the
program records no ``engine.tick`` span.
"""

from benchmarks.lib import harness, opcount, opcount_sparse, xregion


def least(how, cfg, rows):
    if how["opcount"] == "index_scores":
        return opcount_sparse.ragged_index_scores(
            rows, cfg["index_n_heads"], cfg["index_head_dim"])
    if how["opcount"] == "sparse_latent":
        return opcount_sparse.ragged_sparse_latent_attention(
            rows, cfg["index_topk"], cfg["num_attention_heads"],
            cfg["kv_lora_rank"], cfg["qk_rope_head_dim"])
    raise ValueError(f"unknown opcount {how['opcount']!r}")


def read(how, ctx):
    red = ctx.obs.get("xplane")
    ticks = ctx.obs.get("sparse_ticks")
    named = xregion.load(ctx)
    if red is None or not ticks or named is None or not named.ticks:
        return None
    calls = harness.load_module(
        "readers", "xplane_kernel_latent").kernel_calls(
            red, set(how["kernels"]))
    if not calls:
        return None
    cfg = ctx.config
    layers = cfg["num_hidden_layers"]
    total = seconds = 0.0
    used, by_side = 0, {"compute": 0.0, "memory": 0.0}
    for s, e, number in named.ticks:
        rows = ticks.get(number)
        if s < named.t0 or e > named.t1 or not rows:
            continue
        mine = [(a, b) for a, b in calls if s <= a < e]
        if len(mine) != layers:
            if mine:
                ctx.note(f"{how['opcount']} roofline: round {number} has "
                         f"{len(mine)} kernel calls, {layers} layers; "
                         f"left out")
            continue
        t, side = opcount.roofline_s(*least(how, cfg, rows),
                                     ctx.device_kind)
        total += layers * t
        by_side[side] += layers * t
        seconds += sum(b - a for a, b in mine) / 1e9
        used += 1
    if not used:
        return None
    ctx.note(f"{how['opcount']} roofline: least {total:.6f}s "
             f"({max(by_side, key=by_side.get)}-bound) over {seconds:.6f}s "
             f"in {used} rounds of {layers} calls")
    return 100.0 * total / seconds
