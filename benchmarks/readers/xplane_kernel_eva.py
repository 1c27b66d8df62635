"""The EVA attention kernel's share of device time and of its roofline,
from the trace.

``{"kernels": [<source file>], "as": "roofline" | "share"}``:
``xplane_kernel_latent``'s reading for ``ops/ragged_eva_attention.py`` —
over the engine rounds (``engine.tick`` spans) wholly inside the traced
window, the least time the chip could take for each round's pack
(``opcount_eva`` over the ``rows`` the engine recorded on that round's
``tick`` event, ``ctx.obs["eva_ticks"]``, times the layers) over the time
of the kernel's calls that began inside the round (the Pallas calls issued
from the named source file; ``ops/eva_summarize.py``'s are read by
``xplane_scope``).
``"share"``: the same calls' time over the device's busy time in the
traced window.  None where the trace holds no such kernel (a program
without the mechanism), the driver left no rows, or the program records no
``engine.tick`` span.
"""

from benchmarks.lib import harness, opcount, opcount_eva, xregion

def read(how, ctx):
    red = ctx.obs.get("xplane")
    ticks = ctx.obs.get("eva_ticks")
    named = xregion.load(ctx)
    if red is None or not ticks or named is None or not named.ticks:
        return None
    calls = harness.load_module(
        "readers", "xplane_kernel_latent").kernel_calls(
            red, set(how["kernels"]))
    if not calls:
        return None
    if how["as"] == "share":
        busy = red.busy_s()
        return 100.0 * sum(b - a for a, b in calls) / 1e9 / busy \
            if busy else None
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
            ctx.note(f"eva roofline: round {number} has {len(mine)} kernel "
                     f"calls, {layers} layers; left out")
            continue
        t, side = opcount.roofline_s(*opcount_eva.ragged_eva_attention(
            rows, cfg["num_attention_heads"],
            cfg["hidden_size"] // cfg["num_attention_heads"],
            cfg["window_size"], cfg["chunk_size"]), ctx.device_kind)
        total += layers * t
        by_side[side] += layers * t
        seconds += sum(b - a for a, b in mine) / 1e9
        used += 1
    if not used:
        return None
    ctx.note(f"eva roofline: least {total:.6f}s "
             f"({max(by_side, key=by_side.get)}-bound) over {seconds:.6f}s "
             f"in {used} rounds of {layers} calls")
    return 100.0 * total / seconds
