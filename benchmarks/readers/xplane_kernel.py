"""The Pallas kernels issued from the named source files, from the trace.

``{"kernels": [...], "of": "busy"}``: their time over the device's busy
time.  ``{"kernels": [...], "of": "roofline", "opcount": ...}``: the least
time the chip could take for the traced calls (the larger of FLOPs over
peak FLOP/s and bytes over peak bytes/s, counted by
``benchmarks/lib/opcount.py`` from shapes) over their time.
"""

from benchmarks.lib import opcount
from benchmarks.lib.weights import gpt_dims


def read(how, ctx):
    red = ctx.obs.get("xplane")
    if red is None:
        return None
    seconds, calls = red.kernel_s(set(how["kernels"]))
    if seconds <= 0:
        return None
    if how["of"] == "busy":
        return 100.0 * seconds / red.busy_s()
    least = _LEAST[how["opcount"]](ctx, calls)
    if least is None:
        return None
    least_s, side = least
    ctx.note(f"{how['opcount']} roofline: least {least_s:.6f}s "
             f"({side}-bound) over {seconds:.6f}s in {calls:.0f} calls")
    return 100.0 * least_s / seconds


def _ragged(ctx, calls):
    ticks = ctx.obs.get("ragged_ticks")
    if not ticks:
        return None
    layers, hidden, heads, *_ = gpt_dims(ctx.config)
    total, by_side = 0.0, {"compute": 0.0, "memory": 0.0}
    for rows in ticks:
        f, b = opcount.ragged_paged_attention(rows, heads, hidden // heads)
        t, side = opcount.roofline_s(f, b, ctx.device_kind)
        total += layers * t
        by_side[side] += layers * t
    if abs(calls - layers * len(ticks)) > 0.5:
        ctx.note(f"ragged roofline: {calls} kernel calls traced, "
                 f"{layers * len(ticks)} rebuilt; left out")
        return None
    return total, max(by_side, key=by_side.get)


def _flash(ctx, calls):
    train = ctx.obs.get("train")
    if not train:
        return None
    _, hidden, heads, *_ = gpt_dims(ctx.config)
    f, b = opcount.flash_attention(train["batch"] / ctx.chips,
                                   train["seq_len"], heads, hidden // heads)
    t, side = opcount.roofline_s(f, b, ctx.device_kind)
    return t * calls / 3.0, side     # forward, dQ, dK/dV: three calls


_LEAST = {"ragged_paged": _ragged, "flash": _flash}
