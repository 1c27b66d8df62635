"""The latent attention kernel's share of its roofline, from the trace.

``{"kernels": ["ragged_latent_attention"]}``: over the engine rounds
(``engine.tick`` spans) that lie wholly inside the traced window, the
least time the chip could take for each round's pack — the larger of
FLOPs over peak FLOP/s and bytes over peak bytes/s, counted by
``opcount_latent`` from the ``rows`` the engine recorded on that round's
``tick`` event (``ctx.obs["latent_ticks"]``: round number -> rows), times
the layers — over the time of the kernel's calls that began inside the
round (the Pallas calls issued from the named source files,
``xplane.kernel_of``).

The kernel's calls are read from the device's ``XLA Ops`` line itself, not
from the reduction's leaves: the compiler starts asynchronous copies of the
next layer's weights while a kernel runs, the reduction then sees an
operation inside the kernel's interval and no longer counts the kernel as
a leaf.  A round whose calls are not one per layer is left out, and the
run's log says so.  None where the trace holds no such kernel, the driver
left no rows, or the program records no ``engine.tick`` span.
"""

from benchmarks.lib import opcount, opcount_latent, xplane, xregion


def kernel_calls(red, stems):
    """[(start, end)] of the first device's calls of the Pallas kernels
    issued from the source files ``stems``, in the traced window."""
    import jax
    dev = red.devices[0].name
    meta = red.meta.get(dev, {})
    out = []
    for plane in jax.profiler.ProfileData.from_file(red.path).planes:
        if plane.name != dev:
            continue
        for line in plane.lines:
            if line.name != "XLA Ops":
                continue
            for ev in line.events:
                s, e = ev.start_ns, ev.start_ns + ev.duration_ns
                if red.t0 <= s and e <= red.t1 and xplane.kernel_of(
                        ev.name, meta.get(ev.name)) in stems:
                    out.append((s, e))
    return sorted(out)


def read(how, ctx):
    red = ctx.obs.get("xplane")
    ticks = ctx.obs.get("latent_ticks")
    named = xregion.load(ctx)
    if red is None or not ticks or named is None or not named.ticks:
        return None
    calls = kernel_calls(red, set(how["kernels"]))
    if not calls:
        return None
    cfg = ctx.config
    layers = cfg["num_hidden_layers"]
    least = seconds = 0.0
    used, by_side = 0, {"compute": 0.0, "memory": 0.0}
    for s, e, number in named.ticks:
        rows = ticks.get(number)
        if s < named.t0 or e > named.t1 or not rows:
            continue
        mine = [(a, b) for a, b in calls if s <= a < e]
        if len(mine) != layers:
            ctx.note(f"latent roofline: round {number} has {len(mine)} "
                     f"kernel calls, {layers} layers; left out")
            continue
        f, b = opcount_latent.ragged_latent_attention(
            rows, cfg["num_attention_heads"], cfg["kv_lora_rank"],
            cfg["qk_rope_head_dim"])
        t, side = opcount.roofline_s(f, b, ctx.device_kind)
        least += layers * t
        by_side[side] += layers * t
        seconds += sum(b - a for a, b in mine) / 1e9
        used += 1
    if not used:
        return None
    ctx.note(f"latent roofline: least {least:.6f}s "
             f"({max(by_side, key=by_side.get)}-bound) over {seconds:.6f}s "
             f"in {used} rounds of {layers} calls")
    return 100.0 * least / seconds
