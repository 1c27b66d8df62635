"""Share of the devices' own time spent in the named regions of the
compiled programs (``benchmarks/lib/xregion.py``), in percent.

``{"regions": [...]}``: operations whose innermost region is one of these.
``{"except": [...]}``: everything else but the collectives, that is what no
metric of the cell's other regions reads: no scope at all, or a region too
small to have a metric (the run's log says which).  The regions, the rest
and the collectives add to 100.  None when the program names no region.
"""

from benchmarks.lib import xregion


def read(how, ctx):
    named = xregion.load(ctx)
    if named is None or not named.named:
        return None
    shares = named.shares()
    if not ctx.obs.get("xregion_noted"):
        ctx.obs["xregion_noted"] = True
        total = sum(named.by_region.values()) / 1e9 / named.devices
        ctx.note(f"device time by region, percent of {total:.3f}s own time "
                 "a device: " + ", ".join(f"{r} {v:.2f}" for r, v in sorted(
                     shares.items(), key=lambda kv: -kv[1])))
        for region in (xregion.UNSCOPED, "layers", xregion.COLLECTIVE):
            if named.ops.get(region):
                ctx.note(f"{region}: operations by own time, s a device: "
                         + ", ".join(
                             f"{n} {ns / 1e9 / named.devices:.4f}" for n, ns
                             in named.ops[region].most_common(6)))
        if named.collective_regions:
            ctx.note("collectives by the region their path names, s a "
                     "device: " + ", ".join(
                         f"{r} {ns / 1e9 / named.devices:.4f}" for r, ns in
                         named.collective_regions.most_common()))
        for k in xregion.KERNELS:
            s, calls = named.kernel_s(k)
            if calls:
                ctx.note(f"kernel under region {k}: {s:.6f}s in "
                         f"{calls:.0f} calls")
    if "regions" in how:
        return sum(shares.get(r, 0.0) for r in how["regions"])
    return sum(v for r, v in shares.items()
               if r not in how["except"] and r != xregion.COLLECTIVE)
