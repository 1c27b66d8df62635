"""The phases of the serving tick (``engine.admit`` ... ``engine.unpack``,
spans the engine itself puts on the host plane; ``benchmarks/lib/
xregion.py``), inside the traced window.

``{"phases": [...], "statistic": "p50"}``: per round, the milliseconds the
host spent in these phases; the statistic over the rounds wholly inside
the window.  ``{"phases": [...], "idle": true}``: the first device's idle
time that lies under these phases, as a percentage of the window.  The
idle time under every phase, in a round but outside its phases, and
outside any round adds to the window's idle time; the run's log gives all
of them.  None when the program records no such span.
"""

from benchmarks.lib import stats, xregion


def read(how, ctx):
    named = xregion.load(ctx)
    if named is None or not named.phases:
        return None
    window = named.t1 - named.t0
    if not ctx.obs.get("xphase_noted"):
        ctx.obs["xphase_noted"] = True
        idle = named.idle_by_phase()
        ctx.note("idle by phase, percent of the window: " + ", ".join(
            f"{n} {100.0 * ns / window:.3f}" for n, ns in idle.most_common())
            + f"; sum {100.0 * sum(idle.values()) / window:.3f}")
        rounds = named.tick_phase_ms()
        whole = [(e - s) / 1e6 for s, e, _ in named.ticks
                 if named.t0 <= s and e <= named.t1]
        ctx.note(f"host ms by phase, p50 over {len(rounds)} traced rounds: "
                 + ", ".join(
                     f"{p} {stats.percentile([r[p] for r in rounds], 50)}"
                     for p in xregion.PHASES)
                 + f", {xregion.TICK} {stats.percentile(whole, 50)}")
    if how.get("idle"):
        idle = named.idle_by_phase()
        return 100.0 * sum(idle[p] for p in how["phases"]) / window
    rounds = named.tick_phase_ms()
    if not rounds:
        return None
    return stats.percentile([sum(r[p] for p in how["phases"])
                             for r in rounds], int(how["statistic"][1:]))
