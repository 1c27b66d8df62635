"""What the program itself names in a profiler trace: the regions of the
compiled programs and the phases of the serving tick.

Regions.  The program puts ``jax.named_scope`` round its layer boundaries
(``paddle_tpu/models/gpt.py``: embed / layers {attn {kv_write, <kernel>},
mlp} / head, ``optimizer`` in the step builders, each Pallas kernel under
its own name).  A scope reaches the trace as part of the operation's
``tf_op`` path (event metadata, which the reduction keeps), wrapped by whatever
transformed it: ``jit(step)/transpose(jvp(layers))/while/body/attn/
flash_attention/...``.  An operation belongs to the innermost region on
its path, with its own time (a ``while`` does not count its body's).  One
whose path names none is unscoped: the layout copies XLA puts round a
program, and the ``while`` itself, which this compiler gives a source line
and no path, so there is no container to inherit a region from.
Collectives are a class of their own whatever their path says: what the
reduction counts as one (``all-gather``, ``all-reduce`` ...) and also the
``async-collective-start`` / ``-done`` halves of those XLA made
asynchronous, whose own time is the core waiting for the transfer.

Phases.  With a ``Tracer`` attached the engine brackets each round with an
``engine.tick`` span and its five phases (``engine.admit`` ...
``engine.unpack``; ``paddle_tpu/telemetry.py PHASES``), each carrying the
round's number as the stat ``tick``.  They lie on the host plane, on the
device operations' clock.

Both are read on top of the one reduction (``xplane.Reduction``: its
window, its own times, its event metadata); only the host plane is read
from the file again, for the ``engine.*`` spans the reduction does not
keep.  A trace of a program that names neither gives ``None`` and raises
nothing.
"""

import collections
import re

from . import xplane

REGIONS = ("embed", "layers", "attn", "mlp", "kv_write", "head", "optimizer",
           "flash_attention", "ragged_paged_attention")
COLLECTIVE, UNSCOPED = "collective", "unscoped"
KERNELS = ("flash_attention", "ragged_paged_attention")
TICK = "engine.tick"
PHASES = ("engine.admit", "engine.pack", "engine.dispatch", "engine.sync",
          "engine.unpack")
IN_TICK, OUTSIDE = "tick_outside_phases", "outside_ticks"
_WORD = re.compile(r"[A-Za-z_][A-Za-z0-9_.\-]*")


def region_of(tf_op):
    """The innermost region an operation's ``tf_op`` path names, or
    None: the last word of the path that is a region's name, wherever the
    wrappers (``jvp(...)``, ``transpose(...)``, ``while/body``) put it."""
    for word in reversed(_WORD.findall(str(tf_op or ""))):
        if word in REGIONS:
            return word
    return None


class Named:
    """What the program names, on the window and the operations the one
    reduction cut: own time by region over the devices, the first device's
    idle gaps, and the host's engine spans."""

    def __init__(self, red):
        import jax
        self.t0, self.t1 = t0, t1 = red.t0, red.t1
        self.devices = len(red.devices)
        self.by_region = collections.Counter()      # region -> ns, summed
        self.ops = collections.defaultdict(collections.Counter)
        #                               region -> {short name: ns}, summed
        self.collective_regions = collections.Counter()
        self.kernel_ns = collections.Counter()      # kernel region -> ns
        self.kernel_calls = collections.Counter()
        for dev in red.devices:
            meta = red.meta.get(dev.name, {})
            region = {name: region_of((meta.get(name) or {}).get("tf_op"))
                      for name in dev.self_ns}
            for name, own in dev.self_ns.items():
                r = region[name]
                short = xplane.short_name(name, meta.get(name))
                if red.is_collective(dev, name) \
                        or short.startswith("async-collective"):
                    self.collective_regions[r or UNSCOPED] += own
                    r = COLLECTIVE
                self.by_region[r or UNSCOPED] += own
                self.ops[r or UNSCOPED][short] += own
            for s, e, name in dev.leaves:
                if region[name] in KERNELS and "tpu_custom_call" in name:
                    self.kernel_ns[region[name]] += e - s
                    self.kernel_calls[region[name]] += 1
        _, busy = xplane.union_ns([(s, e) for s, e, _ in
                                   red.devices[0].leaves])
        self.gaps, at = [], t0      # the first device's idle intervals
        for s, e in busy:
            if s > at:
                self.gaps.append((at, s))
            at = max(at, e)
        if at < t1:
            self.gaps.append((at, t1))
        self.ticks = []             # (start, end, tick number or None)
        self.phases = []            # (start, end, name, tick number)
        for plane in jax.profiler.ProfileData.from_file(red.path).planes:
            if plane.name != "/host:CPU":
                continue
            for line in plane.lines:
                for ev in line.events:
                    if not ev.name.startswith("engine."):
                        continue
                    s, e = ev.start_ns, ev.start_ns + ev.duration_ns
                    if e <= t0 or s >= t1:
                        continue
                    number = dict(ev.stats).get("tick")
                    if ev.name == TICK:
                        self.ticks.append((s, e, number))
                    elif ev.name in PHASES:
                        self.phases.append((s, e, ev.name, number))
        self.ticks.sort()
        self.phases.sort()

    # ---------------------------------------------------------- regions --

    @property
    def named(self):
        """Whether any operation of the trace lies in a region."""
        return any(r in REGIONS for r in self.by_region)

    def shares(self):
        """{region: percent of the devices' own time}, the unscoped rest
        and the collectives included; they add to 100."""
        total = sum(self.by_region.values())
        out = {r: 100.0 * ns / total for r, ns in self.by_region.items()}
        assert abs(sum(out.values()) - 100.0) < 1e-6, out
        return out

    def kernel_s(self, region):
        """(seconds, calls) of the Pallas kernel called under ``region``,
        mean over the devices: the kernel found by what it is called, not
        by the file it lives in."""
        k = max(self.devices, 1)
        return (self.kernel_ns[region] / k / 1e9,
                self.kernel_calls[region] / k)

    # ----------------------------------------------------------- phases --

    def tick_phase_ms(self):
        """[{phase name: milliseconds}] for every round wholly inside the
        window, phases joined to their round by its number (by containment
        where a trace carries none)."""
        out = []
        for s, e, number in self.ticks:
            if s < self.t0 or e > self.t1:
                continue
            acc = collections.Counter()
            for ps, pe, name, pn in self.phases:
                if (pn == number) if number is not None and pn is not None \
                        else (s <= ps and pe <= e):
                    acc[name] += (pe - ps) / 1e6
            out.append(acc)
        return out

    def idle_by_phase(self):
        """{phase name | IN_TICK | OUTSIDE: ns} of the first device's idle
        time: each gap is cut at the spans' edges, and each piece goes to
        the phase it lies under, else to the round, else outside.  (One
        gap runs from the end of a round's program through unpack, the
        caller's loop, admit and pack into the next dispatch: by its
        middle alone it would go to one of them whole.)  The values add
        to the idle time of the window."""
        def overlap(gap, spans):
            return sum(max(0, min(gap[1], b) - max(gap[0], a))
                       for a, b, *_ in spans)
        acc = collections.Counter()
        by_name = {name: [p for p in self.phases if p[2] == name]
                   for name in PHASES}
        for gap in self.gaps:
            under = 0
            for name, spans in by_name.items():
                ns = overlap(gap, spans)
                acc[name] += ns
                under += ns
            in_tick = max(overlap(gap, self.ticks) - under, 0)
            acc[IN_TICK] += in_tick
            acc[OUTSIDE] += (gap[1] - gap[0]) - under - in_tick
        return acc


def load(ctx):
    """The ``Named`` view of this run's trace, made once and kept in
    ``ctx.obs``; None where the run was not traced (or on the CPU)."""
    if "xregion" not in ctx.obs:
        red = ctx.obs.get("xplane")
        ctx.obs["xregion"] = None if red is None else Named(red)
    return ctx.obs["xregion"]
