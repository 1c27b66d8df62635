"""A serving round in a profiler trace: what kind it was, and what the
chip waited for.

On top of ``xregion.Named`` (the window, the first device's idle gaps, the
``engine.tick`` spans); the host plane is read once more for what ``Named``
does not keep: the children of the phases (``paddle_tpu/telemetry.py
PARTS``: ``engine.dispatch.operands`` / ``.key`` / ``.call``, which
partition ``engine.dispatch``, and ``engine.sync.stats``) and the stat
that every span opened after the pack carries beside ``tick``:
``chunk_rows``.  A round is "with a chunk" where it is > 0 and "decode
only" otherwise.

Only rounds wholly inside the window are read.  A program that records no
children or no kind (an older one) gives ``None`` wherever they are asked
for, and raises nothing.
"""

import bisect
import collections

from . import stats, xregion

DISPATCH, SYNC = "engine.dispatch", "engine.sync"
OPERANDS, KEY, CALL = (DISPATCH + ".operands", DISPATCH + ".key",
                       DISPATCH + ".call")
STATS = SYNC + ".stats"
DISPATCH_PARTS = (OPERANDS, KEY, CALL)
PARTS = DISPATCH_PARTS + (STATS,)
CHUNK, DECODE = "chunk", "decode"
KINDS = (CHUNK, DECODE)
# an idle gap this long (ns) lies between programs, not between one
# program's operations (those are a few microseconds apart)
BETWEEN_PROGRAMS_NS = 20_000


class Round:
    """One ``engine.tick`` and the spans that carry its number."""

    __slots__ = ("number", "start", "end", "kind", "spans")

    def __init__(self, number, start, end):
        self.number, self.start, self.end = number, start, end
        self.kind = None
        self.spans = collections.defaultdict(list)  # name -> [(start, end)]

    @property
    def wall_ms(self):
        return (self.end - self.start) / 1e6

    def host_ms(self, name):
        """Milliseconds the host spent under the spans of this name, or
        None where the round has none."""
        spans = self.spans.get(name)
        return sum(e - s for s, e in spans) / 1e6 if spans else None


class Rounds:
    def __init__(self, named, path):
        import jax
        self.t0, self.t1 = named.t0, named.t1
        self.window = named.t1 - named.t0
        # the idle gaps lie in order and apart, so the idle time up to a
        # moment is a sum of whole gaps and a part of one
        self._gap_start = [a for a, _ in named.gaps]
        self._gap_end = [b for _, b in named.gaps]
        self._before = [0]
        for a, b in named.gaps:
            self._before.append(self._before[-1] + b - a)
        by_number = {n: Round(n, s, e) for s, e, n in named.ticks
                     if n is not None and self.t0 <= s and e <= self.t1}
        for plane in jax.profiler.ProfileData.from_file(path).planes:
            if plane.name != "/host:CPU":
                continue
            for line in plane.lines:
                for ev in line.events:
                    if not ev.name.startswith("engine.") \
                            or ev.name == xregion.TICK:
                        continue
                    found = dict(ev.stats)
                    r = by_number.get(found.get("tick"))
                    if r is None:
                        continue
                    r.spans[ev.name].append(
                        (ev.start_ns, ev.start_ns + ev.duration_ns))
                    if r.kind is None and "chunk_rows" in found:
                        r.kind = CHUNK if int(found["chunk_rows"]) else DECODE
        self.rounds = sorted(by_number.values(), key=lambda r: r.start)

    # ------------------------------------------------------------- idle --

    def _idle_before(self, t):
        i = bisect.bisect_right(self._gap_start, t) - 1
        if i < 0:
            return 0
        return self._before[i] + min(t, self._gap_end[i]) - self._gap_start[i]

    def idle_ns(self, start, end):
        """The first device's idle time inside ``[start, end]``."""
        return self._idle_before(end) - self._idle_before(start)

    def idle_under(self, name, kind=None):
        """Idle nanoseconds under the spans of this name (``engine.tick``:
        the rounds' own extents) over the rounds of ``kind`` (all of
        them if None).  None where no such span was recorded, or where a
        kind is asked for and no round says its kind; 0 where the rounds
        say their kinds and none is of this one."""
        some = self.rounds if kind is None else self.of_kind(kind)
        if kind is not None and all(r.kind is None for r in self.rounds):
            return None
        spans = [(r.start, r.end) for r in some] if name == xregion.TICK \
            else [span for r in some for span in r.spans.get(name, ())]
        if not spans and kind is None:
            return None
        return sum(self.idle_ns(s, e) for s, e in spans)

    def sync_split(self, r):
        """(launch, middle, read-back) idle nanoseconds under the round's
        ``engine.sync``: the gap that is open when the host enters the
        span (the program has not begun), the gaps between the program's
        operations, and the gap that is open when the host leaves it (the
        chip has finished and the host is not yet awake; a span that is
        idle throughout counts here)."""
        launch = middle = back = 0
        for s, e in r.spans.get(SYNC, ()):
            i = bisect.bisect_right(self._gap_end, s)
            while i < len(self._gap_start) and self._gap_start[i] < e:
                a, b = max(self._gap_start[i], s), min(self._gap_end[i], e)
                if b == e:
                    back += b - a
                elif a == s:
                    launch += b - a
                else:
                    middle += b - a
                i += 1
        return launch, middle, back

    def call_lead_ns(self, r):
        """From the opening of the round's ``engine.dispatch.call`` to the
        first operation of the program it calls: the longest run of
        operations (idle gaps shorter than one between programs let pass)
        from the opening of ``engine.dispatch`` to the end of
        ``engine.sync``.  The host calls before the chip can begin, so a
        negative one is the trace's, not the round's: the device's line
        lies that much early against the host's, and idle time that fell
        under ``engine.dispatch.key`` reads under ``engine.sync``.  None
        where the round has no such spans or the chip ran nothing."""
        whole, call, sync = (r.spans.get(n) for n in (DISPATCH, CALL, SYNC))
        if not (whole and call and sync):
            return None
        lo, hi = whole[0][0], sync[0][1]
        i = bisect.bisect_right(self._gap_end, lo)
        at = lo if i < len(self._gap_start) and self._gap_start[i] > lo \
            else None
        runs = []                       # (length, start)
        while i < len(self._gap_start) and self._gap_start[i] < hi:
            a, b = self._gap_start[i], self._gap_end[i]
            if b - a >= BETWEEN_PROGRAMS_NS:
                if at is not None:
                    runs.append((a - at, at))
                at = b
            i += 1
        if at is not None and at < hi:
            runs.append((hi - at, at))
        return max(runs)[1] - call[0][0] if runs else None

    # ------------------------------------------------------------ rounds --

    def of_kind(self, kind):
        return [r for r in self.rounds if r.kind == kind]

    def decode_time_share(self):
        """Percent of the traced rounds' seconds spent in decode-only
        rounds; None where no round says what kind it was."""
        known = [r for r in self.rounds if r.kind is not None]
        if not known:
            return None
        return 100.0 * sum(r.wall_ms for r in known if r.kind == DECODE) \
            / sum(r.wall_ms for r in known)

    def partition(self):
        """(p50, widest, the widest's round number) of the relative distance
        between ``engine.dispatch`` and the sum of its parts over the
        rounds that have parts; None where none has."""
        off = [(abs(whole - sum(parts)) / whole, r.number)
               for r in self.rounds
               for whole, parts in [(r.host_ms(DISPATCH), [
                   r.host_ms(p) for p in DISPATCH_PARTS if p in r.spans])]
               if whole and parts]
        if not off:
            return None
        return (stats.percentile([o for o, _ in off], 50),) + max(off)

    def describe(self):
        """The log line: what is no metric."""
        pct = lambda ns: "none" if ns is None \
            else f"{100.0 * ns / self.window:.3f}"
        p50 = lambda xs: stats.percentile([x for x in xs if x is not None],
                                          50)
        out = [f"{len(self.rounds)} whole rounds"]
        for kind in KINDS:
            some = self.of_kind(kind)
            out.append(
                f"{kind}: {len(some)} rounds, "
                f"{sum(r.wall_ms for r in some) / 1e3:.3f}s, ms p50 "
                f"{p50(r.wall_ms for r in some)}, dispatch p50 "
                f"{p50(r.host_ms(DISPATCH) for r in some)} sum "
                f"{sum(r.host_ms(DISPATCH) or 0.0 for r in some) / 1e3:.4f}s"
                f", idle in them {pct(self.idle_under(xregion.TICK, kind))}")
        out.append("host ms p50 " + ", ".join(
            f"{p} {p50(r.host_ms(p) for r in self.rounds)}" for p in PARTS))
        out.append("idle under, percent of the window: " + ", ".join(
            f"{p} {pct(self.idle_under(p))}" for p in (DISPATCH,) + PARTS))
        split = [self.sync_split(r) for r in self.rounds]
        out.append("idle under engine.sync: " + ", ".join(
            f"{name} {pct(sum(s[i] for s in split))}" for i, name in
            enumerate(("launch", "between operations", "read-back"))))
        lead = [ns / 1e3 for ns in map(self.call_lead_ns, self.rounds)
                if ns is not None]
        if lead:
            out.append(f"the program's first operation after engine.dispatch"
                       f".call opens: us p50 {p50(lead):.1f}, least "
                       f"{min(lead):.1f} (negative: the device's line lies "
                       f"early against the host's, so read the idle under "
                       f".key and under engine.sync as a sum)")
        apart = self.partition()
        if apart is not None:
            out.append(f"parts against engine.dispatch: p50 "
                       f"{100.0 * apart[0]:.3f} percent, widest "
                       f"{100.0 * apart[1]:.3f}, round {apart[2]}")
        return "; ".join(out)


def load(ctx):
    """The ``Rounds`` of this run's trace, made once and kept in
    ``ctx.obs``; None where the run was not traced or the program records
    no round."""
    if "xround" not in ctx.obs:
        named = xregion.load(ctx)
        ctx.obs["xround"] = None if named is None or not named.ticks \
            else Rounds(named, ctx.obs["xplane"].path)
    return ctx.obs["xround"]
