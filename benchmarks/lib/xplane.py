"""From a profiler trace (``.xplane.pb``) to numbers: the one reduction.

What it reads, as the JAX profiler writes it on a TPU: one plane per device
(``/device:TPU:<n>``) whose ``XLA Ops`` line holds every operation the core
ran, nested (a ``while`` contains its body's operations), and whose ``Async
XLA Ops`` line holds the spans of asynchronous copies and collectives; and
the host plane (``/host:CPU``), whose ``python3`` line holds the spans the
benchmark's files put round their calls with ``TraceAnnotation``.  All on
one clock, nanoseconds from the start of the trace.

An operation's name is its HLO text; what kind it is and where in the
source it comes from is in the event's metadata (``hlo_category``,
``source``), read by ``xproto``.  A Pallas kernel is a ``custom-call`` to
``tpu_custom_call`` and is known by the file that issues it
(``ops/ragged_paged_attention.py`` -> ``ragged_paged_attention``).
"""

import collections
import glob
import os
import re

from . import xproto

COLLECTIVE = re.compile(
    r"all-gather|all-reduce|reduce-scatter|collective-permute|all-to-all"
    r"|collective-broadcast")
_OPNAME = re.compile(r"^%?([A-Za-z_\-]+(?:[.\-_][A-Za-z_\-]+)*)")


def find_trace(logdir):
    """The newest ``.xplane.pb`` under a profiler log directory."""
    paths = sorted(glob.glob(os.path.join(
        logdir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {logdir}")
    return paths[-1]


def union_ns(intervals):
    """Total length of the union of (start, end) intervals, and the
    merged intervals in order."""
    merged = []
    for a, b in sorted(intervals):
        if merged and a <= merged[-1][1]:
            if b > merged[-1][1]:
                merged[-1][1] = b
        else:
            merged.append([a, b])
    return sum(b - a for a, b in merged), merged


def subtract_ns(intervals, holes):
    """Length of the union of ``intervals`` outside the union of
    ``holes``."""
    _, a = union_ns(intervals)
    _, b = union_ns(holes)
    total, j = 0, 0
    for s, e in a:
        at = s
        while j < len(b) and b[j][1] <= at:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > at:
                total += b[k][0] - at
            at = max(at, b[k][1])
            k += 1
        if at < e:
            total += e - at
    return total


def short_name(name, meta):
    """What a device operation is called in a breakdown: a kernel by the
    file it comes from, anything else by its HLO name without the
    number."""
    kernel = kernel_of(name, meta)
    if kernel:
        return kernel
    m = _OPNAME.match(name.split(" = ")[0])
    base = m.group(1) if m else name[:40]
    return re.sub(r"[.\-_]\d+$", "", base)


def kernel_of(name, meta):
    """The source file (without ``.py``) of a Pallas kernel's call, or
    None for any other operation."""
    if "tpu_custom_call" not in name:
        return None
    src = (meta or {}).get("source") or ""
    stem = os.path.basename(str(src).split(":")[0])
    return stem[:-3] if stem.endswith(".py") else (stem or "pallas_kernel")


class DeviceOps:
    """One device plane, cut to a window: leaf operations and spans."""

    def __init__(self, name):
        self.name = name
        self.leaves = []        # (start, end, event name) no op inside
        self.self_ns = collections.Counter()    # event name -> own time
        self.async_spans = []   # (start, end, event name)


class Reduction:
    """What the per-layer readers take their numbers from."""

    def __init__(self, path, window_span="bench_window", host_spans=()):
        import jax
        self.path = path
        self.meta = xproto.event_metadata(path)
        data = jax.profiler.ProfileData.from_file(path)
        self.devices = []
        self.spans = collections.defaultdict(list)   # name -> [(s, e)]
        wanted = set(host_spans) | {window_span}
        raw = {}
        for plane in data.planes:
            if plane.name.startswith("/device:TPU:"):
                raw[plane.name] = {
                    line.name: [(ev.start_ns, ev.start_ns + ev.duration_ns,
                                 ev.name) for ev in line.events]
                    for line in plane.lines
                    if line.name in ("XLA Ops", "Async XLA Ops")}
            elif plane.name == "/host:CPU":
                for line in plane.lines:
                    for ev in line.events:
                        if ev.name in wanted:
                            self.spans[ev.name].append(
                                (ev.start_ns, ev.start_ns + ev.duration_ns))
        if not raw:
            raise ValueError(f"no TPU device plane in {path}")
        if self.spans.get(window_span):
            self.t0 = min(s for s, _ in self.spans[window_span])
            self.t1 = max(e for _, e in self.spans[window_span])
        else:   # no marker: from the first to the last device operation
            evs = [e for lines in raw.values() for line in lines.values()
                   for e in line]
            self.t0 = min(e[0] for e in evs)
            self.t1 = max(e[1] for e in evs)
        for name in sorted(raw):
            self.devices.append(self._cut(name, raw[name]))

    # ------------------------------------------------------------ build --

    def _cut(self, name, lines):
        dev = DeviceOps(name)
        t0, t1 = self.t0, self.t1
        # by start, the longer first: a container comes before its body
        ops = sorted(((max(s, t0), min(e, t1), n)
                      for s, e, n in lines.get("XLA Ops", ())
                      if e > t0 and s < t1), key=lambda x: (x[0], -x[1]))
        stack = []      # open events: [start, end, name, covered, has body]

        def close(ev):
            dev.self_ns[ev[2]] += (ev[1] - ev[0]) - ev[3]
            if not ev[4]:
                dev.leaves.append((ev[0], ev[1], ev[2]))
        for s, e, n in ops:
            while stack and stack[-1][1] <= s:
                close(stack.pop())
            if stack:
                stack[-1][3] += min(e, stack[-1][1]) - s
                stack[-1][4] = True
            stack.append([s, e, n, 0, False])
        while stack:
            close(stack.pop())
        dev.async_spans = [(max(s, t0), min(e, t1), n)
                           for s, e, n in lines.get("Async XLA Ops", ())
                           if e > t0 and s < t1]
        return dev

    def _meta(self, dev, name):
        return self.meta.get(dev.name, {}).get(name)

    def is_collective(self, dev, name):
        cat = (self._meta(dev, name) or {}).get("hlo_category") or ""
        head = name.split(" = ")[0] + " " + str(cat)
        return bool(COLLECTIVE.search(head))

    # ---------------------------------------------------------- numbers --

    @property
    def window_s(self):
        return (self.t1 - self.t0) / 1e9

    def busy_s(self):
        """Seconds in which an operation ran, mean over the devices."""
        return sum(union_ns([(s, e) for s, e, _ in d.leaves])[0]
                   for d in self.devices) / len(self.devices) / 1e9

    def idle_share(self):
        return 1.0 - self.busy_s() / self.window_s

    def kernel_s(self, kernels):
        """Seconds inside the Pallas kernels issued from the named source
        files, and how many calls, mean over the devices."""
        total = calls = 0
        for d in self.devices:
            for s, e, n in d.leaves:
                if kernel_of(n, self._meta(d, n)) in kernels:
                    total += e - s
                    calls += 1
        k = len(self.devices)
        return total / k / 1e9, calls / k

    def collective_s(self):
        """(seconds in which a collective was in flight, seconds of those
        in which no other operation ran), mean over the devices."""
        held = exposed = 0
        for d in self.devices:
            coll = [(s, e) for s, e, n in d.leaves
                    if self.is_collective(d, n)]
            coll += [(s, e) for s, e, n in d.async_spans
                     if self.is_collective(d, n)]
            compute = [(s, e) for s, e, n in d.leaves
                       if not self.is_collective(d, n)]
            held += union_ns(coll)[0]
            exposed += subtract_ns(coll, compute)
        k = len(self.devices)
        return held / k / 1e9, exposed / k / 1e9

    def top_ops(self, n=10):
        """[[short name, seconds of own time]] over all devices' mean."""
        acc = collections.Counter()
        for d in self.devices:
            for name, ns in d.self_ns.items():
                acc[short_name(name, self._meta(d, name))] += ns
        k = len(self.devices)
        return [[name, ns / k / 1e9] for name, ns in acc.most_common(n)]

    def idle_gaps(self, n=10):
        """[[host span, seconds]]: the first device's idle time inside the
        window, each gap named by the benchmark's host span that covers
        its middle (``between_spans`` if none does)."""
        d = self.devices[0]
        _, busy = union_ns([(s, e) for s, e, _ in d.leaves])
        gaps, at = [], self.t0
        for s, e in busy:
            if s > at:
                gaps.append((at, s))
            at = max(at, e)
        if at < self.t1:
            gaps.append((at, self.t1))
        spans = sorted((s, e, name) for name, ivs in self.spans.items()
                       for s, e in ivs if name != "bench_window")
        acc = collections.Counter()
        for s, e in gaps:
            mid = (s + e) / 2
            inside = [(b - a, name) for a, b, name in spans if a <= mid < b]
            acc[min(inside)[1] if inside else "between_spans"] += e - s
        return [[name, ns / 1e9] for name, ns in acc.most_common(n)]
