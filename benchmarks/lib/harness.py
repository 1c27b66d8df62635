"""What every driver is given: the cell, the clock, the spans, the trace,
the checks, and the place where readers find what was observed."""

import contextlib
import json
import math
import os
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BENCH = os.path.join(ROOT, "benchmarks")


def load_json(*parts):
    with open(os.path.join(BENCH, *parts)) as f:
        return json.load(f)


def load_module(kind, name):
    """benchmarks/<kind>/<name>.py, found by name (names may hold '-')."""
    import importlib.util
    path = os.path.join(BENCH, kind, name + ".py")
    spec = importlib.util.spec_from_file_location(
        f"benchmarks.{kind}.{name.replace('-', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def merge(base, over):
    out = dict(base)
    for k, v in over.items():
        out[k] = merge(out[k], v) if isinstance(v, dict) \
            and isinstance(out.get(k), dict) else v
    return out


class Context:
    def __init__(self, args, cell, config, traffic, limits, t_start):
        self.workload, self.seed = args.workload, args.seed
        self.seconds, self.trace = float(args.seconds), bool(args.trace)
        self.rehearse = args.rehearse
        self.control = getattr(args, "control", False)
        self.cell, self.chips = cell, cell["chips"]
        self.config, self.traffic, self.limits = config, traffic, limits
        self.t_start = t_start
        self.trace_s = min(float(traffic.get("trace_s", 4.0)), self.seconds)
        self.trace_dir = os.path.join(ROOT, ".bench_trace")
        self.tracing = False
        self.trace_window = None    # (start, end) on time.monotonic
        self.trace_path = None
        self.obs = {"series": {}, "counters": {}}
        self.checks = []
        self.setup_s = None
        self.memory_peak = None
        self._compiles = 0
        self._window_compiles = None
        self._bench_ann = None

    # ------------------------------------------------------------ notes --
    def note(self, text):
        print(f"[bench] +{time.monotonic() - self.t_start:.2f}s {text}",
              flush=True)

    def check(self, name, value, limit, at_least=None):
        """One number compared, beside its limit; printed in every run."""
        kind, bound = (("limit", limit) if at_least is None
                       else ("at_least", at_least))
        ok = bool(value <= bound if at_least is None else value >= bound)
        self.note(f"check {name}: {value!r} {kind.replace('_', ' ')} "
                  f"{bound!r} {'ok' if ok else 'FAILED'}")
        self.checks.append({"name": name, "value": value, kind: bound,
                            "ok": ok})
        return ok

    def checked(self):
        """Every check of the run in call order, as the result line
        carries them: ``{name: {"value", "limit" or "at_least", "ok"}}``.
        A value that is not finite (a ``nan`` gap) is ``null`` there and
        not ok: ``NaN`` is not JSON and would cost the reader the line."""
        out = {}
        for c in self.checks:
            c = dict(c)
            if not math.isfinite(c["value"]):
                c.update(value=None, ok=False)
            out[c.pop("name")] = c
        return out

    # ------------------------------------------------------------ spans --
    @contextlib.contextmanager
    def span(self, name):
        """A host span on the profiler's clock; free when not tracing."""
        if not self.tracing:
            yield
            return
        import jax
        with jax.profiler.TraceAnnotation(name):
            yield

    # ----------------------------------------------------------- window --
    def on_compile(self, event, **_):
        if event == "/jax/compilation_cache/compile_requests_use_cache":
            self._compiles += 1

    def open_window(self, t):
        self.setup_s = t - self.t_start
        self._window_compiles = self._compiles
        self.note(f"window opens, setup_s {self.setup_s:.3f}")

    def close_window(self, compiles):
        self.compiles_in_window = (self._compiles - self._window_compiles
                                   + compiles)

    def start_trace(self):
        import shutil
        import jax
        shutil.rmtree(self.trace_dir, ignore_errors=True)
        jax.profiler.start_trace(self.trace_dir)
        self.tracing = True
        self._bench_ann = jax.profiler.TraceAnnotation("bench_window")
        self._bench_ann.__enter__()
        self.trace_window = [time.monotonic(), None]

    def stop_trace(self):
        import jax
        from . import xplane
        self.trace_window[1] = time.monotonic()
        self._bench_ann.__exit__(None, None, None)
        jax.profiler.stop_trace()
        self.tracing = False
        self.trace_path = xplane.find_trace(self.trace_dir)

    def read_memory(self):
        import jax
        # what the chip holds at its fullest: the live arrays and what
        # the runtime has set aside for the running programs' temporaries
        # (``peak_bytes_in_use`` alone leaves those out on this runtime)
        stats = [d.memory_stats() or {} for d in jax.devices()[:self.chips]]
        self.memory_peak = max(
            s.get("peak_bytes_in_use", 0) + s.get("peak_bytes_reserved", 0)
            for s in stats)
        self.note(f"memory in use {stats[0].get('peak_bytes_in_use')} "
                  f"reserved {stats[0].get('peak_bytes_reserved')} "
                  f"limit {stats[0].get('bytes_limit')}")
        self.obs["counters"]["memory_peak_bytes"] = self.memory_peak
