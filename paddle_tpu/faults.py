"""Deterministic, seeded fault injection for the serving control plane.

PR 11's simulation harness proved the method — a replica ``kill()`` under
a fake clock turns a minutes-long failure trajectory into a millisecond
CPU unit test — but death is only one failure mode, and hand-placed
``sim.at(t, engine.kill)`` calls don't compose into a reproducible chaos
scenario.  This module makes fault injection a first-class subsystem:

- a typed :class:`Fault` vocabulary covering the failure modes a real
  fleet sees — replica **crash** (frozen forever), **stall** (frozen for
  a window, then resumes), **slow** straggler (a latency multiplier for a
  window), transient **dispatch_error** (``add_request`` raises the
  retryable :class:`TransientDispatchError`), **warmup_fail** (the AOT
  warmup path raises), **garble** (a truncated/garbled token stream:
  the engine delivers a partial prefix, then its integrity check raises
  :class:`StreamCorruption` mid-tick), and **alloc_fail** (``step()``
  raises :class:`InjectedAllocationError`, a :class:`MemoryError` — the
  OOM shape that drives the flight recorder's memory forensics);
- a :class:`FaultPlan` — an ordered, seeded, JSON-able collection of
  faults, optionally targeted per replica name, so one plan describes a
  whole chaos scenario and the SAME plan replays the SAME scenario;
- a :class:`FaultyEngine` wrapper that injects the plan into any real
  engine's scheduling surface (``add_request`` / ``step`` / ``cancel`` /
  ``warmup``) without the engine's cooperation — it works on the three
  serving classes and on :class:`~paddle_tpu.simulation.SimEngine`
  alike, and everything else delegates through untouched.

All timing reads an injectable ``clock`` (``SimClock`` in tests, wall
clock in the ``tools/serve_gateway.py --chaos`` demo), so chaos
scenarios run deterministically through
:class:`~paddle_tpu.simulation.TrafficSim`.  Importing this module never
touches JAX — fault plans are host-side control flow only; no compiled
program changes under any fault.

The consumer of all this is the gateway's resilience layer
(``paddle_tpu.gateway.ResiliencePolicy``): circuit breakers open on the
dispatch errors injected here, retries/backoff absorb the transient
window, hedging races the slow straggler, and the stall/crash faults
drive the quarantine-replay path — docs/RESILIENCE.md walks the whole
classification.

No reference counterpart: the reference snapshot serves static batches
with no failure model at all (SURVEY §2.3).
"""

from __future__ import annotations

import json
import logging
import os
import random
from typing import Any, Callable, Dict, List, Optional, Sequence

__all__ = ["Fault", "FaultPlan", "FaultyEngine", "FAULT_KINDS",
           "TransientDispatchError", "StreamCorruption",
           "InjectedAllocationError", "FaultInjectionError",
           "torn_write", "corrupt_file"]

#: the typed fault vocabulary (docs/RESILIENCE.md classification table).
#: ``torn_write``/``corrupt_file`` are FILESYSTEM faults: FaultyEngine
#: never fires them; the checkpoint layer
#: (``train_resilience.CheckpointManager``) consults the plan at save
#: time with a save-ordinal clock and applies them via the
#: :func:`torn_write`/:func:`corrupt_file` primitives below.
FAULT_KINDS = ("crash", "stall", "slow", "dispatch_error", "warmup_fail",
               "garble", "alloc_fail", "torn_write", "corrupt_file")


class FaultInjectionError(RuntimeError):
    """Base class for every injected failure — lets a test assert "this
    came from the chaos layer, not from a real bug"."""


class TransientDispatchError(FaultInjectionError):
    """A RETRYABLE dispatch failure: the engine could not admit the
    request right now (transient device hiccup, allocator pressure, a
    flaky transport), but a later attempt — here or on another replica —
    may succeed.  The gateway's resilience layer catches exactly this
    class for its retry/backoff/circuit-breaker path; anything else an
    engine raises stays a structural (non-retryable) failure."""


class InjectedAllocationError(FaultInjectionError, MemoryError):
    """An injected device-allocation failure (the OOM shape).  Raised
    from ``step()`` BEFORE the inner engine runs — the tick's allocation
    "failed", no tokens moved.  Subclasses :class:`MemoryError` so the
    crash flight-recorder's OOM-forensics path (``telemetry_memory``'s
    ``forensics()`` section in :meth:`FlightRecorder.dump`) exercises
    under chaos exactly as it would under a real allocator failure,
    while tests can still assert the chaos-layer origin."""


class StreamCorruption(FaultInjectionError):
    """A token stream failed an integrity check mid-tick (the
    truncated/garbled-stream fault).  Raised from ``step()`` — the
    gateway's step-exception isolation quarantines the replica and
    replays its in-flight work after the documented
    ``on_token(gid, None, False)`` replay signal, so the partial prefix
    is discarded, never double-delivered."""


# ------------------------------------------------------------------------
# filesystem fault primitives (checkpoint chaos)
# ------------------------------------------------------------------------

def torn_write(path: str, rng: random.Random) -> int:
    """Truncate ``path`` at a seeded offset — the on-disk shape a crash
    mid-``write()`` leaves (a *torn* file: valid prefix, missing tail).
    The offset is drawn from ``rng`` in ``[1, size)`` so at least one
    byte survives and at least one byte is lost; returns the new size.
    Empty/1-byte files are truncated to 0."""
    size = os.path.getsize(path)
    keep = rng.randrange(1, size) if size > 1 else 0
    with open(path, "r+b") as f:
        f.truncate(keep)
    return keep


def corrupt_file(path: str, rng: random.Random, n_bytes: int = 4) -> int:
    """Flip ``n_bytes`` seeded byte positions in ``path`` (XOR with a
    seeded nonzero mask) — post-commit bitrot: the file exists, its size
    is right, its *content* is wrong, so only a content digest catches
    it.  Returns the number of bytes actually flipped."""
    size = os.path.getsize(path)
    if size == 0:
        return 0
    flipped = 0
    with open(path, "r+b") as f:
        for _ in range(max(1, int(n_bytes))):
            off = rng.randrange(size)
            f.seek(off)
            old = f.read(1)
            f.seek(off)
            f.write(bytes([old[0] ^ rng.randrange(1, 256)]))
            flipped += 1
    return flipped


class Fault:
    """One typed fault.  ``kind`` is one of :data:`FAULT_KINDS`; ``at_s``
    is the (injected-clock) second it arms; ``duration_s`` bounds the
    window for windowed kinds (``stall``/``slow``/``dispatch_error``/
    ``garble``; crash is forever by definition).  Kind-specific knobs:

    - ``slow``: ``factor`` — the latency multiplier (10 = a 10× slower
      straggler: one real scheduler round per ``factor`` driver ticks);
    - ``dispatch_error``: ``count`` — at most this many injected
      failures inside the window (None = every dispatch in the window);
    - ``warmup_fail``: ``count`` — the first N ``warmup()`` calls raise
      (time-independent: warmup happens before traffic);
    - ``garble``: ``count`` — at most N corruption events (each one
      raises :class:`StreamCorruption` after the tick's partial
      delivery);
    - ``alloc_fail``: ``count`` — at most N injected allocation
      failures (each ``step()`` in the window raises
      :class:`InjectedAllocationError` before the inner engine runs —
      the OOM shape the flight recorder's forensics dump is tested
      against);
    - ``torn_write`` / ``corrupt_file``: filesystem faults — never fired
      by :class:`FaultyEngine`; ``CheckpointManager`` consults them at
      save time with its save-ordinal clock (``at_s`` = save index) and
      applies :func:`torn_write` (truncate mid-save → the step stays
      uncommitted) or :func:`corrupt_file` (flip bytes *after* commit →
      only the digest verification in ``latest()`` catches it);
      ``count`` bounds how many saves are hit.

    ``replica=None`` matches every replica; a name targets one (the
    :meth:`FaultPlan.for_replica` selector)."""

    __slots__ = ("kind", "at_s", "duration_s", "factor", "count",
                 "replica")

    def __init__(self, kind: str, at_s: float = 0.0,
                 duration_s: Optional[float] = None, factor: float = 10.0,
                 count: Optional[int] = None,
                 replica: Optional[str] = None):
        if kind not in FAULT_KINDS:
            raise ValueError(f"unknown fault kind {kind!r}; choose from "
                             f"{FAULT_KINDS}")
        if float(at_s) < 0:
            raise ValueError("at_s must be >= 0")
        if duration_s is not None and float(duration_s) <= 0:
            raise ValueError("duration_s must be > 0")
        if float(factor) < 1.0:
            raise ValueError("slow factor must be >= 1")
        if count is not None and int(count) < 1:
            raise ValueError("count must be >= 1")
        self.kind = kind
        self.at_s = float(at_s)
        self.duration_s = None if duration_s is None else float(duration_s)
        self.factor = float(factor)
        self.count = None if count is None else int(count)
        self.replica = replica

    def active(self, now: float) -> bool:
        """Inside the fault's window at injected-clock ``now``?  A crash
        never ends; other kinds without ``duration_s`` are open-ended
        too (the plan author said "from t onward")."""
        if now < self.at_s:
            return False
        if self.duration_s is None:
            return True
        return now < self.at_s + self.duration_s

    def to_dict(self) -> Dict[str, Any]:
        return {k: getattr(self, k) for k in self.__slots__}

    @classmethod
    def from_dict(cls, d: Dict[str, Any]) -> "Fault":
        return cls(**{k: d[k] for k in cls.__slots__ if k in d})

    def __repr__(self):
        win = ("" if self.duration_s is None
               else f"+{self.duration_s:g}s")
        tgt = "" if self.replica is None else f" @{self.replica}"
        return f"Fault({self.kind}, t={self.at_s:g}{win}{tgt})"


class FaultPlan:
    """An ordered, seeded chaos scenario: the faults plus the seed any
    probabilistic consumer must draw from (:class:`FaultyEngine` derives
    a per-replica ``random.Random`` from it), so one plan value replays
    one trajectory.  JSON round-trips via :meth:`to_dict` /
    :meth:`from_dict` / :meth:`from_json` — the shape
    ``tools/serve_gateway.py --chaos`` parses."""

    def __init__(self, faults: Sequence[Fault] = (), seed: int = 0):
        self.faults: List[Fault] = sorted(faults, key=lambda f: f.at_s)
        self.seed = int(seed)

    def add(self, fault: Fault) -> "FaultPlan":
        self.faults.append(fault)
        self.faults.sort(key=lambda f: f.at_s)
        return self

    def for_replica(self, name: Optional[str]) -> List[Fault]:
        """The faults that target ``name`` (untargeted faults match
        every replica)."""
        return [f for f in self.faults
                if f.replica is None or f.replica == name]

    def rng(self, name: Optional[str] = None) -> random.Random:
        """A deterministic per-replica RNG: same plan seed + same
        replica name → same draw sequence, independent of every other
        replica's."""
        return random.Random(f"{self.seed}:{name}")

    def to_dict(self) -> Dict[str, Any]:
        return {"seed": self.seed,
                "faults": [f.to_dict() for f in self.faults]}

    @classmethod
    def from_dict(cls, d: Dict[str, Any]) -> "FaultPlan":
        return cls([Fault.from_dict(f) for f in d.get("faults", ())],
                   seed=d.get("seed", 0))

    @classmethod
    def from_json(cls, text: str) -> "FaultPlan":
        """Parse a plan from JSON: either the ``to_dict`` shape or a
        bare list of fault dicts."""
        data = json.loads(text)
        if isinstance(data, list):
            data = {"faults": data}
        return cls.from_dict(data)

    def __len__(self):
        return len(self.faults)

    def __repr__(self):
        return f"FaultPlan({self.faults!r}, seed={self.seed})"


class FaultyEngine:
    """Wrap any serving engine and inject a :class:`FaultPlan` into its
    scheduling surface (module docstring).  ``replica`` names this
    wrapper for fault targeting; ``clock`` is the injected timebase the
    fault windows read.  Everything not intercepted delegates to the
    inner engine (``tracer``, ``_free_slots``, metrics, prefix caches —
    the gateway sees the wrapper as the engine).

    Injection points:

    - ``step()``: a **crash**/**stall** window freezes the engine — the
      inner ``step`` is not called, so no tokens move and no tracer
      events appear (the gateway's stall health-check sees a silent
      replica and quarantines it, exactly like a wedged device).  A
      **slow** window forwards only every ``factor``-th call (the
      straggler shape hedging exists for).  A **garble** event forwards
      the tick — delivering that tick's partial token prefix — then
      raises :class:`StreamCorruption` (the gateway's step isolation
      quarantines + replays).
    - ``add_request()``: inside a **dispatch_error** window (while its
      ``count`` lasts) raises :class:`TransientDispatchError` BEFORE
      touching the inner engine — the retryable shape.
    - ``warmup()``: while **warmup_fail** has count left, raises.

    ``injected()`` reports what actually fired, for report honesty."""

    def __init__(self, engine, plan: FaultPlan,
                 clock: Callable[[], float], replica: Optional[str] = None,
                 logger: Optional[logging.Logger] = None):
        # object.__setattr__ not needed: __getattr__ only fires on misses
        self.engine = engine
        self.plan = plan
        self.replica = replica
        self._clock = clock
        self._log = logger if logger is not None \
            else logging.getLogger(__name__)
        self._faults = plan.for_replica(replica)
        self._rng = plan.rng(replica)
        self._slow_phase = 0
        self._spent: Dict[int, int] = {}     # id(fault) -> injections used
        self._injected: List[Dict[str, Any]] = []
        self.dead = False

    # ------------------------------------------------------------ helpers --

    def _active(self, kind: str, now: float) -> Optional[Fault]:
        for f in self._faults:
            if f.kind == kind and f.active(now):
                return f
        return None

    def _consume(self, fault: Fault) -> bool:
        """Use one injection from a counted fault; False when its count
        is exhausted (the fault stops firing)."""
        if fault.count is None:
            return True
        used = self._spent.get(id(fault), 0)
        if used >= fault.count:
            return False
        self._spent[id(fault)] = used + 1
        return True

    def _note(self, kind: str, **fields):
        self._injected.append({"kind": kind, "t": self._clock(), **fields})

    def injected(self) -> List[Dict[str, Any]]:
        """Every fault actually fired, in firing order — the ground
        truth a chaos report checks its scenario against."""
        return list(self._injected)

    # -------------------------------------------------- injected surface --

    def add_request(self, prompt, max_new_tokens: int, on_token=None,
                    **kwargs) -> int:
        now = self._clock()
        fault = self._active("dispatch_error", now)
        if fault is not None and self._consume(fault):
            self._note("dispatch_error")
            raise TransientDispatchError(
                f"injected dispatch failure (t={now:g})")
        return self.engine.add_request(prompt, max_new_tokens,
                                       on_token=on_token, **kwargs)

    def step(self):
        now = self._clock()
        if self.dead or self._active("crash", now) is not None:
            if not self.dead:
                self.dead = True          # a crash is forever
                self._note("crash")
            return
        if self._active("stall", now) is not None:
            if not self._injected or self._injected[-1]["kind"] != "stall":
                self._note("stall")
            return
        slow = self._active("slow", now)
        if slow is not None:
            self._slow_phase += 1
            if self._slow_phase % max(int(slow.factor), 1) != 0:
                # straggling: skip the real round, but show LIVENESS —
                # a straggler's scheduler loop is running (its tracer
                # heartbeats), it just delivers slowly; without this the
                # stall health-check would collapse slow into crash
                tr = getattr(self.engine, "tracer", None)
                if tr is not None and hasattr(tr, "tick"):
                    tr.tick(type(self.engine).__name__, 0.0, slow=True)
                return
        alloc = self._active("alloc_fail", now)
        if alloc is not None and self._consume(alloc):
            self._note("alloc_fail")
            raise InjectedAllocationError(
                f"injected allocation failure (t={now:g})")
        garble = self._active("garble", now)
        fire_garble = (garble is not None and self._pending_inner()
                       and self._consume(garble))
        out = self.engine.step()
        if fire_garble:
            self._note("garble")
            raise StreamCorruption(
                f"injected token-stream corruption (t={now:g})")
        return out

    def _pending_inner(self) -> bool:
        try:
            return bool(self.engine.pending())
        except Exception:  # noqa: BLE001 — a broken inner engine must not
            # mask the fault we were about to inject
            return True

    def warmup(self, *args, **kwargs):
        fault = self._active("warmup_fail", self._clock())
        if fault is not None and self._consume(fault):
            self._note("warmup_fail")
            raise FaultInjectionError("injected warmup failure")
        return self.engine.warmup(*args, **kwargs)

    def kill(self):
        """Imperative crash (the PR 11 ``SimEngine.kill`` shape) — for
        ``sim.at(t, engine.kill)``-style injections outside a plan."""
        self.dead = True
        self._note("crash", imperative=True)

    # ------------------------------------------------- transparent rest --

    def cancel(self, rid: int) -> bool:
        return self.engine.cancel(rid)

    def pending(self) -> bool:
        return self.engine.pending()

    def pop_finished(self) -> Dict[int, List[int]]:
        return self.engine.pop_finished()

    def __getattr__(self, name):
        # everything else — tracer, _free_slots, _queue, compile_grid,
        # metrics, prefix-cache internals — is the inner engine's
        return getattr(self.engine, name)

    def __repr__(self):
        return (f"FaultyEngine({type(self.engine).__name__}, "
                f"{len(self._faults)} fault(s), replica={self.replica!r})")
